"""falcon-h1-34b — Falcon-H1-34B-Instruct's parallel hybrid layer.
[hf: tiiuae/Falcon-H1-34B-Instruct config.json]  72L d_model=5120
d_ff=21504 vocab=261120, untied head, context 262144.  Every layer is
``hybrid_par``: one RMSNorm feeds GQA attention (20 query / 4 KV heads of
128, rope theta 1e11) and a Mamba-2 mixer (32 heads of 128, d_state 256,
2 groups, inner width ``mamba_d_ssm`` 4096 rather than expand x d_model)
side by side, their sum joins the residual, then a SwiGLU MLP.  The muP
multipliers are the config's own."""
from repro.core.config import AttnConfig, ModelConfig, MupConfig, SSMConfig
from repro.core.registry import register

CONFIG = register(ModelConfig(
    name="falcon-h1-34b",
    family="hybrid",
    n_layers=72,
    d_model=5120,
    d_ff=21504,
    vocab_size=261120,
    attn=AttnConfig(n_heads=20, n_kv_heads=4, head_dim=128,
                    rope_theta=1e11),
    ssm=SSMConfig(d_state=256, headdim=128, expand=2, n_groups=2,
                  conv_kernel=4, chunk=128, d_ssm=4096),
    mup=MupConfig(
        embedding=5.656854249492381,
        lm_head=0.0078125,
        attn_in=1.0,
        attn_out=0.0375,
        key=0.011048543456039804,
        ssm_in=0.25,
        ssm_out=0.08838834764831845,
        ssm=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
             0.3535533905932738),
        mlp=(0.1767766952966369, 0.011160714285714284)),
    layer_pattern=("hybrid_par",),
    norm_eps=1e-5,
    tie_embeddings=False,
), tags=("hybrid", "falcon-h1"))
