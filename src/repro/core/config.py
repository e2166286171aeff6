"""Configuration dataclasses for models, workloads and hardware.

These are the inputs to the characterization flow (paper Fig. 4): the model
registry stores ``ModelConfig``s, the workload configuration is a
``WorkloadConfig``, and the roofline/energy models consume ``HardwareSpec``s.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Optional, Tuple


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclass(frozen=True)
class AttnConfig:
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10_000.0
    sliding_window: Optional[int] = None  # window size for "local" layers
    causal: bool = True                   # False for encoder-only (hubert)
    # "auto": dense masked attention for short seqs, chunked online-softmax
    # (flash-style) beyond ``dense_cutoff`` tokens.
    impl: str = "auto"
    dense_cutoff: int = 8192
    qk_norm: bool = False                 # qwen3-style per-head RMSNorm on q/k

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim


@dataclass(frozen=True)
class SSMConfig:
    d_state: int
    variant: str = "mamba2"   # "mamba2" (SSD) | "mamba1" (selective scan)
    headdim: int = 64         # mamba2 head dim (P)
    expand: int = 2
    n_groups: int = 1         # B/C groups (mamba2)
    conv_kernel: int = 4
    chunk: int = 128          # SSD chunk length (MXU-aligned)
    dt_rank: Optional[int] = None  # mamba1: rank of the dt projection
    # explicit SSM inner width (Falcon-H1's ``mamba_d_ssm``); None keeps
    # the Mamba-2 default ``expand * d_model``
    d_ssm: Optional[int] = None

    def d_inner(self, d_model: int) -> int:
        return self.d_ssm if self.d_ssm is not None else self.expand * d_model

    def n_ssm_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.headdim


@dataclass(frozen=True)
class MupConfig:
    """Falcon-H1's muP multipliers: fixed scalars applied to activations
    where the published model applies them.  ``ssm`` scales the Mamba
    in-projection's z | x | B | C | dt slices; ``mlp`` the gate projection
    (before its activation) and the down projection."""
    embedding: float = 1.0
    lm_head: float = 1.0
    attn_in: float = 1.0
    attn_out: float = 1.0
    key: float = 1.0
    ssm_in: float = 1.0
    ssm_out: float = 1.0
    ssm: Tuple[float, float, float, float, float] = (1.0,) * 5
    mlp: Tuple[float, float] = (1.0, 1.0)

    def __post_init__(self):
        # lists from a JSON file become tuples: configs key jit caches
        object.__setattr__(self, "ssm", tuple(self.ssm))
        object.__setattr__(self, "mlp", tuple(self.mlp))


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    experts_per_token: int
    d_ff_expert: int
    capacity_factor: float = 1.25
    interleave_step: int = 1     # MoE layer every k-th layer (llama4: 2)
    shared_expert: bool = False  # llama4-style always-on shared expert
    router_dtype: str = "float32"
    impl: str = "gshard"         # "gshard" einsum dispatch | "ragged" sort-based


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | ssm | hybrid | encoder | vlm | audio
    n_layers: int
    d_model: int
    d_ff: int
    vocab_size: int
    attn: Optional[AttnConfig] = None
    ssm: Optional[SSMConfig] = None
    moe: Optional[MoEConfig] = None
    # The repeating unit of layer kinds.  Layer kinds:
    #   "dense"       GQA attention + MLP
    #   "local"       sliding-window GQA attention + MLP
    #   "moe"         GQA attention + MoE FF
    #   "dense_moe"   dense layer at MoE interleave positions (llama4)
    #   "mamba2"      SSD block
    #   "mamba1"      selective-scan block
    #   "mamba2+shared"  mamba2 block followed by the shared attention block (zamba2)
    #   "encoder"     bidirectional attention + MLP (hubert)
    layer_pattern: Tuple[str, ...] = ("dense",)
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    act: str = "silu"
    frontend: str = "none"       # none | audio | vision
    # vision/audio stub: number of prefix embedding positions comes from the
    # workload; the frontend projects precomputed features of this dim.
    frontend_feature_dim: int = 0
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    vocab_pad_multiple: int = 256
    # Zamba2: one shared transformer block applied at "mamba2+shared" positions.
    shared_attn: Optional[AttnConfig] = None
    shared_attn_d_ff: int = 0
    # muP multipliers (Falcon-H1); None emits no multiply at all
    mup: Optional[MupConfig] = None
    scan_layers: bool = True     # scan-over-layers (compact HLO); False unrolls
    remat: str = "block"         # "none" | "block" (remat each scanned unit)
    # FSDP: shard the d_model dim of *params* over the data axis (ZeRO-3).
    # Used when attention heads don't divide the model axis (llama4: 40 heads)
    # so head-replicated attention weights would otherwise blow up HBM.
    fsdp: bool = False

    @property
    def padded_vocab(self) -> int:
        return _round_up(self.vocab_size, self.vocab_pad_multiple)

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """Full per-layer kind list of length n_layers."""
        reps = math.ceil(self.n_layers / len(self.layer_pattern))
        return (self.layer_pattern * reps)[: self.n_layers]

    def segments(self) -> Tuple[Tuple[Tuple[str, ...], int], ...]:
        """Decompose the layer list into (unit, n_repeat) scan segments."""
        kinds = self.layer_kinds
        unit = self.layer_pattern
        n_full, rem = divmod(self.n_layers, len(unit))
        segs = []
        if n_full:
            segs.append((unit, n_full))
        if rem:
            segs.append((tuple(kinds[-rem:]), 1))
        return tuple(segs)

    def param_count(self) -> int:
        """Analytic parameter count (embedding included once if tied)."""
        D, F, V = self.d_model, self.d_ff, self.vocab_size
        total = V * D  # embedding
        if not self.tie_embeddings:
            total += V * D
        total += D  # final norm
        for kind in self.layer_kinds:
            total += self._layer_params(kind)
        if self.shared_attn is not None:
            a = self.shared_attn
            total += (self.d_model * (a.q_dim + 2 * a.kv_dim)
                      + a.q_dim * self.d_model
                      + 3 * self.d_model * self.shared_attn_d_ff
                      + 2 * self.d_model)
        return total

    def _layer_params(self, kind: str) -> int:
        D, F = self.d_model, self.d_ff
        if kind in ("dense", "local", "encoder", "dense_moe"):
            a = self.attn
            attn = D * (a.q_dim + 2 * a.kv_dim) + a.q_dim * D
            mlp = 3 * D * F
            return attn + mlp + 2 * D
        if kind == "moe":
            a, m = self.attn, self.moe
            attn = D * (a.q_dim + 2 * a.kv_dim) + a.q_dim * D
            ff = m.n_experts * 3 * D * m.d_ff_expert + D * m.n_experts
            if m.shared_expert:
                ff += 3 * D * m.d_ff_expert
            return attn + ff + 2 * D
        if kind == "hybrid_par":
            # Falcon-H1/Hymba-style parallel heads: attention + SSM side by
            # side in the same layer, then an MLP.
            a, s = self.attn, self.ssm
            di = s.d_inner(D)
            ng, ns = s.n_groups, s.d_state
            nh = s.n_ssm_heads(D)
            conv_dim = di + 2 * ng * ns
            attn = D * (a.q_dim + 2 * a.kv_dim) + a.q_dim * D
            mamba = (D * (2 * di + 2 * ng * ns + nh) + conv_dim * s.conv_kernel
                     + nh * 3 + di + di * D)
            return attn + mamba + 3 * D * F + 2 * D
        if kind in ("mamba2", "mamba2+shared", "mamba1"):
            s = self.ssm
            di = s.d_inner(D)
            if s.variant == "mamba2" or kind.startswith("mamba2"):
                ng, ns = s.n_groups, s.d_state
                nh = s.n_ssm_heads(D)
                conv_dim = di + 2 * ng * ns
                return (D * (2 * di + 2 * ng * ns + nh)   # in_proj
                        + conv_dim * s.conv_kernel         # conv1d
                        + nh * 3                           # A_log, D, dt_bias
                        + di                               # gated norm
                        + di * D + D)                      # out_proj + layer norm
            # mamba1
            dtr = s.dt_rank or max(1, math.ceil(D / 16))
            return (D * 2 * di + di * s.conv_kernel + di
                    + di * (dtr + 2 * s.d_state) + dtr * di
                    + di * s.d_state + di + di * D + D)
        raise ValueError(f"unknown layer kind {kind!r}")

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: only routed experts)."""
        if self.moe is None:
            return self.param_count()
        total = self.param_count()
        m = self.moe
        n_moe_layers = sum(1 for k in self.layer_kinds if k == "moe")
        dead = (m.n_experts - m.experts_per_token) * 3 * self.d_model * m.d_ff_expert
        return total - n_moe_layers * dead


@dataclass(frozen=True)
class WorkloadConfig:
    """One characterization cell: what step is lowered at which shape."""
    name: str
    kind: str            # "train" | "prefill" | "decode"
    seq_len: int
    global_batch: int
    gen_len: int = 1     # decode: number of generated tokens modeled
    dtype: str = "bfloat16"

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch


# The four canonical shapes from the assignment.
TRAIN_4K = WorkloadConfig("train_4k", "train", seq_len=4096, global_batch=256)
PREFILL_32K = WorkloadConfig("prefill_32k", "prefill", seq_len=32768, global_batch=32)
DECODE_32K = WorkloadConfig("decode_32k", "decode", seq_len=32768, global_batch=128)
LONG_500K = WorkloadConfig("long_500k", "decode", seq_len=524288, global_batch=1)
SHAPES = {w.name: w for w in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}


@dataclass(frozen=True)
class HardwareSpec:
    """Per-chip capability used by roofline/energy models."""
    name: str
    peak_flops: float          # FLOP/s at the benchmark dtype
    hbm_bw: float              # bytes/s
    hbm_bytes: float           # capacity
    link_bw: float = 0.0       # bytes/s per ICI/NVLink link
    power_w: float = 0.0       # sustained board power for the energy model
    idle_w: float = 0.0

    def time_compute(self, flops: float) -> float:
        return flops / self.peak_flops

    def time_memory(self, bytes_: float) -> float:
        return bytes_ / self.hbm_bw


TPU_V5E = HardwareSpec("tpu_v5e", peak_flops=197e12, hbm_bw=819e9,
                       hbm_bytes=16e9, link_bw=50e9, power_w=170.0, idle_w=60.0)
RTX_4090 = HardwareSpec("rtx4090", peak_flops=165e12, hbm_bw=1008e9,
                        hbm_bytes=24e9, link_bw=32e9, power_w=450.0, idle_w=30.0)
JETSON_ORIN_NANO = HardwareSpec("jetson_orin_nano", peak_flops=20e12, hbm_bw=68e9,
                                hbm_bytes=8e9, link_bw=0.0, power_w=15.0, idle_w=5.0)
HARDWARE = {h.name: h for h in (TPU_V5E, RTX_4090, JETSON_ORIN_NANO)}
