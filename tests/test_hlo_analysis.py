"""HLO cost-analyzer tests: scan trip-count multiplication, class
attribution via named_scope, and dot-FLOP accounting."""
import jax
import jax.numpy as jnp

from repro.core.hlo_analysis import analyze_compiled, parse_hlo


def _compile(f, *specs):
    return jax.jit(f).lower(*specs).compile()


def test_scan_trip_count_multiplied():
    D, L = 256, 8
    x = jax.ShapeDtypeStruct((D, D), jnp.float32)
    w = jax.ShapeDtypeStruct((L, D, D), jnp.float32)

    def f_scan(x, w):
        return jax.lax.scan(lambda c, wi: (c @ wi, None), x, w)[0]

    def f_unroll(x, w):
        for i in range(L):
            x = x @ w[i]
        return x

    s1 = analyze_compiled(_compile(f_scan, x, w))
    s2 = analyze_compiled(_compile(f_unroll, x, w))
    expected = 2 * D * D * D * L
    assert abs(s1.flops - expected) / expected < 0.05
    assert abs(s1.flops - s2.flops) / expected < 0.05
    # XLA's own aggregate (known limitation): undercounts the scan body.
    xla = _compile(f_scan, x, w).cost_analysis().get("flops", 0)
    assert xla < 0.5 * expected


def test_scope_classification():
    D = 128
    x = jax.ShapeDtypeStruct((D, D), jnp.float32)

    def f(x):
        with jax.named_scope("ssm_core"):
            y = jnp.exp(x) * 2.0
        with jax.named_scope("mlp"):
            y = y @ y
        with jax.named_scope("norm"):
            y = y / jnp.sqrt(jnp.mean(y * y, -1, keepdims=True) + 1e-6)
        return y

    s = analyze_compiled(_compile(f, x))
    cls = s.by_class()
    assert cls.get("ssm", {}).get("flops", 0) > 0, "ssm scope missed"
    assert cls.get("gemm", {}).get("flops", 0) >= 2 * D * D * D * 0.9
    assert cls.get("norm", {}).get("flops", 0) > 0


def test_dot_flops_exact():
    M, K, N = 64, 128, 32
    a = jax.ShapeDtypeStruct((M, K), jnp.float32)
    b = jax.ShapeDtypeStruct((K, N), jnp.float32)
    s = analyze_compiled(_compile(lambda a, b: a @ b, a, b))
    gemm = s.by_class()["gemm"]["flops"]
    assert gemm == 2 * M * K * N


def test_bytes_nonzero_and_fusion_model():
    D = 512
    x = jax.ShapeDtypeStruct((D, D), jnp.float32)
    s = analyze_compiled(_compile(lambda x: jnp.tanh(x) * 2.0 + 1.0, x))
    # fused elementwise chain ≈ one kernel: read + write ≈ 2 * D*D*4
    assert s.bytes <= 3 * D * D * 4
    assert s.bytes >= 1.5 * D * D * 4


def test_parse_hlo_structure():
    D = 64
    x = jax.ShapeDtypeStruct((D, D), jnp.float32)
    txt = _compile(lambda x: x @ x, x).as_text()
    comps = parse_hlo(txt)
    assert "__entry__" in comps
    assert any(op.opcode == "dot" for ops in comps.values() for op in ops)
