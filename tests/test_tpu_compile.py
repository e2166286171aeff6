"""Ahead-of-time compiles of the serving kernels for a TPU v5e, at the
published widths of the served models, with no chip attached.

The TPU compiler (Mosaic) refuses what the Pallas interpreter accepts:
blocks whose last two dims are not (8, 128)-aligned, 1-D blocks whose
layout differs from XLA's, lane<->sublane reshapes.  These tests lower
each kernel against a described ``v5e:2x2`` topology, compile it for one
chip, and check that the Mosaic kernel (``tpu_custom_call``), not a
fallback, is what compiled.

Only one process may load the TPU library at a time, so the topology is
described inside a module-scoped fixture (never at import or collection
time) and every test compiles in this process.
"""
import os

import jax
import jax.numpy as jnp
import pytest

import repro.configs  # noqa: F401  (registers the model configs)
from repro.core.registry import get
from repro.kernels.attn_decode.kernel import decode_attention_pallas
from repro.kernels.conv1d.kernel import causal_conv1d_pallas
from repro.kernels.decode_fused.kernel import mamba2_decode_fused_pallas
from repro.kernels.flash.kernel import flash_attention_pallas
from repro.kernels.ssd.kernel import ssd_pallas

MAMBA2_ARCHS = ("mamba2-2.7b", "zamba2-2.7b")
SEQ = 4096          # the served max_seq
SLOTS = 8
CHUNK = 256         # the engine's prefill chunk


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    """Lower ``fn`` over ``(shape, dtype)`` pairs placed on the described
    chip, compile it for the TPU, and check the kernel is in the program."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


def _mamba2_widths(arch):
    cfg = get(arch)
    ssm = cfg.ssm
    h, p = ssm.n_ssm_heads(cfg.d_model), ssm.headdim
    g, n = ssm.n_groups, ssm.d_state
    c = ssm.d_inner(cfg.d_model) + 2 * g * n        # conv channels: x|B|C
    return ssm, h, p, g, n, c


@pytest.mark.parametrize("arch", MAMBA2_ARCHS)
def test_ssd_scan_compiles(one_chip, arch):
    ssm, h, p, g, n, _ = _mamba2_widths(arch)
    bf, f32 = jnp.bfloat16, jnp.float32
    _compile(lambda x, dt, a, bm, cm, d, h0: ssd_pallas(
        x, dt, a, bm, cm, d, chunk=ssm.chunk, initial_state=h0),
        one_chip, ((1, SEQ, h, p), bf), ((1, SEQ, h), f32), ((h,), f32),
        ((1, SEQ, g, n), bf), ((1, SEQ, g, n), bf), ((h,), f32),
        ((1, h, p, n), f32))


@pytest.mark.parametrize("arch", MAMBA2_ARCHS)
def test_mamba2_decode_fused_compiles(one_chip, arch):
    ssm, h, p, g, n, c = _mamba2_widths(arch)
    bf, f32 = jnp.bfloat16, jnp.float32
    k = ssm.conv_kernel
    _compile(lambda cs, ss, x, w, b, dt, dtb, al, d: mamba2_decode_fused_pallas(
        cs, ss, x, w, b, dt, dtb, al, d, n_groups=g, d_state=n, headdim=p),
        one_chip, ((SLOTS, k - 1, c), bf), ((SLOTS, h, p, n), f32),
        ((SLOTS, c), bf), ((c, k), f32), ((c,), f32), ((SLOTS, h), bf),
        ((h,), f32), ((h,), f32), ((h,), f32))


@pytest.mark.parametrize("arch", MAMBA2_ARCHS)
def test_conv1d_compiles(one_chip, arch):
    ssm, _, _, _, _, c = _mamba2_widths(arch)
    k = ssm.conv_kernel
    bf, f32 = jnp.bfloat16, jnp.float32
    _compile(lambda x, w, b, st: causal_conv1d_pallas(x, w, b,
                                                      initial_state=st),
             one_chip, ((1, SEQ, c), bf), ((c, k), f32), ((c,), f32),
             ((1, k - 1, c), bf))


def _shared_attn():
    a = get("zamba2-2.7b").shared_attn
    return a.n_heads, a.n_kv_heads, a.head_dim


def test_flash_prefill_compiles(one_chip):
    h, kvh, d = _shared_attn()
    bf = jnp.bfloat16
    _compile(lambda q, k, v, off: flash_attention_pallas(
        q, k, v, causal=True, q_offset=off),
        one_chip, ((4, h, CHUNK, d), bf), ((4, kvh, SEQ, d), bf),
        ((4, kvh, SEQ, d), bf), ((4,), jnp.int32))


def test_split_k_decode_attention_compiles(one_chip):
    h, kvh, d = _shared_attn()
    bf = jnp.bfloat16
    _compile(lambda q, k, v, vl: decode_attention_pallas(q, k, v,
                                                         valid_len=vl),
             one_chip, ((SLOTS, h, d), bf), ((SLOTS, kvh, 2 * SEQ, d), bf),
             ((SLOTS, kvh, 2 * SEQ, d), bf), ((SLOTS,), jnp.int32))
