"""Each kernel's FLOP and byte functions against a hand count at one
shape, the whole-step work against the weight tree, and the metric
readers on a made-up window."""
import jax
import numpy as np
import pytest

from chipbench_testlib import harness, tiny_cell


def mod(rel):
    return harness().module(rel)


def test_ssd_scan_hand_count():
    k = mod("kernels/ssd_scan.py")
    # b=1, s=256, h=2, p=4, n=3, g=1, q=128: 2 chunks of
    # 2 heads x (2*128^2*4 + 128^2 + 4*128*3*4 + 2*3*4 + 2*128*4)
    # + 1 group x 2*128^2*3
    assert k.flops(1, 256, 2, 4, 3, 1, 128) == 815200
    # x and y 8192, B and C 3072, dt 2048, two float32 states 192
    assert k.bytes_moved(1, 256, 2, 4, 3, 1) == 13504


def test_decode_fused_hand_count():
    k = mod("kernels/decode_fused.py")
    # 2 rows x (conv 2*10*4 + state 6*2*4*3 + skip 2*2*4)
    assert k.flops(2, 2, 4, 3, 10, 4) == 480
    # 2 rows x (state 192, window 120, xBC 20, y 16, dt 8) + taps, bias
    assert k.bytes_moved(2, 2, 4, 3, 10, 4) == 812


def _shapes(conf):
    ref = harness().module(conf["reference"])
    return jax.eval_shape(lambda: ref.init_weights(
        conf["model"], jax.random.PRNGKey(0)))


@pytest.mark.parametrize("cell", ["mamba2_longdoc_32k"])
def test_weight_bytes_match_the_weight_tree(cell):
    _, _, conf, _ = tiny_cell(cell)
    tree = _shapes(conf)
    work = mod("work.py")
    total = sum(int(np.prod(x.shape)) * 2 for x in jax.tree.leaves(tree))
    assert work.weight_bytes(conf["model"]) == total
    # the stacked layer leaves hold every layer's matrices
    mats = [x for p, x in jax.tree_util.tree_leaves_with_path(tree)
            if jax.tree_util.keystr(p).split("'")[-2] in (
                "wz", "wxBC", "wdt", "out_proj")]
    assert work.matmul_weights(conf["model"]) == sum(
        int(np.prod(x.shape)) for x in mats)


def test_step_work_counts_tokens_and_programs():
    _, _, conf, _ = tiny_cell("mamba2_longdoc_32k")
    work = mod("work.py")
    m = conf["model"]
    z = work.sizes(m)
    w, st = work.weight_bytes(m), 2 * work.row_state_bytes(z)
    # 512 tokens in 3 chunk programs of 256 tokens a row: every weight 3
    # times, a row's states twice
    f, b = work.prefill_work(m, 512, 3, 256)
    assert b == 3 * w + 2 * st
    assert f == 2 * work.prefill_work(m, 256, 1, 256)[0]
    assert f > 512 * 2 * work.matmul_weights(m)
    # 24 tokens in 8 steps: every weight 8 times, 24 rows' states
    f, b = work.decode_work(m, 24, 8)
    assert b == 8 * w + 24 * st
    assert f == 24 * work.decode_work(m, 1, 1)[0]
    assert work.decode_work(m, 0, 8) == (0, 8 * w)


def test_kernel_work_from_trace_shapes():
    _, _, conf, _ = tiny_cell("mamba2_longdoc_32k")
    m, s = conf["model"], conf["model"]["ssm"]
    ssd = mod("kernels/ssd_scan.py")
    key = ("jit_chunk_step/%ssd_core.7 = (bf16[4,8,256,16], "
           "f32[4,8,16,16]) custom-call")
    assert ssd.work(m, key) == (
        ssd.flops(4, 256, 8, 16, 16, s["n_groups"], s["chunk"]),
        ssd.bytes_moved(4, 256, 8, 16, 16, s["n_groups"]))
    m2 = mod("kernels/decode_fused.py")
    key = ("jit_decode_n/%decode_fused.4 = (bf16[4,8,16], "
           "f32[4,8,16,16]) custom-call")
    c = s["expand"] * m["d_model"] + 2 * s["n_groups"] * s["d_state"]
    assert m2.work(m, key) == (
        m2.flops(4, 8, 16, 16, c, s["conv_kernel"]),
        m2.bytes_moved(4, 8, 16, 16, c, s["conv_kernel"]))


M2 = "jit_decode_n/%decode_fused.4 = (bf16[1,8,16], f32[1,8,16,16]) " \
    "custom-call"


def _ctx(counters, ops, modules, window=1.0, busy=0.4):
    """A traced window: ``ops`` and ``modules`` map a name to (seconds,
    runs)."""
    return {"summary": {"window_s": window, "busy_s": busy, "devices": 1,
                        "ops": {k: v[0] for k, v in ops.items()},
                        "ops_n": {k: v[1] for k, v in ops.items()},
                        "modules": {k: v[0] for k, v in modules.items()},
                        "modules_n": {k: v[1] for k, v in modules.items()},
                        "idle_by_span": {}},
            "model": tiny_cell("mamba2_longdoc_32k")[2]["model"],
            "peak": {"flops_per_s": 1e9, "hbm_bytes_per_s": 1e9},
            "counters": dict({"ckpt_ms": 250.0, "prefill_tokens": 0,
                              "decode_tokens": 0}, **counters),
            "engine": {"chunk": 256, "decode_block": 8}}


def test_metric_readers():
    ctx = _ctx({"decode_tokens": 8}, {M2: (0.5, 16)},
               {"jit_decode_n": (0.8, 2)})
    assert mod("metrics/device_idle_share.py").read(ctx) == \
        pytest.approx(60.0)
    assert mod("metrics/checkpoint_share.py").read(ctx) == \
        pytest.approx(25.0)
    work = mod("work.py")
    f, b = work.decode_work(ctx["model"], 8, 16)
    assert mod("metrics/decode_step_mfu.py").read(ctx) == pytest.approx(
        100 * max(f, b) / 1e9 / 0.8)
    f, b = mod("kernels/decode_fused.py").work(ctx["model"], M2)
    assert mod("metrics/decode_fused_roofline.py").read(ctx) == \
        pytest.approx(100 * 16 * max(f, b) / 1e9 / 0.5)
    # a closed-loop cell's readers read the same numbers
    for name in ("checkpoint_share", "decode_step_mfu"):
        assert mod(f"metrics/{name}_closed.py").read(ctx) == \
            mod(f"metrics/{name}.py").read(ctx)
    # nothing to read: no prompt token prefilled, or no call of the kernel
    assert mod("metrics/prefill_step_mfu.py").read(ctx) is None
    assert mod("metrics/ssd_scan_roofline.py").read(ctx) is None


def test_shares_of_the_recorded_v5e_trace_stay_under_100():
    """The kernels' shares on 12 ms of a traced mamba2_longdoc_32k window
    on a TPU v5e, at the published sizes and the chip's peaks."""
    import json
    import os
    from chipbench_testlib import CHIP
    run = harness()
    trace = mod("trace.py")
    with open(os.path.join(CHIP, "tests", "data",
                           "trace_v5e_mamba2_longdoc.json")) as f:
        s = trace.reduce(json.load(f), [])
    conf = run.read_json("configs", "mamba2-2.7b.json")
    ctx = {"summary": s, "model": conf["model"],
           "peak": run.read_json("peaks.json")["TPU v5 lite"]}
    for name in ("ssd_scan_roofline", "decode_fused_roofline"):
        v = mod(f"metrics/{name}.py").read(ctx)
        assert 0 < v < 100, (name, v)
