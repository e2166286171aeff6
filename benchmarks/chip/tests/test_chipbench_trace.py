"""The trace reduction: busy union, idle gaps and their attribution to
host spans, matching kernels and programs by name."""
import json
import os

import numpy as np
import pytest

from chipbench_testlib import CHIP, harness

trace = None


@pytest.fixture(scope="module", autouse=True)
def _load():
    global trace
    trace = harness().module("trace.py")


SSD = ('%ssd_core.7 = (bf16[4,80,256,64]{3,2,1,0:T(8,128)(2,1)}, '
       'f32[4,80,64,128]{3,2,1,0:T(8,128)}) custom-call(bf16[4,80,256,64]'
       '{3,2,1,0} %bitcast.1), custom_call_target="tpu_custom_call"')
M2 = ('%decode_fused.4 = (bf16[4,80,64]{2,1,0}, f32[4,80,64,128]{3,2,1,0}) '
      'custom-call(bf16[4,3,80,64]{3,2,1,0} %reshape.1)')
OTHER = '%conv1d.7 = bf16[4,256,5376]{2,1,0} custom-call(%a, %w)'
LOOP = ('%while.3 = (s32[]{:T(128)}, bf16[4]{0}) while((s32[], bf16[4]) '
        '%tuple.1), condition=%c, body=%b')


def synthetic():
    ms = 1_000_000
    return {"host": [["window", 0, 100 * ms],
                     ["engine.step", 0, 60 * ms],
                     ["engine.checkpoint", 40 * ms, 58 * ms],
                     ["client.wait", 70 * ms, 100 * ms]],
            "devices": {"/device:TPU:0": {
                "ops": [[LOOP, 5 * ms, 35 * ms],
                        [SSD, 5 * ms, 15 * ms],
                        ["%fusion.1 = bf16[4,256]{1,0} fusion(%a), kind=kLoop",
                         10 * ms, 20 * ms],
                        [OTHER, 18 * ms, 20 * ms],
                        [M2, 25 * ms, 33 * ms],
                        ["%copy.1 = bf16[4,80,64]{2,1,0} copy(%y)",
                         33 * ms, 35 * ms],
                        ["%fusion.2 = f32[4]{0} fusion(%b)", 62 * ms, 64 * ms],
                        ["outside", 120 * ms, 130 * ms]],
                "modules": [["jit_chunk_step(1234)", 5 * ms, 20 * ms],
                            ["jit_decode_n(5678)", 25 * ms, 35 * ms]]}}}


def test_busy_union_and_gaps():
    s = trace.reduce(synthetic(), ["engine.step", "engine.checkpoint",
                                   "client.wait"])
    assert s["window_s"] == pytest.approx(0.100)
    # [5,20] u [25,35] u [62,64] ms: the loop holding [5,35] is left out,
    # the op after the window is clipped off
    assert s["busy_s"] == pytest.approx(0.027)
    idle = s["idle_by_span"]
    # gaps: [0,5] step, [20,25] step, [35,62] mid 48.5 -> checkpoint,
    # [64,100] mid 82 -> client.wait
    assert idle == pytest.approx({"engine.step": 0.010,
                                  "engine.checkpoint": 0.027,
                                  "client.wait": 0.036})
    assert sum(idle.values()) + s["busy_s"] == pytest.approx(s["window_s"])


def test_unattributed_gap_is_host_other():
    ev = synthetic()
    ev["host"] = ev["host"][:1]
    s = trace.reduce(ev, ["engine.step"])
    assert set(s["idle_by_span"]) == {"host_other"}


def test_op_key():
    assert trace.op_key(SSD) == ("%ssd_core.7 = (bf16[4,80,256,64], "
                                 "f32[4,80,64,128]) custom-call")
    assert trace.op_key(trace.op_key(SSD)) == trace.op_key(SSD)
    assert trace.op_key(LOOP).endswith(" while")
    assert trace.op_key("x" * 300) == "x" * 120


def test_kernel_and_program_seconds():
    s = trace.reduce(synthetic(), [])
    run = harness()

    def kernel(name):
        return trace.device_seconds(
            s, run.module(f"kernels/{name}.py").PATTERN)
    assert kernel("ssd_scan") == pytest.approx(0.010)
    assert kernel("decode_fused") == pytest.approx(0.008)
    assert trace.device_seconds(s, r"chunk_step", key="modules") \
        == pytest.approx(0.015)
    assert trace.runs(s, r"decode_n", key="modules") == {"jit_decode_n": 1.0}
    assert trace.runs(s, run.module("kernels/ssd_scan.py").PATTERN) == {
        "jit_chunk_step/" + trace.op_key(SSD): 1.0}
    assert not any("while" in k for k in s["ops"])
    b = trace.breakdown(s)
    assert b["device_ops"][0][0] == "jit_chunk_step/" + trace.op_key(SSD)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_runs_cut_by_the_window_count_by_their_share():
    ev = synthetic()
    ms = 1_000_000
    dev = ev["devices"]["/device:TPU:0"]
    # a decode burst from 90 to 110 ms: half of it lies in the window
    dev["ops"].append([M2, 90 * ms, 110 * ms])
    dev["modules"].append(["jit_decode_n(5678)", 90 * ms, 110 * ms])
    s = trace.reduce(ev, [])
    assert trace.runs(s, r"decode_n", key="modules") == {
        "jit_decode_n": pytest.approx(1.5)}
    key = "jit_decode_n/" + trace.op_key(M2)
    assert s["ops_n"][key] == pytest.approx(1.5)
    assert s["ops"][key] == pytest.approx(0.018)


def test_result_shapes():
    assert trace.result_shapes("jit_chunk_step/" + trace.op_key(SSD)) == [
        [4, 80, 256, 64], [4, 80, 64, 128]]
    assert trace.result_shapes(trace.op_key(LOOP)) == [[], [4]]
    assert trace.result_shapes("%x = (bf16[2,3], /*index=5*/f32[7]) "
                               "fusion") == [[2, 3], [7]]


def test_recorded_v5e_trace():
    """12 ms of a traced mamba2_longdoc_32k window on a TPU v5e: the end
    of a prefill chunk, the host's checkpoint and the start of a decode
    burst."""
    with open(os.path.join(CHIP, "tests", "data",
                           "trace_v5e_mamba2_longdoc.json")) as f:
        ev = json.load(f)
    s = trace.reduce(ev, ["engine.step", "prefill.chunk", "decode.burst",
                          "engine.checkpoint", "client.submit"])
    lo, hi = next((a, b) for n, a, b in ev["host"] if n == "window")
    # busy time at 1 us resolution, independently of the interval union
    busy = np.zeros(int((hi - lo) / 1000) + 1, bool)
    for name, a, b in ev["devices"]["/device:TPU:0"]["ops"]:
        if trace.op_key(name).endswith(" while") or b <= lo or a >= hi:
            continue
        busy[int((max(a, lo) - lo) / 1000):int((min(b, hi) - lo) / 1000)] = 1
    assert s["busy_s"] == pytest.approx(busy.sum() * 1e-6, abs=2e-5)
    assert s["window_s"] == pytest.approx(0.012)
    assert s["busy_s"] + sum(s["idle_by_span"].values()) == \
        pytest.approx(s["window_s"])
    # the host sat in the chunk's step (its host sync and argmax) while
    # the chip went idle between the chunk program and the decode burst
    assert max(s["idle_by_span"], key=s["idle_by_span"].get) == \
        "prefill.chunk"
    run = harness()
    assert trace.device_seconds(s, run.module(
        "kernels/ssd_scan.py").PATTERN) == pytest.approx(0.001919647)
    assert trace.device_seconds(s, run.module(
        "kernels/decode_fused.py").PATTERN) == pytest.approx(0.000942203)
    assert set(s["modules"]) == {"jit_chunk_step", "jit_decode_n",
                                 "jit_convert_element_type"}


def test_missing_window_is_an_error():
    ev = synthetic()
    ev["host"] = ev["host"][1:]
    with pytest.raises(ValueError):
        trace.reduce(ev, [])


def test_load_reads_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = trace.load(str(tmp_path))
    assert any(name == "window" for name, _, _ in ev["host"])
    assert ev["devices"] == {}          # a CPU run has no TPU plane
