"""The readers of the program's own spans and counters: known values on
small hand-built events and counters, nothing where their input is
absent, the counters as read from an engine's registry, and a CPU
profiler trace of one engine step holding the program's span names."""
import json
import os

import numpy as np
import pytest

from chipbench_testlib import CHIP, harness

MS = 1_000_000
READERS = ("checkpoint_transfer_share", "checkpoint_pack_share",
           "checkpoint_kept_share", "queue_wait_share")


def reader(name):
    return harness().module(f"metrics/{name}.py").read


def events():
    """100 ms: a step whose checkpoint moves the cache (idle 20-50 ms) and
    packs it (idle 50-70 ms), then a decode burst (busy 70-90 ms)."""
    return {"host": [["window", 0, 100 * MS],
                     ["engine.step", 0, 95 * MS],
                     ["engine.checkpoint", 15 * MS, 70 * MS],
                     ["checkpoint.transfer", 20 * MS, 50 * MS],
                     ["checkpoint.pack", 50 * MS, 70 * MS],
                     ["decode.burst", 70 * MS, 92 * MS]],
            "devices": {"/device:TPU:0": {
                "ops": [["%fusion.1 = f32[8]{0} fusion(%a)", 0, 20 * MS],
                        ["%fusion.2 = f32[8]{0} fusion(%b)", 70 * MS,
                         90 * MS]],
                "modules": [["jit_decode_n(1)", 70 * MS, 90 * MS]]}}}


def test_span_shares_on_hand_built_events():
    ctx = {"events": events()}
    # the one idle gap [20, 70] ms splits at 50 between transfer and pack
    assert reader("checkpoint_transfer_share")(ctx) == pytest.approx(30.0)
    assert reader("checkpoint_pack_share")(ctx) == pytest.approx(20.0)


def test_span_share_is_zero_where_the_span_left_the_chip_busy():
    ev = events()
    ev["devices"]["/device:TPU:0"]["ops"].append(
        ["%fusion.3 = f32[8]{0} fusion(%c)", 50 * MS, 70 * MS])
    assert reader("checkpoint_pack_share")({"events": ev}) == 0.0


def test_a_shorter_span_inside_takes_its_time():
    ev = events()
    # a shorter program span inside the transfer owns 30-40 ms; a longer
    # one around it takes nothing from it
    ev["host"] += [["prefill.chunk", 30 * MS, 40 * MS],
                   ["decode.burst", 10 * MS, 60 * MS]]
    assert reader("checkpoint_transfer_share")({"events": ev}) == \
        pytest.approx(20.0)
    # the window clips the span, and two devices are averaged
    ev = events()
    ev["host"][0] = ["window", 0, 40 * MS]
    ev["devices"]["/device:TPU:1"] = {
        "ops": [["%fusion.9 = f32[8]{0} fusion(%a)", 0, 40 * MS]],
        "modules": []}
    assert reader("checkpoint_transfer_share")({"events": ev}) == \
        pytest.approx(100.0 * 0.5 * 20 / 40)


def test_counter_shares_on_hand_built_counters():
    ctx = {"program_counters": {
        "repro_checkpoint_transfer_bytes_total": 8e9,
        "repro_checkpoint_bytes_total": 2e9,
        "repro_request_seconds_total{state=queued}": 6.0,
        "repro_request_seconds_total{state=prefill}": 6.0,
        "repro_request_seconds_total{state=decode}": 12.0}}
    assert reader("checkpoint_kept_share")(ctx) == pytest.approx(25.0)
    assert reader("queue_wait_share")(ctx) == pytest.approx(25.0)


@pytest.mark.parametrize("name", READERS)
def test_nothing_without_the_input(name):
    """The parent program has neither the spans nor the counters: its
    traced runs read nothing, and raise nothing."""
    read = reader(name)
    assert read({}) is None
    ev = events()
    ev["host"] = [h for h in ev["host"] if not h[0].startswith("checkpoint.")]
    assert read({"events": ev, "program_counters": {
        "repro_checkpoint_bytes_total": 1e9}}) is None
    idle = {"repro_checkpoint_transfer_bytes_total": 0.0,
            "repro_request_seconds_total{state=queued}": 0.0,
            "repro_request_seconds_total{state=prefill}": 0.0,
            "repro_request_seconds_total{state=decode}": 0.0}
    assert read({"program_counters": idle}) is None


def test_span_names_are_the_programs():
    with open(os.path.join(CHIP, "program_spans.json")) as f:
        names = json.load(f)["spans"]
    with open(os.path.join(CHIP, "spans.json")) as f:
        harness_names = json.load(f)["engine"]
    # the harness's engine spans keep their names inside the program
    assert set(harness_names) <= set(names)
    assert {"checkpoint.transfer", "checkpoint.pack"} <= set(names)


def _engine():
    import jax
    from repro.core.config import ModelConfig, SSMConfig
    from repro.models.lm import init_lm_params
    from repro.serving.engine import ServingEngine
    from repro.serving.metrics import MetricsRegistry
    cfg = ModelConfig(name="mamba2", family="ssm", n_layers=2, d_model=64,
                      d_ff=0, vocab_size=97,
                      ssm=SSMConfig(d_state=16, headdim=16, chunk=8),
                      layer_pattern=("mamba2",), vocab_pad_multiple=16)
    return ServingEngine(cfg, init_lm_params(cfg, jax.random.PRNGKey(0)),
                         slots=4, max_seq=64, decode_block=4, chunk_size=8,
                         metrics=MetricsRegistry())


def _prompt(n):
    return np.random.default_rng(n).integers(2, 97, n).astype(np.int32)


def test_program_counters_from_the_engine_registry():
    from repro.serving.engine import Request
    pc = harness().module("program_counters.py")
    eng = _engine()
    eng.submit(Request(rid=0, prompt=_prompt(6), max_new=12))
    c0 = pc.values(eng.metrics)
    eng.submit(Request(rid=1, prompt=_prompt(5), max_new=12))
    eng.step()
    d = pc.delta(c0, pc.values(eng.metrics))
    assert d["repro_submitted_total"] == 1
    assert d["repro_checkpoints_total"] == 2
    assert d["repro_checkpoint_transfer_bytes_total"] > 0
    assert 0 < d["repro_checkpoint_bytes_total"] \
        < d["repro_checkpoint_transfer_bytes_total"]
    assert d["repro_tokens_total{phase=prefill}"] == 11
    assert {"repro_request_seconds_total{state=queued}",
            "repro_request_seconds_total{state=prefill}",
            "repro_request_seconds_total{state=decode}"} <= set(d)
    assert not any(k.startswith(("repro_decode_burst_ms",
                                 "repro_queue_depth")) for k in d)
    ctx = {"program_counters": d}
    assert reader("checkpoint_kept_share")(ctx) == pytest.approx(
        100.0 * d["repro_checkpoint_bytes_total"]
        / d["repro_checkpoint_transfer_bytes_total"])


def test_a_cpu_trace_of_one_step_holds_the_program_spans(tmp_path):
    import jax
    from repro.serving.engine import Request
    trace = harness().module("trace.py")
    eng = _engine()
    eng.submit(Request(rid=0, prompt=_prompt(6), max_new=12))
    eng.step()                      # compile outside the trace
    eng.submit(Request(rid=1, prompt=_prompt(5), max_new=12))
    jax.profiler.start_trace(str(tmp_path))
    eng.step()
    jax.profiler.stop_trace()
    names = {name for name, _, _ in trace.load(str(tmp_path))["host"]}
    with open(os.path.join(CHIP, "program_spans.json")) as f:
        assert set(json.load(f)["spans"]) <= names
