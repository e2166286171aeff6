"""Step spans and the engine's work-site counters: the spans
``ServingEngine`` emits at its layer boundaries (profiler annotations
always, records on the engine clock while a trace path is set), the
request-seconds it counts by state, and the bytes its checkpoints move
and keep."""
import sys
import threading

import jax
import numpy as np
import pytest

from repro.core.config import ModelConfig, SSMConfig
from repro.models.lm import init_lm_params
from repro.serving import telemetry as telemetry_mod
from repro.serving.engine import Request, ServingEngine
from repro.serving.metrics import MetricsRegistry
from repro.serving.telemetry import Telemetry, read_trace

KEY = jax.random.PRNGKey(0)
CFG = ModelConfig(name="mamba2", family="ssm", n_layers=2, d_model=64,
                  d_ff=0, vocab_size=97,
                  ssm=SSMConfig(d_state=16, headdim=16, chunk=8),
                  layer_pattern=("mamba2",), vocab_pad_multiple=16)


@pytest.fixture(scope="module")
def params():
    return init_lm_params(CFG, KEY)


class FakeClock:
    """Engine clock that moves only when the test moves it."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _prompt(n, seed=3):
    rng = np.random.default_rng(seed)
    return rng.integers(2, CFG.vocab_size, int(n)).astype(np.int32)


def _engine(params, **kw):
    kw = {"slots": 2, "max_seq": 64, "decode_block": 4, "chunk_size": 8,
          **kw}
    return ServingEngine(CFG, params, **kw)


def _counter(eng, name, **labels):
    for s in eng.metrics.snapshot()["metrics"][name]["samples"]:
        if s["labels"] == labels:
            return s["value"]
    return None


def test_one_step_records_the_six_spans_with_parents_and_rids(
        params, tmp_path):
    eng = _engine(params, trace_path=str(tmp_path / "trace.jsonl"))
    eng.submit(Request(rid=5, prompt=_prompt(6), max_new=9))
    eng.step()          # admits (one chunk), checkpoints, decodes
    assert eng.live[0].ckpt_blob is not None    # waits for the pool
    spans = {name: (start, end, parent, rids)
             for name, start, end, parent, rids in eng.telemetry.step_spans}
    assert set(spans) == {"engine.step", "prefill.chunk", "decode.burst",
                          "engine.checkpoint", "checkpoint.transfer",
                          "checkpoint.pack"}
    # the transfer and pack run on a pool thread, where no span is open
    assert {k: v[2] for k, v in spans.items()} == {
        "engine.step": None, "prefill.chunk": "engine.step",
        "decode.burst": "engine.step", "engine.checkpoint": "engine.step",
        "checkpoint.transfer": None, "checkpoint.pack": None}
    assert spans["engine.checkpoint"][0] <= spans["checkpoint.transfer"][0]
    assert spans["checkpoint.transfer"][1] <= spans["checkpoint.pack"][0]
    assert spans["prefill.chunk"][3] == [5]
    assert spans["decode.burst"][3] == [5]
    assert spans["engine.checkpoint"][3] == [5]
    for name, (start, end, parent, _) in spans.items():
        assert start <= end
        if parent is not None:     # a child lies inside its parent
            assert spans[parent][0] <= start and end <= spans[parent][1]
    # in order: the chunk, then the checkpoint, then the burst
    assert spans["prefill.chunk"][1] <= spans["engine.checkpoint"][0]
    assert spans["engine.checkpoint"][1] <= spans["decode.burst"][0]
    assert not eng.telemetry._open


def test_outputs_are_identical_with_recording_on_and_off(params, tmp_path):
    path = tmp_path / "trace.jsonl"
    outs = {}
    for on in (False, True):
        eng = _engine(params, trace_path=str(path) if on else "",
                      checkpoint_every=2)
        for i, n in enumerate((5, 13, 9)):
            eng.submit(Request(rid=i, prompt=_prompt(n, seed=i),
                               max_new=11))
        done = eng.run(max_iters=300)
        outs[on] = {r.rid: list(r.out) for r in done}
        assert all(r.status == "ok" for r in done)
        if not on:      # nothing kept, nothing written
            assert not eng.telemetry.step_spans
            assert not eng.telemetry._open
            assert eng.telemetry.write_step_spans() == 0
    assert outs[False] == outs[True]
    assert read_trace(str(path), type="step")


def test_run_writes_step_spans_beside_request_spans(params, tmp_path):
    path = str(tmp_path / "trace.jsonl")
    eng = _engine(params, trace_path=path)
    for i in range(2):
        eng.submit(Request(rid=i, prompt=_prompt(7 + i), max_new=6))
    eng.run(max_iters=200)
    assert not eng.telemetry.step_spans         # written out, emptied
    requests = read_trace(path)
    assert sorted(s["rid"] for s in requests) == [0, 1]
    assert all(s["type"] == "request" for s in requests)
    steps = read_trace(path, type="step")
    assert sum(s["name"] == "engine.step" for s in steps) \
        == eng.stats["iters"]
    for s in steps:
        assert set(s) == {"version", "type", "arch", "name", "start", "end",
                          "parent", "rids"}
        assert s["version"] == telemetry_mod.TRACE_SCHEMA_VERSION
        assert s["arch"] == "mamba2"
    burst_rids = {r for s in steps if s["name"] == "decode.burst"
                  for r in s["rids"]}
    assert burst_rids == {0, 1}


def test_the_buffer_keeps_the_newest_spans(monkeypatch, tmp_path):
    monkeypatch.setattr(telemetry_mod, "STEP_SPAN_BUFFER", 5)
    t = [0.0]
    tel = Telemetry(clock=lambda: t[0],
                    trace_path=str(tmp_path / "trace.jsonl"))
    for i in range(8):
        t[0] = float(i)
        with tel.span("engine.step", rids=[i]):
            pass
    assert [s[4] for s in tel.step_spans] == [[3], [4], [5], [6], [7]]
    assert tel.write_step_spans() == 5
    assert [s["rids"] for s in read_trace(str(tmp_path / "trace.jsonl"),
                                          type="step")] == [[3], [4], [5],
                                                            [6], [7]]


def test_spans_take_their_parent_on_their_own_thread(tmp_path):
    """Threads recording spans on one telemetry at once, the interpreter
    switching threads as often as it can: no span is lost, and each
    takes its parent from the spans open on its own thread."""
    tel = Telemetry(trace_path=str(tmp_path / "trace.jsonl"))
    n_threads, n_spans = 16, 100

    def work(i):
        for _ in range(n_spans):
            with tel.span(f"outer{i}"):
                with tel.span(f"inner{i}"):
                    pass
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert len(tel.step_spans) == 2 * n_threads * n_spans
    for name, _, _, parent, _ in tel.step_spans:
        want = None if name.startswith("outer") else \
            "outer" + name[len("inner"):]
        assert parent == want, (name, parent)


def test_timed_span_reads_the_clock_only_when_asked():
    reads = []

    def clock():
        reads.append(1)
        return float(len(reads))
    tel = Telemetry(clock=clock, trace_path="")
    with tel.span("engine.step"):
        pass
    assert not reads
    with tel.span("decode.burst", timed=True) as sp:
        pass
    assert (sp.start, sp.end) == (1.0, 2.0)
    assert not tel.step_spans


def test_request_seconds_are_integrated_by_state(params):
    clock = FakeClock()
    eng = _engine(params, slots=1, clock=clock, checkpoint_every=0)
    eng.submit(Request(rid=0, prompt=_prompt(12), max_new=40))
    clock.t = 1.0
    eng.submit(Request(rid=1, prompt=_prompt(5), max_new=4))
    # 0-1 s: one queued; then two
    clock.t = 3.0
    eng.step()          # r0 prefills its first 8 of 12 tokens
    assert (len(eng.queue), eng._open_pending()) == (1, 1)
    clock.t = 3.5
    eng.step()          # r0's last chunk: it decodes
    assert len(eng.queue) == 1 and eng.live[0] is not None
    clock.t = 4.5
    eng.step()
    got = {s: _counter(eng, "repro_request_seconds_total", state=s)
           for s in ("queued", "prefill", "decode")}
    assert got == pytest.approx({"queued": 1.0 + 2 * 2.0 + 0.5 + 1.0,
                                 "prefill": 0.5, "decode": 1.0})


def test_preempted_requests_count_as_queued(params):
    clock = FakeClock()
    eng = _engine(params, slots=1, clock=clock, preempt_after=1)
    eng.submit(Request(rid=0, prompt=_prompt(6), max_new=40))
    eng.submit(Request(rid=1, prompt=_prompt(6, seed=4), max_new=4))
    for _ in range(4):
        eng.step()
        if eng.stats["preemptions"]:
            break
    # r0 was offloaded and requeued behind r1: two queued
    assert [r.rid for r in eng.queue] == [1, 0]
    assert eng.live == [None]
    before = _counter(eng, "repro_request_seconds_total", state="queued")
    clock.t += 2.0
    eng.step()
    assert _counter(eng, "repro_request_seconds_total", state="queued") \
        == pytest.approx(before + 2.0 * 2)


def test_checkpoint_counts_bytes_moved_and_kept(params):
    eng = _engine(params, slots=4, metrics=MetricsRegistry())
    eng.submit(Request(rid=0, prompt=_prompt(6), max_new=20))
    eng.step()          # admission checkpoint of the one live slot
    assert eng.stats["checkpoints"] == 1
    whole = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(eng.cache))
    moved = _counter(eng, "repro_checkpoint_transfer_bytes_total")
    req = eng.live[0]
    kept = sum(v.nbytes for v in req.ckpt_blob.values()
               if hasattr(v, "nbytes"))
    assert _counter(eng, "repro_checkpoint_bytes_total") == kept
    # only the due slot moves: one slot of four, and its pos entry
    assert moved == kept
    assert kept == pytest.approx(whole / 4, rel=0.01)


def test_tokens_per_s_gauge_is_gone(params):
    eng = _engine(params)
    eng.submit(Request(rid=0, prompt=_prompt(6), max_new=6))
    eng.run(max_iters=50)
    assert "repro_tokens_per_s" not in eng.metrics.snapshot()["metrics"]
