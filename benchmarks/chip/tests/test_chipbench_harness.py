"""The harness's own pieces on the CPU: the end-to-end statistics over
a window's requests, the host spans around the engine's calls, and a
run that compiles nothing inside its window."""
import time
import types

import pytest

from chipbench_testlib import harness, tiny_cell


def _window(records, t0=100.0, t1=110.0):
    win = types.SimpleNamespace(t0=t0, t1=t1, reqs={})
    for i, (arrival, deliveries) in enumerate(records):
        win.reqs[i] = {"arrival": arrival, "deliveries": deliveries,
                       "first": deliveries[0][0] if deliveries else None}
    return win


def test_ttft_counts_first_tokens_after_the_window():
    run = harness()
    # three requests sent in the window; the last one's first token
    # comes 5 s after the window closed
    win = _window([(100.0, [(101.0, 1), (101.5, 8), (102.0, 8)]),
                   (104.0, [(106.0, 1), (111.0, 8)]),
                   (108.0, [(115.0, 1), (115.5, 8)])])
    e = run.end_to_end(win, tok0=1000, tok1=1500)
    assert e["samples"] == {"ttft": 3, "itl": 2}
    assert e["ttft_p50_ms"] == pytest.approx(2000.0)
    # deliveries after the window count for neither the gaps nor tokens
    assert e["itl_p95_ms"] == pytest.approx(62.5)
    assert e["tokens_per_s"] == pytest.approx((500 + 18) / 10.0)


def test_percentile_over_all_samples():
    run = harness()
    assert run.percentile(list(range(101)), 95) == pytest.approx(95.0)
    assert run.percentile([3.0], 50) == 3.0
    assert run.percentile([], 50) is None


class _Engine:
    def __init__(self):
        self.calls = []
        self.inner = types.SimpleNamespace(step=lambda: self.calls.append(
            "inner"))

    def step(self):
        self.calls.append("step")
        return 7


def test_spans_wrap_the_calls_the_engine_has(capsys):
    run = harness()
    eng = _Engine()
    spans = run.Spans(eng, {"engine.step": "step",
                            "inner.step": "inner.step",
                            "gone": "_no_such_call",
                            "gone.too": "_nothing.step"})
    assert "gone: the engine has no _no_such_call" in capsys.readouterr().err
    assert eng.step() == 7
    spans.on = True
    assert eng.step() == 7
    eng.inner.step()
    assert eng.calls == ["step", "step", "inner"]


@pytest.mark.parametrize("cell", ["mamba2_longdoc_32k", "mamba2_chat_bursty"])
def test_nothing_compiles_in_the_window(cell):
    run = harness()
    bench, c, conf, mix = tiny_cell(cell)
    out = run.run_cell(bench, c, conf, mix, seed=2**32 + 17, seconds=1.5,
                       trace=False, limits={"max_logit_gap": 0.01,
                                            "requests_failed": 0},
                       per_layer=[], t_start=time.perf_counter())
    assert out["correct"], out["checks"]
    assert out["compiles_in_window"] == 0
    assert set(out["metrics"]) == {
        m["name"] for m in bench["end_to_end"]
        if cell in m.get("workloads", [cell])}
