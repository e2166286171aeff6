"""Replay checkpoints finished off the step loop: the engine gathers the
due slots on the device, a pool thread waits for their host copy and
packs each blob, and the step loop waits only where a blob is read (a
replay, the request's next checkpoint, an attached store).

A :class:`Gate` holds every pool task until the step loop starts to wait
for one, so each test sees a checkpoint that is still pending where it
is read."""
import sys
import threading
import time
from functools import lru_cache

import jax
import numpy as np
import pytest

from repro.core.config import AttnConfig, ModelConfig, SSMConfig
from repro.kernels import dispatch
from repro.models.lm import init_lm_params
from repro.serving import cache as cache_mod
from repro.serving.cache import (BLOB_META_KEY, finish_offload,
                                 offload_slot, offload_slots, start_offload)
from repro.serving.engine import Request, ServingEngine
from repro.serving.fault_inject import FaultPlan
from repro.serving.metrics import MetricsRegistry
from repro.serving.telemetry import Telemetry

KEY = jax.random.PRNGKey(0)
ARCHS = ("mamba2", "hybrid", "dense")
ENG_KW = dict(slots=2, max_seq=48, decode_block=4, chunk_size=8)


def _cfg(arch: str) -> ModelConfig:
    if arch == "dense":
        return ModelConfig(name="dense", family="dense", n_layers=2,
                           d_model=64, d_ff=128, vocab_size=97,
                           attn=AttnConfig(n_heads=4, n_kv_heads=2,
                                           head_dim=16),
                           layer_pattern=("dense",), vocab_pad_multiple=16)
    if arch == "mamba2":
        return ModelConfig(name="mamba2", family="ssm", n_layers=2,
                           d_model=64, d_ff=0, vocab_size=97,
                           ssm=SSMConfig(d_state=16, headdim=16, chunk=8),
                           layer_pattern=("mamba2",), vocab_pad_multiple=16)
    assert arch == "hybrid"
    return ModelConfig(name="hyb", family="hybrid", n_layers=4, d_model=64,
                       d_ff=0, vocab_size=97,
                       ssm=SSMConfig(d_state=16, headdim=16, chunk=8),
                       layer_pattern=("mamba2", "mamba2+shared"),
                       shared_attn=AttnConfig(n_heads=4, n_kv_heads=4,
                                              head_dim=16),
                       shared_attn_d_ff=128, vocab_pad_multiple=16)


@lru_cache(maxsize=None)
def _setup(arch: str):
    cfg = _cfg(arch)
    return cfg, init_lm_params(cfg, KEY)


def _prompt(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(2, cfg.vocab_size, int(n)).astype(np.int32)


class Gate:
    """Once ``hold`` is called, holds each pool task's pack until the
    step loop waits for a checkpoint.  As the engine's clock it hands out
    one pass for the reading ``ServingEngine._join_checkpoint`` takes
    just before it waits; ``open`` lets every task through."""

    def __init__(self):
        self.passes = threading.Semaphore(0)
        self.holding = False
        self.joins = 0

    def __call__(self):
        caller = sys._getframe(1)
        if (caller.f_code.co_name == "_join_checkpoint"
                and "t0" not in caller.f_locals):
            self.joins += 1
            self.passes.release()
        return time.monotonic()

    def hold(self):
        self.holding = True

    def open(self):
        self.holding = False
        self.passes.release(1000)


@pytest.fixture
def gate(monkeypatch):
    g = Gate()
    finalize = cache_mod._finalize_blob

    def held(*a, **k):
        if g.holding and threading.current_thread() \
                is not threading.main_thread():
            assert g.passes.acquire(timeout=60), "pool task never joined"
        return finalize(*a, **k)
    monkeypatch.setattr(cache_mod, "_finalize_blob", held)
    yield g
    g.open()


def _same_blob(a, b):
    assert set(a) == set(b)
    assert a[BLOB_META_KEY] == b[BLOB_META_KEY]
    for k in a:
        if k != BLOB_META_KEY:
            assert a[k].dtype == b[k].dtype and a[k].shape == b[k].shape
            assert a[k].tobytes() == b[k].tobytes(), k


@pytest.mark.parametrize("backend", ["ref", "interpret"])
@pytest.mark.parametrize("arch", ARCHS)
def test_async_blob_equals_offload_slot(arch, backend):
    """Every due slot's blob from the pool equals ``offload_slot``'s of
    the cache the checkpoint was taken from: keys, bytes and meta."""
    cfg, params = _setup(arch)
    with dispatch.use_backend(backend):
        eng = ServingEngine(cfg, params, checkpoint_every=2, **ENG_KW)
        taken = []
        checkpoint = eng._checkpoint

        def spy(it):
            before = [r and r._ckpt for r in eng.live]
            checkpoint(it)
            # the due slots, read before the burst donates the cache
            taken.append({
                b: (r, offload_slot(eng.cache, b, tags={
                    "rid": r.rid, "priority": r.priority}))
                for b, r in enumerate(eng.live)
                if r is not None and r._ckpt is not before[b]})
        eng._checkpoint = spy
        for rid, n in enumerate((9, 6)):
            eng.submit(Request(rid=rid, prompt=_prompt(cfg, n, rid),
                               max_new=14))
        compared = 0
        for _ in range(6):
            eng.step()
            for b, (req, want) in (taken.pop() if taken else {}).items():
                if eng.live[b] is req:      # not finished in the burst
                    assert req.ckpt_pos == int(want["pos"][0])
                    _same_blob(req.ckpt_blob, want)
                    compared += 1
        eng.run(max_iters=100)
    assert compared >= 4


@pytest.mark.parametrize("arch", ARCHS)
def test_offload_slots_is_start_then_finish(arch):
    cfg, params = _setup(arch)
    eng = ServingEngine(cfg, params, checkpoint_every=0, **ENG_KW)
    for rid, n in enumerate((9, 6)):
        eng.submit(Request(rid=rid, prompt=_prompt(cfg, n, rid), max_new=9))
    for _ in range(3):
        eng.step()
    tel, reg = Telemetry(trace_path=""), MetricsRegistry()
    blobs = offload_slots(eng.cache, [1, 0], tel, metrics=reg,
                          tags={0: {"rid": 0}, 1: {"rid": 1}})
    for b in (0, 1):
        _same_blob(blobs[b], offload_slot(eng.cache, b, tags={"rid": b}))
    parts = start_offload(eng.cache, [1])
    _same_blob(finish_offload(parts[1], tel), offload_slot(eng.cache, 1))
    moved = reg.snapshot()["metrics"][
        "repro_checkpoint_transfer_bytes_total"]["samples"][0]["value"]
    assert moved == sum(v.nbytes for blob in blobs.values()
                        for v in blob.values() if hasattr(v, "nbytes"))


def _waits(eng):
    """(waits, seconds waited) of the engine's counters."""
    snap = eng.metrics.snapshot()["metrics"]
    return tuple(sum(s["value"] for s in snap[name]["samples"])
                 for name in ("repro_checkpoint_waits_total",
                              "repro_checkpoint_wait_seconds_total"))


@pytest.mark.parametrize("arch", ARCHS)
def test_sentinel_trip_before_the_pack_joins_and_replays(arch, gate):
    """The admission checkpoint is still held on the pool when slot 0's
    first burst trips its sentinel: the replay waits for it, once, and
    the request ends bit-identical with a run without the fault."""
    cfg, params = _setup(arch)
    prompt = _prompt(cfg, 6, 7)
    ref = ServingEngine(cfg, params, **ENG_KW)
    ref.submit(Request(rid=0, prompt=prompt, max_new=13))
    want = ref.run(max_iters=100)[0].out

    gate.hold()
    eng = ServingEngine(cfg, params, clock=gate,
                        fault_plan=FaultPlan.from_spec(
                            "nan_decode@iter=0:slot=0"), **ENG_KW)
    eng.submit(Request(rid=0, prompt=prompt, max_new=13))
    eng.step()          # admit, checkpoint (held), poisoned burst, replay
    assert (eng.stats["divergences"], eng.stats["replays"]) == (1, 1)
    assert gate.joins == 1
    waits, seconds = _waits(eng)
    assert waits == 1 and seconds > 0
    gate.open()
    done = eng.run(max_iters=100)
    assert [(r.status, r.out) for r in done] == [("ok", want)]


def test_each_forced_join_counts_one_wait(gate):
    """With a checkpoint every step, each step's checkpoint waits for the
    request's previous one, which the gate holds until then."""
    cfg, params = _setup("mamba2")
    gate.hold()
    eng = ServingEngine(cfg, params, clock=gate, checkpoint_every=1,
                        **ENG_KW)
    eng.submit(Request(rid=0, prompt=_prompt(cfg, 6, 1), max_new=30))
    for k in range(4):
        eng.step()
        assert eng.stats["checkpoints"] == k + 1
        assert _waits(eng)[0] == gate.joins == k
    gate.open()
    eng.run(max_iters=100)


def test_finished_request_leaves_nothing_pending(gate):
    """A request that ends inside its first burst drops its pending
    checkpoint; the pool's threads end with ``run``."""
    cfg, params = _setup("mamba2")
    gate.hold()
    eng = ServingEngine(cfg, params, clock=gate, **ENG_KW)
    eng.submit(Request(rid=0, prompt=_prompt(cfg, 6, 2), max_new=3))
    eng.step()          # admission checkpoint held; three tokens, done
    req, = eng.finished
    assert req.status == "ok" and req._ckpt is None
    assert gate.joins == 0
    threads = list(eng._ckpt_pool._threads)
    assert threads
    gate.open()
    eng.run(max_iters=10)
    assert eng._ckpt_pool is None
    assert not any(t.is_alive() for t in threads)
