"""Serving runtime: prefill / decode step builders + a slot-based batch
engine (continuous batching with interleaved chunked prefill and a
fault-tolerance layer).

``serve_step`` (the decode shape lowered by the dry-run) is one new token
against a KV/state cache of the workload's seq_len, exactly per the
assignment.  The engine keeps a fixed batch of slots; finished sequences
are replaced by newly prefilled prompts whose per-layer cache slices are
scattered into the batch cache.

Decode is the fused on-device loop (:func:`repro.models.lm.decode_tokens`):
each engine iteration advances every live slot by ``decode_block`` tokens
inside one compiled ``lax.scan`` — on-device argmax, a single
device->host transfer per block instead of one per token.  The cache
carries a per-slot ``pos`` vector, so slots admitted at different times
decode at their own offsets (no shared position counter).

Admission runs through the chunked-prefill subsystem
(:mod:`repro.serving.prefill`) for EVERY decodable architecture — dense,
rolling sliding-window, SSM, hybrid, windowed-hybrid: queued prompts of
heterogeneous lengths form one padded group, and every engine iteration
runs exactly ONE prefill chunk interleaved with the decode burst — a
57K-token prompt can no longer stall the decoding slots behind a
monolithic O(L) prefill.  Rolling-window layers prefill into their
ring-buffer caches chunk-by-chunk (modular scatter + ring-unrolling
mask); there is no separate one-shot admission pipeline anymore.

Scheduling DECISIONS — admission order, preemption urgency and victim
choice, deadline/starvation expiry, prefill interleave shares — are
delegated to a pluggable policy (:mod:`repro.serving.scheduler`:
``fifo`` / ``strict_tiers`` / ``weighted_fair`` over
``Request.priority`` classes, selected via ``REPRO_SCHED_POLICY``).
The engine keeps the MECHANISM: when the queue is starved of slots and
the policy names a victim, that slot is host-offloaded via
:mod:`repro.serving.cache` (the ring cursor travelling inside the
offloaded ``pos``, request identity and priority class riding in the
blob meta tags) and restored bit-exactly once a slot frees up.
Policies reorder work; they never change any request's decoded bytes.

Fault tolerance (:mod:`repro.serving.faults` is the taxonomy): every
request ends in a structured terminal state (``ok`` / ``failed`` /
``cancelled`` / ``timed_out``) on :attr:`ServingEngine.finished` — a
faulted request is quarantined and reported, never crashing the engine
or stranding its co-batched neighbours.  Decode bursts and prefill
chunks carry per-row on-device finiteness sentinels; a tripped slot is
restored from its last good checkpoint blob (periodic ``offload_slot``
every ``checkpoint_every`` bursts) and replayed once before failing with
``DivergenceDetected``.  Offload blobs are crc32/schema-validated on
restore (``CacheCorruption``), deadlines are enforced at admission and
in flight (``DeadlineExceeded``), and a no-progress watchdog
(``SlotStalled`` after ``stall_after`` zero-token iterations with work
queued) plus ``run(max_iters=...)`` bound the host loop.  All of it is
exercised deterministically via :mod:`repro.serving.fault_inject`
(``REPRO_FAULT_SPEC``).

Durability (:mod:`repro.serving.store`): with a ``CheckpointStore``
attached (``store=`` / ``store_dir=`` / ``REPRO_CHECKPOINT_DIR``), the
periodic checkpoint and preemption blobs — and every request's
metadata — persist to disk under an atomically-committed manifest.  A
fresh engine constructed over a populated store **rehydrates**: live
requests resume from their last durable blob (bad blobs degrade to
replay-from-prompt), queued ones re-enter with their original priority
and remaining deadline budget, and the resumed token streams are
bit-identical to an uninterrupted run.  Crashes are simulated
deterministically with ``kill`` fault clauses (``SimulatedCrash``).
"""
from __future__ import annotations

import logging
import os
import time
import zlib
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import ModelConfig
from repro.distributed.sharding import ShardingPlan
from repro.models.lm import (decode_tokens, init_lm_cache, lm_decode_step,
                             lm_forward, lm_prefill)
from repro.serving.bucketing import (clamped_bucket, kv_cache_extent,
                                     rope_len_for)
from repro.serving.cache import (blob_tags, finish_offload, offload_slot,
                                 restore_slot, slot_schema, start_offload,
                                 validate_blob)
from repro.serving.fault_inject import FaultPlan, SimulatedCrash, poison_slot
from repro.serving.faults import (CacheCorruption, DeadlineExceeded,
                                  DivergenceDetected, RecoveryFailed,
                                  RequestError, SlotStalled,
                                  StarvationTimeout)
from repro.serving.store import CheckpointStore, layout_fingerprint
from repro.serving.metrics import MetricsRegistry
from repro.serving.prefill import ChunkedPrefill, supports_chunked_prefill
from repro.serving.profiler import Profiler
from repro.serving.scheduler import (Scheduler, VictimCandidate,
                                     make_scheduler)
from repro.serving.telemetry import Telemetry

log = logging.getLogger("repro.serving.engine")


def make_prefill_step(cfg: ModelConfig, plan: Optional[ShardingPlan] = None):
    kv_repeat = plan.kv_repeat if plan else 1
    moe_groups = plan.moe_groups if plan else 1

    def prefill_step(params, inputs, cache):
        return lm_prefill(cfg, params, inputs, cache, kv_repeat=kv_repeat,
                          moe_groups=moe_groups)

    return prefill_step


def make_decode_step(cfg: ModelConfig, plan: Optional[ShardingPlan] = None):
    kv_repeat = plan.kv_repeat if plan else 1
    moe_groups = plan.moe_groups if plan else 1

    def decode_step(params, token, cache):
        return lm_decode_step(cfg, params, token, cache, kv_repeat=kv_repeat,
                              moe_groups=moe_groups)

    return decode_step


def make_decode_tokens(cfg: ModelConfig, plan: Optional[ShardingPlan] = None):
    """Builder for the fused multi-token decode loop (jit with n static).

    ``rope_len`` (static) sizes the rope tables past the cache extent —
    rolling-window caches span only their window, but decode positions run
    to the serving ``max_seq``.  ``with_sentinel`` (static) appends the
    per-row finiteness flag to the return."""
    kv_repeat = plan.kv_repeat if plan else 1
    moe_groups = plan.moe_groups if plan else 1

    def decode_n(params, cache, first_token, n: int,
                 kv_bucket: Optional[int] = None,
                 rope_len: Optional[int] = None,
                 with_sentinel: bool = False):
        return decode_tokens(cfg, params, cache, first_token, n,
                             kv_repeat=kv_repeat, moe_groups=moe_groups,
                             kv_bucket=kv_bucket, rope_len=rope_len,
                             with_sentinel=with_sentinel)

    return decode_n


def make_encode_step(cfg: ModelConfig, plan: Optional[ShardingPlan] = None):
    """Encoder-only archs (hubert): one full forward is the serve step."""
    kv_repeat = plan.kv_repeat if plan else 1

    def encode_step(params, inputs):
        return lm_forward(cfg, params, inputs, kv_repeat=kv_repeat,
                          train=False)

    return encode_step


def greedy_generate(cfg: ModelConfig, params, inputs: Dict[str, jax.Array],
                    max_seq: int, gen_len: int,
                    plan: Optional[ShardingPlan] = None
                    ) -> Tuple[jax.Array, Any]:
    """Prefill + fused greedy decode: the whole generation burst runs as a
    single compiled program (no host round-trip per token)."""
    batch = next(iter(inputs.values())).shape[0]
    kv_repeat = plan.kv_repeat if plan else 1
    cache = init_lm_cache(cfg, batch, max_seq, kv_repeat=kv_repeat)
    prefill = jax.jit(make_prefill_step(cfg, plan))
    decode_n = jax.jit(make_decode_tokens(cfg, plan),
                       static_argnames=("n", "rope_len"))
    logits, cache = prefill(params, inputs, cache)
    first = jnp.argmax(logits[..., :cfg.vocab_size], -1).astype(jnp.int32)
    if gen_len <= 1:
        return first, cache
    rest, cache = decode_n(params, cache, first, n=gen_len - 1,
                           rope_len=rope_len_for(cfg, max_seq))
    return jnp.concatenate([first, rest], axis=1), cache


# ---------------------------------------------------------------------------
# slot-based batch engine
# ---------------------------------------------------------------------------

@dataclass
class Request:
    rid: int
    prompt: np.ndarray            # [S] int32
    max_new: int
    deadline_ms: Optional[float] = None   # TTL from submit; None = no SLO
    priority: int = 0             # scheduling class; higher = more important
    out: List[int] = field(default_factory=list)
    done: bool = False
    status: str = "pending"       # terminal: ok/failed/cancelled/timed_out
    error: Optional[RequestError] = None
    submit_t: float = 0.0         # engine clock at submit (deadline base)
    # preemption state (set when the engine offloads this request's slot)
    blob: Optional[Dict[str, Any]] = None
    next_token: int = 0
    resume_pos: int = 0
    preemptions: int = 0
    # last-good checkpoint (divergence replay target): the blob, or the
    # Future of one whose transfer and pack still run off the step loop
    _ckpt: Any = field(default=None, init=False, repr=False)
    ckpt_token: int = 0
    ckpt_pos: int = 0
    ckpt_out: int = 0
    replays: int = 0

    @property
    def ckpt_blob(self) -> Optional[Dict[str, Any]]:
        """The replay blob; reading it waits for a pending one."""
        if isinstance(self._ckpt, Future):
            self._ckpt = self._ckpt.result()
        return self._ckpt

    @ckpt_blob.setter
    def ckpt_blob(self, blob) -> None:
        """Set the replay blob (or its Future); a pending one it replaces
        is dropped."""
        if isinstance(self._ckpt, Future):
            self._ckpt.cancel()
        self._ckpt = blob


def _scatter_group(batch_cache, src_cache, dst: jax.Array):
    """Insert rows of a batch-k prefill cache into slots ``dst`` ([k]) of
    the batch cache in one call (per leaf the batch dim is axis 1: caches
    are stacked [n_rep, B, ...]).  Rows with ``dst[i] < 0`` are skipped
    (inert padding rows / rows emitted on an earlier chunk).  Jitted by
    the engine so a whole admission group lands in a single dispatch
    instead of one full-cache copy per request."""
    def ins(full, one):
        if full.ndim == 0 or one is None:
            return full

        def body(i, acc):
            d = jnp.clip(dst[i], 0, acc.shape[1] - 1)
            sl = jax.lax.dynamic_slice_in_dim(one, i, 1, axis=1)
            cur = jax.lax.dynamic_slice_in_dim(acc, d, 1, axis=1)
            sl = jnp.where(dst[i] >= 0, sl.astype(acc.dtype), cur)
            return jax.lax.dynamic_update_slice_in_dim(acc, sl, d, axis=1)

        return jax.lax.fori_loop(0, one.shape[1], body, full)
    segs = [jax.tree_util.tree_map(ins, fs, ss)
            for fs, ss in zip(batch_cache["segments"], src_cache["segments"])]
    return {"segments": segs, "pos": batch_cache["pos"]}


class ServingEngine:
    """Fixed-slot continuous batching over the fused decode loop.

    Each :meth:`step` runs one admission move — one chunk of the in-flight
    mixed-length prefill group, or a preempted-slot restore — then decodes
    ``decode_block`` tokens for every slot in one compiled loop.  Prefill
    and decode interleave: a long prompt prefilling chunk-by-chunk never
    blocks decode progress on live slots.  Per-slot ``pos`` means
    late-admitted slots attend only over their own valid cache rows.
    Every decodable architecture admits through this one path — encoder
    and audio-frontend configs have no autoregressive serving step and
    are rejected at construction.

    Attention work is bounded to the live prefix by static KV bucketing
    (:mod:`repro.serving.bucketing`): every decode burst and prefill chunk
    runs with the smallest power-of-two KV extent covering
    ``max(live pos) + block``, capped at the model's largest KV cache —
    ``max_seq`` for append-only caches, the *window* for rolling ones —
    so outputs stay bit-identical with O(log extent) compiled programs
    and FLOPs/IO that grow with the true context.

    When queued prompts are starved (no slot has freed for
    ``preempt_after`` iterations and no prefill is in flight — or
    immediately, when the policy reports a higher class waiting), the
    scheduler picks a victim from slack-costed candidates (estimated
    finish margin under the per-(phase, bucket) latency model;
    deadline-less slots rank as infinite slack): the default fifo rule
    evicts the most-slack slot tie-broken on max remaining decode work,
    strict tiers the lowest class, weighted fairness the class furthest
    over its share.  The victim is offloaded to host memory and
    requeued; it is restored bit-exactly once a slot frees.

    Scheduling policy (:mod:`repro.serving.scheduler`) is injected via
    ``scheduler=`` or built from ``sched_policy`` / ``sched_weights`` /
    ``starve_ms`` (environment: ``REPRO_SCHED_POLICY``,
    ``REPRO_SCHED_WEIGHTS``).  ``Request.priority`` is the class; the
    fifo default reproduces the engine's historical behaviour exactly.

    Failure handling (every knob below; taxonomy in
    :mod:`repro.serving.faults`):

    * ``sentinel`` — per-row on-device finiteness flags ride inside the
      decode scan and each prefill chunk.  A tripped decode row is
      restored from its last checkpoint and replayed once (bit-identical
      on transient faults), then failed with ``DivergenceDetected``; a
      tripped prefill row is quarantined out of its group.
    * ``checkpoint_every`` — every N engine iterations each live slot is
      offloaded as its replay target (plus once at admission); ``0``
      disables checkpointing (divergence then fails without replay).
    * ``Request.deadline_ms`` — TTL from submit.  Queued, mid-prefill and
      mid-decode expiries are cancelled (``timed_out``) and their slots
      reclaimed; admission rejects (``cancelled``) requests whose
      estimated latency under the per-(phase, KV-bucket) latency model
      (:attr:`telemetry`, steady-state samples only — first-dispatch
      compile spikes are segregated; ``estimate()``'s bucket-to-global
      fallback is the only fallback) exceeds the budget.
    * ``telemetry`` / ``trace_path`` — the structured metrics + tracing
      layer (:mod:`repro.serving.telemetry`): per-(phase, bucket)
      latency records and per-request span traces, JSONL-exported when
      ``trace_path`` (or ``REPRO_TRACE_PATH``) is set.  All engine
      timing, deadlines included, reads the one injectable ``clock``.
    * ``stall_after`` — no-progress watchdog: after N iterations with
      zero decoded tokens, no prefill progress and work still queued, the
      stranded requests fail with ``SlotStalled`` instead of hanging the
      host loop; :meth:`run` additionally takes ``max_iters``.
    * ``fault_plan`` — deterministic fault injection
      (:mod:`repro.serving.fault_inject`; defaults to the
      ``REPRO_FAULT_SPEC`` env plan) poking NaNs, blob bit-flips and
      prefill stalls at exact points so every path above is testable.

    Co-batch isolation invariant: rows are independent across the batch
    dim in every kernel, quarantine restores full slot rows, and failed
    slots are fully overwritten at re-admission — so a healthy request
    decodes bit-identically whether or not a neighbour slot faulted.
    """

    def __init__(self, cfg: ModelConfig, params, *, slots: int, max_seq: int,
                 plan: Optional[ShardingPlan] = None, decode_block: int = 8,
                 chunk_size: Optional[int] = None, preempt_after: int = 4,
                 checkpoint_every: int = 8, stall_after: int = 32,
                 sentinel: bool = True,
                 fault_plan: Optional[FaultPlan] = None,
                 clock: Optional[Callable[[], float]] = None,
                 telemetry: Optional[Telemetry] = None,
                 trace_path: Optional[str] = None,
                 warmstart_path: Optional[str] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 profiler: Optional[Profiler] = None,
                 scheduler: Optional[Scheduler] = None,
                 sched_policy: Optional[str] = None,
                 sched_weights: Optional[Dict[int, float]] = None,
                 starve_ms: Optional[float] = None,
                 store: Optional[CheckpointStore] = None,
                 store_dir: Optional[str] = None):
        if not supports_chunked_prefill(cfg):
            raise ValueError(
                f"{cfg.name}: no autoregressive serving path (encoder / "
                "audio-frontend architectures serve through "
                "make_encode_step, not the slot engine)")
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_seq = max_seq
        self.decode_block = decode_block
        kv_repeat = plan.kv_repeat if plan else 1
        self.cache = init_lm_cache(cfg, slots, max_seq, kv_repeat=kv_repeat)
        # the burst's output cache replaces self.cache: donating the input
        # lets XLA update KV/state in place instead of holding two copies
        # (at published widths each is GBs of a 16 GB chip)
        self._decode_n = jax.jit(make_decode_tokens(cfg, plan),
                                 static_argnames=("n", "kv_bucket",
                                                  "rope_len",
                                                  "with_sentinel"),
                                 donate_argnames=("cache",))
        # a model that holds KV caches of max_seq rows a slot scatters
        # into its slot cache in place: a second copy of it would not fit
        # beside the weights at published widths (a KV-free model's cache
        # is small, and its programs stay as they are)
        self._scatter = jax.jit(
            _scatter_group,
            donate_argnums=(0,) if kv_cache_extent(cfg, max_seq) else ())
        self.kv_repeat = kv_repeat
        self.chunk_size = chunk_size or min(256, max_seq)
        self.preempt_after = preempt_after
        self.checkpoint_every = int(checkpoint_every)
        self.stall_after = int(stall_after)
        self.sentinel = bool(sentinel)
        self.faults = fault_plan if fault_plan is not None \
            else FaultPlan.from_env()
        # ALL scheduling DECISIONS — admission order, preemption victims,
        # deadline/starvation expiry, prefill interleave shares — live in
        # the policy object; the engine below is pure mechanism (dispatch,
        # scatter, offload/restore, terminal-state bookkeeping).  Policy
        # may reorder work but never changes any request's decoded bytes.
        self.scheduler = scheduler if scheduler is not None else \
            make_scheduler(sched_policy, sched_weights, starve_ms)
        self._clock = clock or time.monotonic
        # ALL engine timing — deadlines, dispatch latency, checkpoint cost
        # — reads this one clock, so fake-clock tests see consistent EWMAs.
        # The default Telemetry is keyed by this config's arch name (the
        # latency table never mixes rungs across archs) and warm-starts
        # from `warmstart_path` / REPRO_TELEMETRY_WARMSTART when set.
        self.telemetry = telemetry if telemetry is not None else Telemetry(
            clock=self._clock, trace_path=trace_path, arch=cfg.name,
            warmstart_path=warmstart_path)
        self.metrics = metrics if metrics is not None else MetricsRegistry(
            clock=self._clock)
        self.profiler = profiler if profiler is not None else Profiler(
            clock=self._clock)
        self._init_metrics()
        # bucket-ladder top: the model's largest KV extent (window-capped
        # for rolling archs); None = no KV cache worth bucketing
        self.kv_extent = kv_cache_extent(cfg, max_seq)
        self.kv_buckets = self.kv_extent is not None
        self.rope_len = rope_len_for(cfg, max_seq)
        self._chunked_prefill = ChunkedPrefill(
            cfg, params, max_seq=max_seq, chunk_size=self.chunk_size,
            plan=plan, sentinel=self.sentinel, fault_plan=self.faults,
            metrics=self.metrics)
        # slots reserved for the in-flight prefill group: row i of the
        # group lands in slot _pending[i][0] when its prompt completes
        self._pending: List[Tuple[int, Request]] = []
        self._starved = 0
        self._no_progress = 0
        self._req_s_t: Optional[float] = None   # last request-seconds count
        # fractional-interleave accumulator: policies may grant the
        # in-flight prefill group < 1.0 chunk per iteration next to
        # higher-priority decode slots; credit accrues until a chunk runs
        self._prefill_credit = 0.0
        self.live: List[Optional[Request]] = [None] * slots
        self.tokens = np.zeros((slots, 1), np.int32)
        self.pos = np.zeros((slots,), np.int64)
        self.queue: List[Request] = []
        self.finished: List[Request] = []
        self.stats = {"iters": 0, "decode_tokens": 0, "prefill_chunks": 0,
                      "preemptions": 0, "restores": 0,
                      "interleave_iters": 0, "interleave_decode_iters": 0,
                      "checkpoints": 0, "ckpt_ms": 0.0, "divergences": 0,
                      "replays": 0, "failures": 0, "timeouts": 0,
                      "cancelled": 0, "watchdog_trips": 0,
                      "starvation_timeouts": 0}
        # distinct KV buckets the decode loop has run in (bounded by the
        # bucket ladder — observability for the compile-count discipline)
        self.buckets_used: set = set()
        # decode bucket keys already dispatched (None included, for archs
        # without KV buckets): the FIRST dispatch per key pays XLA
        # trace+compile and its latency sample must be segregated from
        # the steady-state estimates feeding admission and preemption
        self._decode_seen: set = set()
        self._max_bucket = -1     # deepest decode rung seen (climb counter)
        # durable checkpoint store (crash recovery): explicit instance >
        # store_dir > REPRO_CHECKPOINT_DIR; None = host-memory-only FT.
        # A populated store rehydrates NOW — in-flight requests re-enter
        # as restore-from-blob admissions, queued ones with their
        # original priority and REMAINING deadline budget.
        if store is None:
            store_dir = store_dir or os.environ.get("REPRO_CHECKPOINT_DIR")
            store = CheckpointStore(store_dir) if store_dir else None
        self.store = store
        self._slot_schema = slot_schema(self.cache)
        self._template_keys = list(self._slot_schema)
        self._store_fp = layout_fingerprint(cfg.name, max_seq,
                                            self._slot_schema)
        self._store_order = 0
        # finishes checkpoints off the step loop (made at the first one)
        self._ckpt_pool: Optional[ThreadPoolExecutor] = None
        self._rehydrate()

    def _init_metrics(self) -> None:
        """Register this engine's instruments on the (possibly shared)
        registry; get-or-create, so several engines can share one."""
        m = self.metrics
        self._m_queue = m.gauge(
            "repro_queue_depth", "requests waiting for a slot")
        self._m_live = m.gauge("repro_live_slots", "slots decoding now")
        self._m_submitted = m.counter(
            "repro_submitted_total", "requests submitted")
        self._m_admitted = m.counter(
            "repro_admitted_total", "requests admitted into a prefill group")
        self._m_finished = m.counter(
            "repro_finished_total",
            "terminal requests by status (ok/failed/cancelled/timed_out)")
        self._m_tokens = m.counter(
            "repro_tokens_total", "tokens processed per phase")
        self._m_preempt = m.counter(
            "repro_preemptions_total", "slot offloads for starved queues")
        self._m_restore = m.counter(
            "repro_restores_total", "preempted slots restored")
        self._m_ckpts = m.counter(
            "repro_checkpoints_total", "replay checkpoints taken")
        self._m_ckpt_bytes = m.counter(
            "repro_checkpoint_bytes_total",
            "host bytes offloaded by checkpointing")
        # counted by cache.start_offload; registered here for its help
        m.counter("repro_checkpoint_transfer_bytes_total",
                  "device->host bytes a checkpoint moved (the due slots); "
                  "repro_checkpoint_bytes_total is the part it kept")
        self._m_ckpt_wait_s = m.counter(
            "repro_checkpoint_wait_seconds_total",
            "seconds the step loop waited for a checkpoint's transfer and "
            "pack to finish")
        self._m_ckpt_waits = m.counter(
            "repro_checkpoint_waits_total",
            "times the step loop waited for an unfinished checkpoint")
        req_s = m.counter(
            "repro_request_seconds_total",
            "request-seconds spent queued (preempted ones included), in "
            "prefill and in decode; decode over slots x window is the "
            "slots' occupancy")
        self._m_queued_s = req_s.labels(state="queued")
        self._m_prefill_s = req_s.labels(state="prefill")
        self._m_decode_s = req_s.labels(state="decode")
        kv_pos = m.counter(
            "repro_attn_kv_positions_total",
            "attention KV positions per prefill-chunk row and per decode "
            "step of a live slot: the KV bucket the program holds (read) "
            "and the positions live in it (live)")
        self._m_kv_read = {ph: kv_pos.labels(phase=ph, what="read")
                           for ph in ("prefill", "decode")}
        self._m_kv_live = {ph: kv_pos.labels(phase=ph, what="live")
                           for ph in ("prefill", "decode")}
        self._m_climbs = m.counter(
            "repro_bucket_climbs_total",
            "decode dispatches entering a deeper KV rung (each pays "
            "trace+compile)")
        self._m_diverg = m.counter(
            "repro_divergences_total", "sentinel trips")
        self._m_replays = m.counter(
            "repro_replays_total", "checkpoint replays after divergence")
        self._m_watchdog = m.counter(
            "repro_watchdog_trips_total", "no-progress watchdog trips")
        self._m_decode_ms = m.histogram(
            "repro_decode_burst_ms", "decode burst wall time (ms)")
        self._m_prefill_ms = m.histogram(
            "repro_prefill_chunk_ms", "prefill chunk wall time (ms)")
        self._m_ttft = m.histogram(
            "repro_ttft_ms",
            "time to first token (ms), labelled by priority class")
        self._m_class_tokens = m.counter(
            "repro_class_tokens_total",
            "tokens served per priority class and phase")
        self._m_starved = m.counter(
            "repro_starvation_timeouts_total",
            "queued requests failed by the scheduler's starvation bound")
        self._m_recoveries = m.counter(
            "repro_recoveries_total",
            "requests rehydrated from the durable checkpoint store at "
            "engine restart, by outcome (restored/replayed/requeued/"
            "expired/unrecoverable)")
        self._m_recovery_ms = m.histogram(
            "repro_recovery_ms",
            "wall time of one engine-restart rehydration pass (ms)")

    def _count_request_seconds(self, now: float) -> None:
        """Add the request-seconds by state since the last call: only
        ``submit`` and ``step`` change the states, so the counts seen now
        have held since then."""
        if self._req_s_t is not None:
            dt = max(0.0, now - self._req_s_t)
            self._m_queued_s.inc(dt * len(self.queue))
            self._m_prefill_s.inc(dt * self._open_pending())
            self._m_decode_s.inc(
                dt * sum(r is not None for r in self.live))
        self._req_s_t = now

    def submit(self, req: Request) -> None:
        # validate here, before admission can pop the request and reserve
        # slots: a mid-group failure would strand co-batched requests.
        # Submit-time ValueErrors are CALLER bugs and raise; in-flight
        # faults never do — they land on Request.status/.error instead.
        if len(req.prompt) == 0:
            raise ValueError(f"rid={req.rid}: empty prompt")
        # decode room is max_seq - 1 - pos, so a prompt needs at least two
        # cache rows beyond itself to emit any decoded token
        if len(req.prompt) > self.max_seq - 2:
            raise ValueError(
                f"rid={req.rid}: prompt length {len(req.prompt)} exceeds "
                f"max_seq-2 ({self.max_seq - 2}); no room to decode")
        p = np.asarray(req.prompt)
        if not np.issubdtype(p.dtype, np.integer):
            raise ValueError(f"rid={req.rid}: prompt dtype {p.dtype} is not "
                             "an integer token array")
        lo, hi = int(p.min()), int(p.max())
        if lo < 0 or hi >= self.cfg.vocab_size:
            raise ValueError(
                f"rid={req.rid}: prompt token ids [{lo}, {hi}] fall outside "
                f"the vocab [0, {self.cfg.vocab_size}) — out-of-vocab ids "
                "index garbage embedding rows")
        req.submit_t = self._clock()
        self._count_request_seconds(req.submit_t)
        self.telemetry.begin_span(req.rid, prompt_len=len(req.prompt),
                                  max_new=req.max_new,
                                  deadline_ms=req.deadline_ms,
                                  priority=req.priority,
                                  t=req.submit_t)
        self.queue.append(req)
        self._m_submitted.inc()
        self._m_queue.set(len(self.queue))
        if self.store is not None:
            self._persist_request(req, state="queued")
            self.store.commit()

    # -------------------------------------------------------- durability
    def _persist_request(self, req: Request, *, state: str,
                         next_token: int = 0, pos: int = 0) -> None:
        """Write/refresh ``req``'s manifest record (uncommitted).  The
        record alone is enough to REPLAY the request from its prompt;
        with a staged blob it restores mid-stream.  ``age_ms`` (budget
        already consumed) + the persist-time clock reading let the next
        engine resurrect the deadline as *remaining* budget, and
        ``prompt_crc`` guards against a record whose replay would decode
        a different request."""
        p = np.asarray(req.prompt, np.int64)
        rec = self.store.record(
            req.rid, state=state,
            prompt=[int(x) for x in req.prompt],
            prompt_crc=zlib.crc32(p.tobytes()),
            max_new=int(req.max_new), priority=int(req.priority),
            deadline_ms=req.deadline_ms,
            age_ms=(self._clock() - req.submit_t) * 1e3, t=self._clock(),
            out=list(req.out), next_token=int(next_token), pos=int(pos))
        if "order" not in rec:           # admission order survives restart
            rec["order"] = self._store_order
            self._store_order += 1

    def _forget_request(self, req: Request) -> None:
        """Terminal state reached: the durable record (and its blob
        files, at the next prune) has nothing left to recover."""
        if self.store is not None:
            self.store.forget(req.rid)
            self.store.commit()

    def _rehydrate(self) -> None:
        """Resurrect a crashed engine's work from the durable store (at
        construction).  Per persisted record, in admission order:

        * expired while down — the consumed budget (pre-crash ``age_ms``
          + downtime) already exceeds ``deadline_ms``: fail with
          ``DeadlineExceeded`` NOW, before any replay work is wasted.
        * prompt fails its recorded crc32 — nothing can reproduce the
          original stream (``RecoveryFailed``); corrupt *blobs* are the
          recoverable case below, this is not.
        * in-flight with a durable checkpoint/preemption blob — validate
          it (crc/schema/identity-tag, exactly like a preemption
          restore); good blobs re-enter as restore-from-blob admissions
          with the already-decoded output reattached ("restored"), bad
          blobs degrade to replay-from-prompt ("replayed") — never a
          crash.
        * queued-but-unstarted — requeued with original priority
          ("requeued").

        ``submit_t`` is back-dated by the consumed budget so deadlines
        resume as REMAINING budget, not a fresh TTL.  Outcome counts
        land on :attr:`recovery` and ``repro_recoveries_total``."""
        self.recovery: Dict[str, int] = {
            "restored": 0, "replayed": 0, "requeued": 0,
            "expired": 0, "unrecoverable": 0}
        if self.store is None:
            return
        fp = self.store.manifest.get("fingerprint")
        if fp is not None and fp != self._store_fp:
            # a store written by a different config / cache geometry:
            # refuse to adopt it (rehydrating would scatter mis-shaped
            # rows; writing to it would corrupt the other engine's state)
            log.warning(
                "checkpoint store %s: layout fingerprint %s does not "
                "match this engine's %s (config %r, max_seq %d); "
                "ignoring the store", self.store.root, fp, self._store_fp,
                self.cfg.name, self.max_seq)
            self.store = None
            return
        t0 = self._clock()
        recs = sorted(self.store.requests.values(),
                      key=lambda r: r.get("order", 0))
        if recs:
            self._store_order = max(r.get("order", 0) for r in recs) + 1
        for rec in list(recs):
            rid = int(rec["rid"])
            prompt = np.asarray(rec.get("prompt") or [], np.int32)
            req = Request(rid=rid, prompt=prompt,
                          max_new=int(rec.get("max_new", 0)),
                          deadline_ms=rec.get("deadline_ms"),
                          priority=int(rec.get("priority", 0)))
            now = self._clock()
            # downtime on top of the budget consumed pre-crash; clamped
            # at 0 for clocks that restart from an earlier origin
            downtime_ms = max(0.0, (now - float(rec.get("t", now))) * 1e3)
            consumed_ms = float(rec.get("age_ms", 0.0)) + downtime_ms
            req.submit_t = now - consumed_ms / 1e3
            self.telemetry.begin_span(
                rid, prompt_len=len(prompt), max_new=req.max_new,
                deadline_ms=req.deadline_ms, priority=req.priority,
                t=req.submit_t, rehydrated=rec.get("state", "queued"))
            if (req.deadline_ms is not None
                    and consumed_ms >= req.deadline_ms):
                self.recovery["expired"] += 1
                self._m_recoveries.labels(outcome="expired").inc()
                self._fail(req, "timed_out", DeadlineExceeded(
                    f"deadline expired while the engine was down "
                    f"({consumed_ms:.1f}ms consumed of "
                    f"{req.deadline_ms:.1f}ms)", rid=rid))
                continue
            crc = rec.get("prompt_crc")
            if (len(prompt) == 0 or (crc is not None and int(crc) !=
                    zlib.crc32(np.asarray(prompt, np.int64).tobytes()))):
                self.recovery["unrecoverable"] += 1
                self._m_recoveries.labels(outcome="unrecoverable").inc()
                self._fail(req, "failed", RecoveryFailed(
                    "persisted prompt fails its recorded crc32 — replay "
                    "would decode a different request", rid=rid))
                continue
            outcome = "requeued"
            if rec.get("state") != "queued":
                outcome = "replayed"
                # only the NEWEST blob matches the record's resume point
                # (out/next_token/pos are persisted alongside it); any
                # failure degrades to replay-from-prompt
                rels = rec.get("blobs") or []
                if rels:
                    try:
                        blob = self.store.load_blob(rels[0])
                        validate_blob(blob, self._template_keys, rid=rid)
                        tags = blob_tags(blob)
                        if "rid" in tags and tags["rid"] != rid:
                            raise CacheCorruption(
                                f"durable blob carries rid {tags['rid']!r}",
                                rid=rid)
                        req.blob = blob
                        req.next_token = int(rec.get("next_token", 0))
                        req.resume_pos = int(rec.get("pos", 0))
                        req.out = [int(x) for x in rec.get("out") or []]
                        outcome = "restored"
                    except CacheCorruption as e:
                        log.warning("rid=%d: durable blob rejected (%s); "
                                    "replaying from prompt", rid, e)
            self.queue.append(req)
            self.recovery[outcome] += 1
            self._m_recoveries.labels(outcome=outcome).inc()
            self.telemetry.event(rid, "rehydrate", detail=outcome)
        self.store.set_fingerprint(self._store_fp)
        self.store.commit()
        self._m_queue.set(len(self.queue))
        if recs:
            self._m_recovery_ms.observe((self._clock() - t0) * 1e3)

    # ------------------------------------------------------------ failures
    def _fail(self, req: Request, status: str,
              err: Optional[RequestError]) -> None:
        """Move a request to a non-ok terminal state (never raises)."""
        req.status = status
        req.error = err
        req.done = True
        req.blob = None
        req.ckpt_blob = None
        self.finished.append(req)
        self.telemetry.end_span(req.rid, status,
                                error=str(err) if err else None,
                                tokens_out=len(req.out))
        self.stats[{"failed": "failures", "timed_out": "timeouts",
                    "cancelled": "cancelled"}[status]] += 1
        self._m_finished.labels(status=status).inc()
        self._forget_request(req)

    def _expired(self, req: Request, now: float) -> bool:
        return self.scheduler.expired(req, now)

    def _expire_deadlines(self, now: float) -> None:
        """Cancel queued / mid-prefill / mid-decode requests whose TTL has
        run out by ``now`` (the scheduler owns the expiry decision;
        reclaiming slots and group rows is mechanism and happens here),
        then fail queued requests the policy's starvation bound has given
        up on."""
        for req in [r for r in self.queue if self._expired(r, now)]:
            self.queue.remove(req)
            self._fail(req, "timed_out", DeadlineExceeded(
                "deadline expired while queued "
                f"({req.deadline_ms:.1f}ms)", rid=req.rid))
        for row, (b, req) in enumerate(self._pending):
            if not req.done and self._expired(req, now):
                self._chunked_prefill.cancel_row(row)
                self._fail(req, "timed_out", DeadlineExceeded(
                    "deadline expired mid-prefill "
                    f"({req.deadline_ms:.1f}ms)", rid=req.rid))
        for b, req in enumerate(self.live):
            if req is not None and self._expired(req, now):
                self.live[b] = None
                self._fail(req, "timed_out", DeadlineExceeded(
                    "deadline expired mid-decode after "
                    f"{len(req.out)} tokens ({req.deadline_ms:.1f}ms)",
                    rid=req.rid))
        for req in self.scheduler.starved_out(self.queue, self.live, now):
            self.queue.remove(req)
            wait_ms = (now - req.submit_t) * 1e3
            self._fail(req, "timed_out", StarvationTimeout(
                f"class-{req.priority} request starved for {wait_ms:.1f}ms "
                f"(> {self.scheduler.starve_ms:.1f}ms bound) behind "
                "higher-priority work", rid=req.rid))
            self.stats["starvation_timeouts"] += 1
            self._m_starved.inc()

    def _admission_estimate_ms(self, req: Request) -> Optional[float]:
        """Latency estimate from the per-(phase, bucket) latency model:
        prefill cost at the rung covering the prompt, decode cost at the
        rung the request will finish under (conservative — the deepest
        bucket it reaches).  ``estimate()`` itself falls back from the
        bucket to the phase-global steady record (never across archs,
        never to compile samples) — that is the ONLY fallback; None until
        either phase has a steady-state measurement."""
        plen, mnew = len(req.prompt), req.max_new
        ptok = self.telemetry.estimate(
            "prefill", clamped_bucket(plen, self.kv_extent))
        tpot = self.telemetry.estimate(
            "decode", clamped_bucket(plen + mnew, self.kv_extent))
        if ptok is None and tpot is None:
            return None
        return plen * (ptok or 0.0) + mnew * (tpot or 0.0)

    # ----------------------------------------------------------- admission
    def _restore(self, b: int, req: Request) -> bool:
        """Re-admit a preempted request from its host-offloaded state.
        A corrupted blob fails the REQUEST (CacheCorruption), not the
        engine; returns False and leaves the slot free."""
        try:
            self.cache = restore_slot(self.cache, req.blob, b, rid=req.rid,
                                      metrics=self.metrics,
                                      expect_tags={"rid": req.rid})
        except CacheCorruption as e:
            self._fail(req, "failed", e)
            return False
        self.tokens[b, 0] = req.next_token
        self.pos[b] = req.resume_pos
        self.live[b] = req
        # the validated preemption blob doubles as the replay checkpoint
        req.ckpt_blob = req.blob
        req.ckpt_token = req.next_token
        req.ckpt_pos = req.resume_pos
        req.ckpt_out = len(req.out)
        req.blob = None
        self.stats["restores"] += 1
        self._m_restore.inc()
        self.telemetry.event(req.rid, "restore", pos=req.resume_pos)
        return True

    def _admit(self, it: int) -> None:
        ch = self._chunked_prefill
        # a group whose every request already reached a terminal state
        # (deadline sweep, watchdog) is pure inert work: drop it
        if ch.active and self._pending and all(r.done
                                               for _, r in self._pending):
            ch.finish()
            self._pending = []
        reserved = {b for b, r in self._pending if not r.done}
        free = [b for b in range(self.slots)
                if self.live[b] is None and b not in reserved]
        # fill free slots from the queue in SCHEDULER order (fifo = submit
        # order, so the walk below reproduces the historical head-of-queue
        # loop exactly): preempted requests are restored in place (their
        # cache is already prefilled+decoded), fresh prompts accumulate
        # into one mixed-length prefill group.  A fresh prompt that can't
        # start (group already in flight) ends the walk — later requests
        # must not jump a reserved slot the policy ordered ahead of them.
        fresh: List[Request] = []
        order = self.scheduler.admission_order(self.queue, self._clock())
        for req in order:
            if not free:
                break
            if req.blob is not None:
                self.queue.remove(req)
                b = free.pop(0)
                if self._restore(b, req):
                    self._progress = True
                else:
                    free.insert(0, b)
            elif not ch.active:
                if req.deadline_ms is not None:
                    est = self._admission_estimate_ms(req)
                    left = (req.deadline_ms
                            - (self._clock() - req.submit_t) * 1e3)
                    if est is not None and est > left:
                        self.queue.remove(req)
                        self._fail(req, "cancelled", DeadlineExceeded(
                            f"admission reject: estimated {est:.1f}ms "
                            f"exceeds remaining {left:.1f}ms budget",
                            rid=req.rid))
                        continue
                self.queue.remove(req)
                fresh.append(req)
                self._pending.append((free.pop(0), req))
            else:  # a group is already in flight; keep the slot reserved
                break
        if fresh:
            ch.start([r.prompt for r in fresh],
                     batch=self.slots if len(fresh) > 1 else 1,
                     priorities=[r.priority for r in fresh])
            self._m_admitted.inc(len(fresh))
            self._m_queue.set(len(self.queue))
        stalled = self.faults.active and self.faults.stalled(it)
        run_chunk = ch.active and not stalled
        if run_chunk:
            # the policy may grant a low-priority group a fractional
            # iteration share next to higher-priority decode slots;
            # credit accrues until a whole chunk is due.  With no live
            # decode slot there is nothing to yield to: always run.
            live_cls = [r.priority for r in self.live if r is not None]
            share = 1.0 if not live_cls else min(1.0, max(
                0.0, self.scheduler.interleave_share(
                    [r.priority for _, r in self._pending if not r.done],
                    live_cls)))
            self._prefill_credit += share
            if self._prefill_credit >= 1.0:
                self._prefill_credit -= 1.0
            else:
                run_chunk = False
                self._starved = 0    # group in flight: queue isn't starved
        if run_chunk:
            with self.telemetry.span(
                    "prefill.chunk",
                    (r.rid for _, r in self._pending if not r.done),
                    timed=True) as chunk:
                emitted, done, diverged = ch.step()
            dt_ms = (chunk.end - chunk.start) * 1e3
            info = ch.last_chunk
            self._chunk_ran = True
            self._progress = True
            self.stats["prefill_chunks"] += 1
            # per-token cost over the group's VALID (unmasked) tokens —
            # dividing by the padded chunk size deflated the estimate on
            # ragged final chunks — recorded per (phase, bucket) with the
            # first dispatch of a (batch, bucket) combo segregated as a
            # compile sample (trace+compile must not poison steady state)
            rows = info["valid_per_row"] > 0
            if info["bucket"] is not None and rows.any():
                # each row with tokens in the chunk attends the bucket;
                # its live keys end where its chunk tokens end
                self._m_kv_read["prefill"].inc(
                    int(rows.sum()) * info["bucket"])
                self._m_kv_live["prefill"].inc(int(
                    (info["offset"] + info["valid_per_row"][rows]).sum()))
            if info["valid_tokens"] > 0:
                tok_ms = dt_ms / info["valid_tokens"]
                self.telemetry.record_latency(
                    "prefill", info["bucket"], tok_ms,
                    compiled=info["fresh_compile"])
                self._m_tokens.labels(phase="prefill").inc(
                    info["valid_tokens"])
            self._m_prefill_ms.observe(dt_ms)
            self.profiler.observe("prefill", dt_ms)
            for row, (b, req) in enumerate(self._pending):
                if not req.done and info["valid_per_row"][row]:
                    tokens = int(info["valid_per_row"][row])
                    self.telemetry.event(
                        req.rid, "prefill", bucket=info["bucket"],
                        tokens=tokens)
                    # DRR debit: prefill work counts against the class's
                    # weighted share exactly like decode tokens do
                    self.scheduler.note_service(req.priority, tokens)
                    self._m_class_tokens.labels(
                        priority=str(req.priority), phase="prefill").inc(
                            tokens)
            for row in diverged:
                b, req = self._pending[row]
                if not req.done:
                    self.telemetry.event(req.rid, "fault",
                                         detail="prefill_divergence")
                    self._fail(req, "failed", DivergenceDetected(
                        "non-finite activations in prefill chunk "
                        f"{ch._group['idx'] - 1}", rid=req.rid))
            if emitted:
                dst = np.full((len(self._pending),), -1, np.int32)
                for row, tok, plen in emitted:
                    b, req = self._pending[row]
                    if req.done:             # expired/failed while pending
                        continue
                    dst[row] = b
                    req.out.append(tok)
                    self.tokens[b, 0] = tok
                    self.pos[b] = plen
                    self.live[b] = req
                    ttft = self.telemetry.first_token(req.rid)
                    if ttft is not None:
                        self._m_ttft.labels(
                            priority=str(req.priority)).observe(ttft)
                # batch rows past the real group are inert (dst stays -1)
                full = np.full((ch.group_cache["pos"].shape[0],), -1,
                               np.int32)
                full[:len(dst)] = dst
                self.cache = self._scatter(self.cache, ch.group_cache,
                                           jnp.asarray(full))
            if done:
                ch.finish()
                self._pending = []
            self._starved = 0
        elif self.queue and not free and not ch.active and not stalled:
            # queue starved: no slot freed and nothing is prefilling.
            # The policy can demand immediate preemption (strict tiers:
            # a higher class is waiting) instead of sitting out the
            # preempt_after starvation window.
            self._starved += 1
            if (self._starved >= self.preempt_after
                    or self.scheduler.urgent_preempt(self.queue, self.live)):
                self._preempt()
        elif not stalled and not ch.active:
            self._starved = 0

    def _preempt(self) -> None:
        """Offload one live slot so a starved queued prompt can take it
        next iteration.  The engine's part is MECHANISM: cost every live
        slot's deadline slack under the per-(phase, bucket) latency model
        (each slot's remaining decode costed at the rung it will finish
        under; deadline-less slots rank as infinite slack) and offload
        whichever slot the scheduler names.  Victim CHOICE is policy:
        fifo keeps the historical most-slack / most-remaining rule,
        strict tiers evict the lowest class, weighted fairness evicts the
        class furthest over its share."""
        now = self._clock()
        candidates: List[VictimCandidate] = []
        for b, req in enumerate(self.live):
            if req is None:
                continue
            remaining = req.max_new - len(req.out)
            if req.deadline_ms is None:
                slack = float("inf")
            else:
                tpot = self.telemetry.estimate("decode", clamped_bucket(
                    int(self.pos[b]) + remaining, self.kv_extent)) or 0.0
                slack = (req.deadline_ms - (now - req.submit_t) * 1e3
                         - remaining * tpot)
            candidates.append(VictimCandidate(
                slot=b, priority=req.priority, slack=slack,
                remaining=remaining))
        b = self.scheduler.preempt_victim(candidates, self.queue)
        if b is None:
            return
        req = self.live[b]
        self.cache = dict(self.cache, pos=jnp.asarray(self.pos, jnp.int32))
        blob = offload_slot(self.cache, b, tags={
            "rid": req.rid, "priority": req.priority})
        if self.faults.active:
            blob = self.faults.corrupt_blob(req.rid, blob)
        req.blob = blob
        req.next_token = int(self.tokens[b, 0])
        req.resume_pos = int(self.pos[b])
        req.preemptions += 1
        if self.store is not None:
            # a preemption blob is already a consistent resume point —
            # persist it so a crash while the request sits requeued
            # restores mid-stream instead of replaying the whole prefix
            self.store.stage_blob(req.rid, blob)
            self._persist_request(req, state="preempted",
                                  next_token=req.next_token,
                                  pos=req.resume_pos)
            self.store.commit()
        self.telemetry.event(req.rid, "preempt", pos=int(self.pos[b]))
        self.live[b] = None
        self.queue.append(req)
        self._starved = 0
        self.stats["preemptions"] += 1
        self._m_preempt.inc()

    # --------------------------------------------------------- checkpoints
    def _checkpoint(self, it: int) -> None:
        """Periodic lightweight checkpointing: offload each live slot as
        its divergence-replay target.  Runs every ``checkpoint_every``
        iterations plus once at each request's first burst (so replay is
        possible before the first periodic tick).  Taken at burst START,
        where host ``pos``/``tokens`` and device cache rows agree.

        Only the gather of the due slots runs here; each slot's transfer
        and pack finish on the engine's pool (``min(slots, 4)`` threads)
        while the burst runs.  The step loop waits for a blob only where
        it is read: a replay, the request's next checkpoint, and an
        attached store, which stages and commits it before this
        returns."""
        if not self.checkpoint_every:
            return
        due = it % self.checkpoint_every == 0
        need = [(b, r) for b, r in enumerate(self.live)
                if r is not None and (due or r._ckpt is None)]
        if not need:
            return
        with self.telemetry.span("engine.checkpoint",
                                 (r.rid for _, r in need),
                                 timed=True) as span:
            for _, req in need:
                # one gathered set a request in flight, at most
                self._join_checkpoint(req)
            self.cache = dict(self.cache,
                              pos=jnp.asarray(self.pos, jnp.int32))
            # gather only the due slots on the device; their transfer and
            # pack finish on the pool while the decode burst runs
            parts = start_offload(self.cache, [b for b, _ in need],
                                  metrics=self.metrics)
            pool = self._checkpoint_pool()
            for b, req in need:
                part = parts[b]
                damage = self.faults.active and self.faults.blob_hit(req.rid)
                req.ckpt_blob = pool.submit(
                    self._finish_checkpoint, part,
                    {"rid": req.rid, "priority": req.priority}, damage)
                req.ckpt_token = int(self.tokens[b, 0])
                req.ckpt_pos = int(self.pos[b])
                req.ckpt_out = len(req.out)
                self.stats["checkpoints"] += 1
                self._m_ckpts.inc()
                self._m_ckpt_bytes.inc(sum(a.nbytes for _, a in part))
                self.telemetry.event(req.rid, "checkpoint")
            if self.store is not None:
                # durability first: stage each finished blob, then commit
                for _, req in need:
                    self.store.stage_blob(req.rid,
                                          self._join_checkpoint(req))
                    self._persist_request(req, state="live",
                                          next_token=req.ckpt_token,
                                          pos=req.ckpt_pos)
                # crash point 1: blob files staged, manifest commit not
                # yet landed — recovery must see the PREVIOUS manifest
                # intact
                if self.faults.active and self.faults.kill_now(it,
                                                               point=1):
                    raise SimulatedCrash(
                        "fault injection: kill between checkpoint stage "
                        f"and manifest commit at iteration {it}")
                self.store.commit()
        # the step loop's share of checkpointing: the gather's dispatch
        # and any wait for a blob (benchmark readers divide it by the
        # window)
        self.stats["ckpt_ms"] += (span.end - span.start) * 1e3

    def _finish_checkpoint(self, part, tags, damage: bool):
        """Pool task: one due slot's host copy and blob (then its planted
        damage, if a ``corrupt_blob`` clause was spent on it)."""
        blob = finish_offload(part, self.telemetry, tags)
        return self.faults.damage_blob(tags["rid"], blob) if damage \
            else blob

    def _checkpoint_pool(self) -> ThreadPoolExecutor:
        if self._ckpt_pool is None:
            self._ckpt_pool = ThreadPoolExecutor(
                max_workers=min(self.slots, 4),
                thread_name_prefix="repro-checkpoint")
        return self._ckpt_pool

    def _close_checkpoint_pool(self) -> None:
        """Let the pool's tasks finish and its threads end (made anew at
        the next checkpoint)."""
        if self._ckpt_pool is not None:
            self._ckpt_pool.shutdown(wait=True)
            self._ckpt_pool = None

    def _join_checkpoint(self, req: Request) -> Optional[Dict[str, Any]]:
        """``req``'s replay blob, once finished.  A wait for a pending one
        is counted (``repro_checkpoint_wait{s,_seconds}_total``)."""
        pending = req._ckpt
        if isinstance(pending, Future) and not pending.done():
            t0 = self._clock()
            pending.exception()          # waits; result() raises below
            self._m_ckpt_wait_s.inc(self._clock() - t0)
            self._m_ckpt_waits.inc()
        return req.ckpt_blob

    def _quarantine(self, b: int, req: Request) -> None:
        """Divergence sentinel tripped for slot ``b`` this burst: none of
        the burst's tokens are accepted.  Restore the slot from its last
        good checkpoint and replay once; on a second trip (or with
        checkpointing disabled / a corrupt checkpoint) fail the request
        with ``DivergenceDetected`` — co-batched slots are untouched
        either way."""
        blob = (self._join_checkpoint(req)
                if self.checkpoint_every and req.replays < 1 else None)
        self.stats["divergences"] += 1
        self._m_diverg.inc()
        self.telemetry.event(req.rid, "fault", detail="decode_divergence")
        if blob is not None:
            try:
                self.cache = restore_slot(self.cache, blob, b,
                                          rid=req.rid, metrics=self.metrics,
                                          expect_tags={"rid": req.rid})
            except CacheCorruption as e:
                self.live[b] = None
                self._fail(req, "failed", e)
                return
            self.tokens[b, 0] = req.ckpt_token
            self.pos[b] = req.ckpt_pos
            del req.out[req.ckpt_out:]
            req.replays += 1
            self.stats["replays"] += 1
            self._m_replays.inc()
            self.telemetry.event(req.rid, "replay", pos=req.ckpt_pos)
        else:
            self.live[b] = None
            self._fail(req, "failed", DivergenceDetected(
                "non-finite logits in decode burst"
                + (" after checkpoint replay" if req.replays else
                   " (no checkpoint to replay)"), rid=req.rid))

    # ------------------------------------------------------------ watchdog
    def _watchdog(self, decoded: int) -> None:
        waiting = bool(self.queue) or any(not r.done
                                          for _, r in self._pending)
        if decoded or self._progress or not waiting:
            self._no_progress = 0
            return
        self._no_progress += 1
        if self._no_progress < self.stall_after:
            return
        self._no_progress = 0
        self.stats["watchdog_trips"] += 1
        self._m_watchdog.inc()
        stuck = [(row, req) for row, (b, req) in enumerate(self._pending)
                 if not req.done]
        if stuck:
            for row, req in stuck:
                self._chunked_prefill.cancel_row(row)
                self._fail(req, "failed", SlotStalled(
                    f"no progress for {self.stall_after} iterations with "
                    "prefill in flight", rid=req.rid))
            if self._chunked_prefill.active:
                self._chunked_prefill.finish()
            self._pending = []
        elif self.queue:
            req = self.queue.pop(0)
            self._fail(req, "failed", SlotStalled(
                f"no progress for {self.stall_after} iterations at the "
                "head of the queue", rid=req.rid))

    def _open_pending(self) -> int:
        return sum(1 for _, r in self._pending if not r.done)

    # ------------------------------------------------------------- decode
    def step(self) -> int:
        """One engine iteration: one admission move (prefill chunk /
        restore) interleaved with a ``decode_block`` burst for all live
        slots.  Returns live + queued + in-prefill (terminal requests
        excluded).  Never raises for in-flight faults — failing requests
        land on :attr:`finished` with a structured status."""
        with self.telemetry.span("engine.step"):
            return self._step()

    def _step(self) -> int:
        it = self.stats["iters"]
        # crash point 0: between iterations, before any state mutates —
        # everything committed through iteration it-1 must recover
        if self.faults.active and self.faults.kill_now(it):
            raise SimulatedCrash(
                f"fault injection: kill at engine iteration {it}")
        self.stats["iters"] += 1
        self._chunk_ran = False
        self._progress = False
        now = self._clock()
        self._count_request_seconds(now)
        self._expire_deadlines(now)
        self._admit(it)
        chunk_ran = self._chunk_ran
        if not any(req is not None for req in self.live):
            self._watchdog(decoded=0)
            return len(self.queue) + self._open_pending()
        self._checkpoint(it)
        if self.faults.active:
            for b in self.faults.nan_decode_slots(it):
                if 0 <= b < self.slots:
                    self.cache = poison_slot(self.cache, b)
        kblk = self.decode_block
        self.cache = dict(self.cache, pos=jnp.asarray(self.pos, jnp.int32))
        kv_bucket = None
        if self.kv_buckets:
            # bound the whole burst's attention to the live prefix: every
            # live slot reads/writes below max(pos) + decode_block, capped
            # at the extent ladder's top (rolling caches: the window —
            # their reads are already window-bounded past the cap).  Stale
            # pos of retired slots is excluded (their rows neither read
            # sensibly nor write at all inside the bucket).
            live_pos = [int(self.pos[b]) for b, r in enumerate(self.live)
                        if r is not None]
            kv_bucket = clamped_bucket(max(live_pos) + kblk, self.kv_extent)
            self.buckets_used.add(kv_bucket)
            # step i of the burst reads the bucket for every live slot,
            # whose keys then span pos + i + 1 positions
            self._m_kv_read["decode"].inc(len(live_pos) * kblk * kv_bucket)
            self._m_kv_live["decode"].inc(
                kblk * sum(live_pos) + len(live_pos) * kblk * (kblk + 1) // 2)
        # the first dispatch per bucket key (None included — archs without
        # KV buckets still compile on their first burst) pays trace+compile
        fresh_compile = kv_bucket not in self._decode_seen
        self._decode_seen.add(kv_bucket)
        if kv_bucket is not None and kv_bucket > self._max_bucket:
            if self._max_bucket >= 0:
                self._m_climbs.inc()
            self._max_bucket = kv_bucket
        with self.telemetry.span("decode.burst",
                                 (r.rid for r in self.live if r is not None),
                                 timed=True) as burst:
            out = self._decode_n(self.params, self.cache,
                                 jnp.asarray(self.tokens), n=kblk,
                                 kv_bucket=kv_bucket, rope_len=self.rope_len,
                                 with_sentinel=self.sentinel)
            if self.sentinel:
                toks_d, self.cache, ok_d = out
                # ONE host sync per block: tokens and sentinel flags
                # fetched in a single batched transfer, not two round-trips
                toks, okh = jax.device_get((toks_d, ok_d))
            else:
                toks_d, self.cache = out
                toks = np.asarray(toks_d)
                okh = None
        dt_ms = (burst.end - burst.start) * 1e3
        # per-token latency feeds the deadline admission controller and
        # preemption slack ordering, keyed by (phase, bucket); the first
        # dispatch per bucket is tagged a compile sample and segregated —
        # a bucket-ladder climb must not poison the steady-state estimate
        # (it used to: fresh_compile was computed but never gated here)
        self.telemetry.record_latency("decode", kv_bucket, dt_ms / kblk,
                                      compiled=fresh_compile)
        self._m_decode_ms.observe(dt_ms)
        self.profiler.observe("decode", dt_ms)
        n_live = 0
        decoded = 0
        for b, req in enumerate(self.live):
            if req is None:
                continue
            if okh is not None and not bool(okh[b]):
                self._quarantine(b, req)
                if self.live[b] is not None:
                    n_live += 1
                continue
            room = min(req.max_new - len(req.out),
                       self.max_seq - 1 - int(self.pos[b]))
            take = min(kblk, max(room, 0))
            req.out.extend(int(t) for t in toks[b, :take])
            decoded += take
            if take:
                self.tokens[b, 0] = int(toks[b, take - 1])
                self.telemetry.event(req.rid, "decode", bucket=kv_bucket,
                                     tokens=take)
                self.scheduler.note_service(req.priority, take)
                self._m_class_tokens.labels(
                    priority=str(req.priority), phase="decode").inc(take)
            self.pos[b] += take
            if len(req.out) >= req.max_new or self.pos[b] >= self.max_seq - 1:
                req.done = True
                req.status = "ok"
                req.ckpt_blob = None
                self.finished.append(req)
                self._m_finished.labels(status="ok").inc()
                self.telemetry.end_span(req.rid, "ok",
                                        tokens_out=len(req.out))
                self._forget_request(req)
                self.live[b] = None
            else:
                n_live += 1
        self.stats["decode_tokens"] += decoded
        self._m_tokens.labels(phase="decode").inc(decoded)
        self._m_live.set(n_live)
        self._m_queue.set(len(self.queue))
        if chunk_ran:
            # interleaving fairness: iterations where a prefill chunk ran
            # alongside live decode slots, and whether decode progressed
            self.stats["interleave_iters"] += 1
            if decoded:
                self.stats["interleave_decode_iters"] += 1
        self._watchdog(decoded)
        return n_live + len(self.queue) + self._open_pending()

    def run(self, max_iters: Optional[int] = None) -> List[Request]:
        """Drive :meth:`step` until all work reaches a terminal state.
        ``max_iters`` is the escape hatch over the watchdog: past it, all
        in-flight and queued requests are cancelled (``SlotStalled``
        records the bound) and the engine returns instead of hanging."""
        try:
            while self.step() or self.queue or self._open_pending():
                if max_iters is not None and self.stats["iters"] >= max_iters:
                    self._abort_inflight("cancelled", SlotStalled(
                        f"run(max_iters={max_iters}) exhausted with work "
                        "outstanding"))
                    break
        finally:
            # persist the measured latency model for the next process,
            # write out the step spans and flush metrics — each a no-op
            # unless its path is configured
            self._close_checkpoint_pool()
            self.telemetry.save_warmstart()
            self.telemetry.write_step_spans()
            self.metrics.export()
            if self.store is not None:
                self.store.commit()
        return self.finished

    def profile_snapshot(self) -> Dict[str, Any]:
        """The profiler's per-kernel-family attribution.  In coarse mode
        the representative decode program is registered lazily here (its
        lowering cost lands on the caller asking for shares, never on the
        serving hot path)."""
        if (self.profiler.mode == "coarse"
                and not self.profiler.registered("decode")
                and self._decode_seen):
            kv_bucket = max((b for b in self._decode_seen if b is not None),
                            default=None)
            # re-lowering through the engine's own jitted wrapper hits the
            # executable cache for shapes the loop already ran
            lowered = self._decode_n.lower(
                self.params, self.cache, jnp.asarray(self.tokens),
                n=self.decode_block, kv_bucket=kv_bucket,
                rope_len=self.rope_len, with_sentinel=self.sentinel)
            self.profiler.register("decode", lowered.compile())
        return self.profiler.snapshot()

    def _abort_inflight(self, status: str, err: RequestError) -> None:
        for req in self.queue:
            self._fail(req, status, err)
        self.queue = []
        for row, (b, req) in enumerate(self._pending):
            if not req.done:
                self._chunked_prefill.cancel_row(row)
                self._fail(req, status, err)
        if self._chunked_prefill.active:
            self._chunked_prefill.finish()
        self._pending = []
        for b, req in enumerate(self.live):
            if req is not None:
                self.live[b] = None
                self._fail(req, status, err)
