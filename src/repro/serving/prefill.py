"""Chunked-prefill subsystem: state-carrying long-context prefill.

The paper's long-context regime (TTFT inversion around ~57K tokens) makes
monolithic prefill the serving bottleneck: one O(L) forward spikes
activation memory at exactly the sequence lengths under study and stalls
every decoding slot behind it (head-of-line blocking).  This module
splits prompts into fixed-size chunks and drives them through the single
compiled :func:`repro.models.lm.lm_prefill_chunk` step, which carries
state between chunks — attention layers scatter KV at each row's running
offset with an offset causal mask, rolling sliding-window layers fold the
chunk into their ring-buffer caches with a modular mask (no rolled copy),
mamba1/mamba2 layers carry their conv + SSM states — so a 57K-token
prompt prefills in 1K-token chunks with flat peak memory and chunk-parity
with one-shot prefill.  Every decodable architecture family — dense,
windowed ("local"), SSM, hybrid, windowed-hybrid — admits through this
one path; there is no separate one-shot fallback pipeline.

Chunk/decode interleave contract (what ``ServingEngine`` relies on):

* ``ChunkedPrefill`` owns an in-flight *group*: a padded mixed-length
  batch of prompts plus a group cache.  One :meth:`ChunkedPrefill.step`
  call advances the whole group by exactly ONE chunk and returns
  immediately, so the engine can interleave one prefill chunk with one
  ``decode_block`` burst per iteration — decode makes progress on every
  engine iteration even while a long prompt is prefilling.
* Rows are *emitted* (first token + filled cache rows, ready to scatter
  into decode slots) as soon as their own prompt completes, not when the
  whole group does: short prompts sharing a group with a long one start
  decoding after their last chunk, chunks earlier than the long row's.
* Heterogeneous prompt lengths need no same-length grouping: prompts are
  right-padded onto the chunk grid and a per-row ``lengths`` vector makes
  padding inert (no SSM-state updates; stale KV is overwritten or masked
  by the decode-time valid_len, and ring-buffer caches gate their writes
  on the valid length so padding never clobbers live window history).
  Rows past the real group (batch padded to a template size) are
  zero-length and therefore complete no-ops.
* For a KV-free model the group cache template is allocated once per
  retained batch size and reused for every subsequent group (prefill is
  functional — the template itself is never mutated).  A model with KV
  caches of ``max_seq`` rows gets a fresh group cache per group, which
  its chunk steps update in place (donated), so one group cache is live.

Compiled-shape discipline: every chunk step lowers to the same
``[batch, chunk]`` program regardless of prompt length, so XLA compiles
at most one prefill program per retained batch size (times the KV bucket
rungs actually touched — a ladder that tops out at the model's largest
KV extent, i.e. the *window* for rolling architectures) and peak
activation memory is O(chunk), not O(prompt).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import ModelConfig
from repro.distributed.sharding import ShardingPlan
from repro.kernels import dispatch as kdispatch
from repro.models.lm import init_lm_cache, lm_prefill_chunk
from repro.serving.bucketing import (clamped_bucket, kv_cache_extent,
                                     rope_len_for)


def _has_attn_cache(cfg: ModelConfig) -> bool:
    """Only architectures with attention layers hold KV caches worth
    bucketing; pure-SSM stacks would pay a compile per rung for nothing."""
    return cfg.attn is not None or cfg.shared_attn is not None


def supports_chunked_prefill(cfg: ModelConfig) -> bool:
    """Chunked prefill needs causal attention with a state-carrying cache.

    Rolling sliding-window ("local") layers qualify: their ring-buffer
    caches carry the trailing window between chunks (modular scatter +
    ring-unrolling mask).  Excluded: encoder layers (bidirectional —
    every token sees the whole sequence, so there is no prefix-extension
    recurrence) and audio frontends (the serving path feeds token chunks;
    audio models embed precomputed frame features instead).  Vision
    frontends pass — token-only serving treats them as dense decoders.
    """
    if cfg.frontend == "audio":
        return False
    return "encoder" not in cfg.layer_kinds


def _make_chunk_step(cfg: ModelConfig, plan: Optional[ShardingPlan] = None):
    kv_repeat = plan.kv_repeat if plan else 1
    moe_groups = plan.moe_groups if plan else 1

    def chunk_step(params, tokens, lengths, cache, kv_bucket=None,
                   rope_len=None, with_sentinel=False):
        return lm_prefill_chunk(cfg, params, {"tokens": tokens}, cache,
                                lengths=lengths, kv_repeat=kv_repeat,
                                moe_groups=moe_groups, kv_bucket=kv_bucket,
                                rope_len=rope_len,
                                with_sentinel=with_sentinel)

    return chunk_step


# jitted chunk steps keyed by everything the closure actually depends on
# (cfg plus the plan's kv_repeat/moe_groups, plus the REPRO_RING_BUCKETS
# flag and the kernel backend — both are read at TRACE time inside
# lm_prefill_chunk, so they must key the cache or flipping either after a
# first compile would silently reuse the old trace, e.g. a reference run
# under ``dispatch.use_backend("ref")`` replaying the Pallas program):
# repeated chunked_prefill calls must reuse the compiled program, not
# re-trace.  kv_bucket and rope_len are static arguments: one compile per
# bucket-ladder rung actually touched (rope_len is constant per serving
# deployment).  ``donate`` jits a step that takes its input cache over (a
# group cache of max_seq-row KV leaves is updated in place; see
# ChunkedPrefill).
_STEP_CACHE: Dict[Tuple[ModelConfig, int, int, bool, str, bool], Any] = {}


def _jitted_chunk_step(cfg: ModelConfig, plan: Optional[ShardingPlan],
                       donate: bool = False):
    key = (cfg, plan.kv_repeat if plan else 1,
           plan.moe_groups if plan else 1, kdispatch.ring_buckets(),
           kdispatch.get_backend(), donate)
    if key not in _STEP_CACHE:
        _STEP_CACHE[key] = jax.jit(_make_chunk_step(cfg, plan),
                                   static_argnames=("kv_bucket", "rope_len",
                                                    "with_sentinel"),
                                   donate_argnames=("cache",) if donate
                                   else ())
    return _STEP_CACHE[key]


def chunk_schedule(lens: np.ndarray, chunk: int,
                   idx: int) -> Tuple[int, np.ndarray, np.ndarray]:
    """Per-chunk admission arithmetic, shared by the group scheduler and
    the host-loop helper so ragged-last-chunk / finish detection can never
    diverge between them.  Returns ``(offset, valid_lens, finished)`` for
    chunk ``idx``: how many of the chunk's tokens are valid per row, and
    which rows' prompts end inside this chunk."""
    off = idx * chunk
    clens = np.clip(lens - off, 0, chunk).astype(np.int32)
    fin = (lens > off) & (lens <= off + chunk)
    return off, clens, fin


def _cache_kv_extent(cache) -> Optional[int]:
    """KV row capacity of a cache pytree (max Skv across "k"/"v" leaves,
    stacked [n_rep, B, Skv, KV, hd]); None when no layer holds a KV cache.
    Uses the same leaf predicate the models layer slices with, so the
    selected bucket always bounds exactly the leaves that get sliced."""
    from repro.models.lm import _is_kv_leaf
    best = None
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        if _is_kv_leaf(path):
            best = max(best or 0, int(leaf.shape[2]))
    return best


def chunked_prefill(cfg: ModelConfig, params, tokens: jax.Array, cache, *,
                    chunk_size: int, lengths: Optional[Sequence[int]] = None,
                    plan: Optional[ShardingPlan] = None,
                    step=None, kv_buckets: bool = True,
                    rope_len: Optional[int] = None
                    ) -> Tuple[jax.Array, Any]:
    """Prefill ``tokens`` [B, S] (right-padded, per-row valid ``lengths``)
    in ``chunk_size`` chunks.  Drop-in replacement for
    :func:`repro.models.lm.lm_prefill` — returns (last-valid-token logits
    [B, 1, V], filled cache) — but runs the fixed-shape chunk program
    ceil(S/chunk) times instead of one O(S) program.

    ``kv_buckets`` (default on, also gated by ``REPRO_PREFILL_KV_BUCKETS``)
    bounds each chunk's attention to the live prefix: chunk ``i`` runs
    with a static KV bucket covering ``(i+1) * chunk`` rows (smallest
    power-of-two rung, capped at the model's KV extent — the *window* for
    rolling architectures), so early chunks pay early-prefix FLOPs/IO
    instead of the full extent.  Outputs are bit-identical either way.

    ``rope_len`` sizes the rope tables; it defaults to the prompt length
    when that outgrows the cache extent (rolling windows), so positions
    past the window still rotate correctly.

    ``step`` overrides the compiled chunk callable (e.g. an AOT-compiled
    executable, so benchmarks don't pay a second trace+compile); bucketing
    and rope sizing are disabled then — the executable's shapes and tables
    are fixed by its caller.
    """
    tokens = jnp.asarray(tokens)
    b, total = tokens.shape
    lens = (np.full((b,), total, np.int64) if lengths is None
            else np.asarray(lengths, np.int64))
    kv_extent = None
    aot = step is not None
    if not aot:
        step = _jitted_chunk_step(cfg, plan)
        if (kv_buckets and kdispatch.prefill_kv_buckets()
                and supports_chunked_prefill(cfg) and _has_attn_cache(cfg)):
            kv_extent = _cache_kv_extent(cache)
        if rope_len is None and _has_attn_cache(cfg):
            ext = _cache_kv_extent(cache)
            if ext is not None and ext < total:
                # rope_len is STATIC on the jitted step: round the prompt
                # length up to a power of two so nearby lengths share one
                # compiled program (values at a position are identical for
                # any sufficient table size)
                rope_len = max(ext, 1 << (total - 1).bit_length())
    n_chunks = max(1, -(-total // chunk_size))
    pad = n_chunks * chunk_size - total
    if pad:
        tokens = jnp.pad(tokens, ((0, 0), (0, pad)))
    logits = None
    for i in range(n_chunks):
        off, clens, fin = chunk_schedule(lens, chunk_size, i)
        if aot:
            lg, cache = step(params, tokens[:, off:off + chunk_size],
                             jnp.asarray(clens), cache)
        else:
            bucket = clamped_bucket(off + chunk_size, kv_extent)
            lg, cache = step(params, tokens[:, off:off + chunk_size],
                             jnp.asarray(clens), cache, kv_bucket=bucket,
                             rope_len=rope_len)
        if logits is None:
            logits = lg
        elif fin.any():
            logits = jnp.where(jnp.asarray(fin)[:, None, None], lg, logits)
    return logits, cache


class ChunkedPrefill:
    """Incremental chunked-prefill scheduler for the serving engine.

    One group at a time; :meth:`step` advances it by one chunk and reports
    rows whose prompt just completed (see module docstring for the full
    interleave contract).

    ``sentinel`` (default on) folds the per-row finiteness sentinel of
    :func:`lm_prefill_chunk` into every chunk: rows that turn non-finite
    are quarantined — their remaining chunks go inert, they never emit —
    and reported to the engine, which fails the request with
    ``DivergenceDetected`` while co-batched rows prefill on untouched.
    ``fault_plan`` (a :class:`repro.serving.fault_inject.FaultPlan`)
    optionally injects NaN into exact (chunk, row) points for testing."""

    def __init__(self, cfg: ModelConfig, params, *, max_seq: int,
                 chunk_size: int = 256,
                 plan: Optional[ShardingPlan] = None,
                 sentinel: bool = True, fault_plan=None, metrics=None):
        if not supports_chunked_prefill(cfg):
            raise ValueError(f"{cfg.name}: architecture does not support "
                             "chunked prefill")
        self.cfg = cfg
        self.params = params
        self.max_seq = max_seq
        self.chunk = int(chunk_size)
        self.sentinel = bool(sentinel)
        self._faults = fault_plan
        self.kv_repeat = plan.kv_repeat if plan else 1
        # bucket ladder top: the model's largest KV extent — max_seq for
        # append-only caches, the window for rolling ones (O(log window)
        # compiles however long the prompt grows)
        self.kv_extent = kv_cache_extent(cfg, max_seq)
        self.kv_buckets = self.kv_extent is not None
        # rolling caches span only their window: rope must cover max_seq
        self.rope_len = rope_len_for(cfg, max_seq)
        # KV leaves of max_seq rows: each group gets a fresh cache that its
        # chunks update in place (a kept template plus a chunk's input and
        # output would hold three group caches at once); KV-free models
        # keep one template a batch size and functional chunk steps
        self._fresh_cache = self.kv_extent is not None
        self._step = _jitted_chunk_step(cfg, plan,
                                        donate=self._fresh_cache)
        self._templates: Dict[int, Any] = {}
        self._group: Optional[Dict[str, Any]] = None
        # (batch, kv_bucket) combos this scheduler has dispatched: the
        # first dispatch of a combo pays trace+compile, and the engine's
        # latency model must segregate that sample from steady state.
        # (The jitted step cache is process-global, so a second scheduler
        # instance may tag an already-compiled combo "fresh" — that only
        # diverts one sample to the compile record, never poisons steady.)
        self._dispatched: set = set()
        # facts about the most recent step(), for the engine's telemetry:
        # {"bucket", "offset", "valid_tokens", "valid_per_row",
        #  "class_tokens", "fresh_compile"}
        self.last_chunk: Optional[Dict[str, Any]] = None
        # optional shared MetricsRegistry (the engine passes its own)
        self._m_chunks = self._m_quar = self._m_rows = None
        if metrics is not None:
            self._m_chunks = metrics.counter(
                "repro_prefill_chunks_total", "prefill chunks dispatched")
            self._m_quar = metrics.counter(
                "repro_prefill_rows_quarantined_total",
                "group rows removed by the prefill divergence sentinel")
            self._m_rows = metrics.gauge(
                "repro_prefill_group_rows",
                "rows still prefilling in the in-flight group")

    @property
    def active(self) -> bool:
        return self._group is not None

    @property
    def group_cache(self):
        """The in-flight group's cache (scatter emitted rows from here)."""
        assert self._group is not None
        return self._group["cache"]

    def _template(self, batch: int):
        if self._fresh_cache:
            return init_lm_cache(self.cfg, batch, self.max_seq,
                                 kv_repeat=self.kv_repeat)
        if batch not in self._templates:
            self._templates[batch] = init_lm_cache(
                self.cfg, batch, self.max_seq, kv_repeat=self.kv_repeat)
        return self._templates[batch]

    def start(self, prompts: List[np.ndarray],
              batch: Optional[int] = None,
              priorities: Optional[Sequence[int]] = None) -> None:
        """Begin a group over mixed-length ``prompts`` (1-D int arrays).
        ``batch`` pads the compiled batch dimension (rows past
        ``len(prompts)`` get zero-length prompts and are inert), bounding
        XLA compiles to one chunk program per retained batch size.

        ``prompts`` arrive in SCHEDULER order — the engine's admission
        policy decides group membership and row order; this class only
        executes the group.  ``priorities`` (parallel to ``prompts``;
        default all class 0) labels each row's priority class so
        :attr:`last_chunk` can report per-class valid-token counts — the
        DRR accounting and fairness benches read them without walking
        engine internals."""
        assert self._group is None, "one prefill group at a time"
        k = len(prompts)
        kb = batch or k
        assert kb >= k
        lens = np.zeros((kb,), np.int64)
        lens[:k] = [len(p) for p in prompts]
        if lens.max() > self.max_seq:
            raise ValueError(f"prompt length {int(lens.max())} exceeds "
                             f"max_seq {self.max_seq}")
        prios = np.zeros((kb,), np.int64)
        if priorities is not None:
            assert len(priorities) == k
            prios[:k] = np.asarray(priorities, np.int64)
        n_chunks = max(1, -(-int(lens.max()) // self.chunk))
        toks = np.zeros((kb, n_chunks * self.chunk), np.int32)
        for i, p in enumerate(prompts):
            toks[i, :len(p)] = np.asarray(p, np.int32)
        self._group = {"tokens": toks, "lens": lens, "n_chunks": n_chunks,
                       "idx": 0, "k": k, "emitted": np.zeros(kb, bool),
                       "bad": np.zeros(kb, bool), "priorities": prios,
                       "cache": self._template(kb)}

    def cancel_row(self, row: int) -> None:
        """Withdraw one group row (deadline expiry / engine quarantine):
        its remaining chunks go inert (zero valid tokens) and it will
        never emit.  Other rows are untouched; the group keeps running to
        its original chunk count."""
        g = self._group
        if g is None or not (0 <= row < g["lens"].shape[0]):
            return
        g["lens"][row] = 0
        g["emitted"][row] = True

    def step(self) -> Tuple[List[Tuple[int, int, int]], bool, List[int]]:
        """Run ONE chunk for the in-flight group.

        Returns ``(emitted, done, diverged)``: ``emitted`` lists
        ``(row, first_token, prompt_len)`` for rows whose prompt completed
        this chunk (their cache rows in :attr:`group_cache` are final and
        ready to scatter); ``done`` is True once every chunk has run —
        call :meth:`finish` afterwards; ``diverged`` lists rows whose
        sentinel tripped THIS chunk (already quarantined via
        :meth:`cancel_row` semantics — the engine owns failing their
        requests)."""
        g = self._group
        assert g is not None
        if self._faults is not None and self._faults.active:
            from repro.serving.fault_inject import poison_slot
            for r in self._faults.nan_prefill_rows(g["idx"]):
                if 0 <= r < g["lens"].shape[0]:
                    g["cache"] = poison_slot(g["cache"], r)
        off, clens, fin = chunk_schedule(g["lens"], self.chunk, g["idx"])
        ctoks = jnp.asarray(g["tokens"][:, off:off + self.chunk])
        # every row's pos <= off, so a bucket covering off + chunk (capped
        # at the extent ladder's top) bounds all of this chunk's KV reads
        # and writes to the live prefix
        kv_bucket = (clamped_bucket(off + self.chunk, self.kv_extent)
                     if self.kv_buckets and kdispatch.prefill_kv_buckets()
                     else None)
        combo = (g["lens"].shape[0], kv_bucket)
        class_tokens: Dict[int, int] = {}
        for r in range(g["k"]):
            if clens[r]:
                cls = int(g["priorities"][r])
                class_tokens[cls] = class_tokens.get(cls, 0) + int(clens[r])
        self.last_chunk = {"bucket": kv_bucket, "offset": off,
                           "valid_tokens": int(clens.sum()),
                           "valid_per_row": np.asarray(clens),
                           "class_tokens": class_tokens,
                           "fresh_compile": combo not in self._dispatched}
        self._dispatched.add(combo)
        if self._m_chunks is not None:
            self._m_chunks.inc()
        out = self._step(self.params, ctoks, jnp.asarray(clens), g["cache"],
                         kv_bucket=kv_bucket, rope_len=self.rope_len,
                         with_sentinel=self.sentinel)
        diverged: List[int] = []
        if self.sentinel:
            logits, g["cache"], ok = out
            # one [B]-bool host read per CHUNK (not per token); rows past
            # the real group and rows already done are vacuously finite
            bad = ~np.asarray(ok) & ~g["bad"] & ~g["emitted"] & (clens > 0)
            bad[g["k"]:] = False
            if bad.any():
                g["bad"] |= bad
                for r in np.nonzero(bad)[0]:
                    diverged.append(int(r))
                    self.cancel_row(int(r))
                if self._m_quar is not None:
                    self._m_quar.inc(len(diverged))
        else:
            logits, g["cache"] = out
        g["idx"] += 1
        fin &= ~g["emitted"]
        fin[g["k"]:] = False
        emitted: List[Tuple[int, int, int]] = []
        if fin.any():
            nxt = np.asarray(jnp.argmax(
                logits[:, -1, :self.cfg.vocab_size], -1), np.int32)
            emitted = [(int(r), int(nxt[r]), int(g["lens"][r]))
                       for r in np.nonzero(fin)[0]]
            g["emitted"] |= fin
        if self._m_rows is not None:
            self._m_rows.set(int((~g["emitted"][:g["k"]]).sum()))
        return emitted, g["idx"] >= g["n_chunks"], diverged

    def finish(self) -> None:
        """Retire the completed group (template is reused by the next)."""
        self._group = None
        if self._m_rows is not None:
            self._m_rows.set(0)
