"""Cache administration utilities for the serving layer.

The cache pytrees themselves are built by ``models.lm.init_lm_cache``;
this module adds the operational pieces a serving deployment needs:
sizing (admission control), slot extraction/insertion, and host
offload/restore of individual slots (preemption & prefix reuse).

Offload blobs always carry FULL cache rows plus the slot's ``pos`` entry.
``pos`` doubles as the ring cursor of rolling sliding-window caches (slot
i holds the token with ``pos % window == i``), so a preempted request
restores bit-exactly even when the engine preempts it mid-window-wrap or
resumes it under a different KV bucket.

Integrity: blobs carry a ``__meta__`` record — a per-key crc32 of the
payload bytes (bounded to the live prefix for attention KV leaves, whose
tail rows are zeros by construction and masked on read — see
:func:`_live_rows`), a per-key schema (shape + dtype), and a single crc32
fingerprint over the schema.  :func:`restore_slot` validates the key set
against the slot template (reporting the FULL missing/extra diff), then
each key's schema and checksum, and raises
:class:`repro.serving.faults.CacheCorruption` naming the offending key —
a bit-flipped or truncated preemption/checkpoint blob can never be
scattered silently into a live continuous-batching group.
"""
from __future__ import annotations

import json
import zlib
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import ModelConfig
from repro.core.memmodel import kv_cache_bytes, ssm_state_bytes
from repro.serving.faults import CacheCorruption

#: Reserved blob key holding the JSON integrity record (not a cache leaf).
BLOB_META_KEY = "__meta__"


def cache_bytes(cfg: ModelConfig, batch: int, max_seq: int) -> int:
    """Analytic cache footprint — the serving admission controller's input."""
    return kv_cache_bytes(cfg, batch, max_seq) + ssm_state_bytes(cfg, batch)


def max_slots(cfg: ModelConfig, max_seq: int, hbm_budget: float,
              weight_bytes: float) -> int:
    """How many concurrent sequences fit next to the weights."""
    per_slot = cache_bytes(cfg, 1, max_seq)
    free = hbm_budget - weight_bytes
    return max(0, int(free // max(per_slot, 1)))


def extract_slot(cache: Any, b: int) -> Any:
    """Pull slot b out of a batched cache as a batch-1 cache (host copy).

    Jitted (slot index traced): one dispatch for the whole pytree instead
    of one eager slice per leaf — periodic checkpointing calls this on
    the serving hot path, where per-leaf dispatch overhead dominated."""
    return _extract_slot_jit(cache, jnp.asarray(b, jnp.int32))


@jax.jit
def _extract_slot_jit(cache: Any, b: jax.Array) -> Any:
    def pick(leaf):
        if leaf.ndim == 0:
            return leaf
        return jax.lax.dynamic_slice_in_dim(leaf, b, 1, axis=1)
    segs = [jax.tree_util.tree_map(pick, seg) for seg in cache["segments"]]
    # pos is [B] (batch on axis 0, unlike the [n_rep, B, ...] segment leaves)
    return {"segments": segs,
            "pos": jax.lax.dynamic_slice_in_dim(cache["pos"], b, 1, axis=0)}


def insert_slot(cache: Any, one: Any, b: int) -> Any:
    """Write a batch-1 cache into slot b (inverse of extract_slot)."""
    def ins(full, single):
        if full.ndim == 0:
            return full
        return jax.lax.dynamic_update_slice_in_dim(
            full, single.astype(full.dtype), b, axis=1)
    segs = [jax.tree_util.tree_map(ins, fs, ss)
            for fs, ss in zip(cache["segments"], one["segments"])]
    pos = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], one["pos"].astype(cache["pos"].dtype), b, axis=0)
    return {"segments": segs, "pos": pos}


def _blob_schema(arrays: Dict[str, np.ndarray]) -> Dict[str, Any]:
    return {k: [list(a.shape), str(a.dtype)]
            for k, a in sorted(arrays.items())}


def slot_schema(cache: Any) -> Dict[str, Any]:
    """The blob schema (key -> [shape, dtype]) an :func:`offload_slot` of
    this cache produces, computed from leaf metadata alone — no device
    transfer.  The durable checkpoint store fingerprints this next to the
    config so an engine never rehydrates blobs shaped for a different
    cache layout."""
    out: Dict[str, Any] = {}
    for key, leaf in _keyed_leaves(cache):
        if key == "pos":                         # [B]: batch on axis 0
            shape: Tuple[int, ...] = (1,)
        elif leaf.ndim == 0:
            shape = ()
        else:                                    # [n_rep, B, ...]
            shape = (leaf.shape[0], 1) + tuple(leaf.shape[2:])
        out[key] = [list(shape), str(leaf.dtype)]
    return {k: out[k] for k in sorted(out)}


def _schema_fingerprint(schema: Dict[str, Any]) -> str:
    return f"{zlib.crc32(json.dumps(schema, sort_keys=True).encode()):08x}"


def _payload_crc(a: np.ndarray) -> int:
    # buffer protocol, no tobytes() copy: checkpointing crc's every live
    # slot's full cache rows on the serving hot path
    return zlib.crc32(np.ascontiguousarray(a).reshape(-1).view(np.uint8))


def _live_rows(out: Dict[str, np.ndarray], pos: int) -> Dict[str, int]:
    """Which blob keys get prefix-bounded checksums, and how many rows.

    Attention KV leaves (``.../attn/k|v``, row axis 2 after slot slicing)
    are zero past the slot's live prefix by construction — rows are only
    ever written at ``pos`` and reads are masked to ``valid_len`` — so a
    checksum over the first ``min(pos, rows)`` rows covers every byte
    that can ever influence a restored slot's output.  Checkpointing
    crc's every live slot on the serving hot path; bounding the
    checksummed bytes to the live prefix is the same trick the KV bucket
    ladder plays on attention reads."""
    live: Dict[str, int] = {}
    for k, a in out.items():
        if (k.endswith(("attn/k", "attn/v")) and a.ndim > 2
                and 0 <= pos < a.shape[2]):
            live[k] = pos
    return live


def _payload_crc_live(a: np.ndarray, rows) -> int:
    if rows is None:
        return _payload_crc(a)
    return _payload_crc(np.ascontiguousarray(a[:, :, :rows]))


def _finalize_blob(out: Dict[str, np.ndarray],
                   tags: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    pos = int(out["pos"][0]) if "pos" in out else -1
    live = _live_rows(out, pos)
    schema = _blob_schema(out)
    blob: Dict[str, Any] = dict(out)
    meta = {
        "schema": schema,
        "fingerprint": _schema_fingerprint(schema),
        "crc": {k: _payload_crc_live(a, live.get(k))
                for k, a in out.items()},
    }
    if live:
        meta["live"] = live
    if tags:
        meta["tags"] = dict(tags)
    blob[BLOB_META_KEY] = json.dumps(meta)
    return blob


def blob_tags(blob: Dict[str, Any]) -> Dict[str, Any]:
    """The caller-supplied identity/class tags a blob was offloaded with
    (``{"rid": ..., "priority": ...}`` from the engine), or {} for legacy
    blobs.  Unreadable meta raises the same CacheCorruption restore
    would."""
    meta_raw = blob.get(BLOB_META_KEY)
    if meta_raw is None:
        return {}
    try:
        return dict(json.loads(meta_raw).get("tags") or {})
    except (ValueError, AttributeError, TypeError) as e:
        raise CacheCorruption(
            f"unreadable blob __meta__ record: {e}") from None


def _blob_nbytes(blob: Dict[str, Any]) -> int:
    return sum(v.nbytes for v in blob.values() if hasattr(v, "nbytes"))


def _count_bytes(metrics, name: str, nbytes: int) -> None:
    """Optional metrics hook (a :class:`repro.serving.metrics
    .MetricsRegistry`): get-or-create is one dict lookup, so threading it
    through the offload/restore hot path costs nothing when unset."""
    if metrics is not None:
        metrics.counter(name, "host<->device cache traffic").inc(nbytes)


def _keyed_leaves(tree: Any):
    """(blob key, leaf) pairs of a cache pytree, keys as ``a/b/c``."""
    return [("/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                      for p in path), leaf)
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)]


def offload_slot(cache: Any, b: int, metrics=None,
                 tags: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Host-offload one slot (preempted request / periodic checkpoint) as
    numpy arrays, plus a ``__meta__`` integrity record (per-key crc32 +
    schema fingerprint) that :func:`restore_slot` validates.  ``tags``
    (JSON-able, e.g. ``{"rid": ..., "priority": ...}``) ride in the meta
    record so a blob stays attributable to its request and priority
    class after the engine that wrote it is gone — and so restore can
    refuse a blob that was offloaded for a different request."""
    one = jax.device_get(extract_slot(cache, b))   # one batched transfer
    out = {key: np.asarray(leaf) for key, leaf in _keyed_leaves(one)}
    blob = _finalize_blob(out, tags=tags)
    _count_bytes(metrics, "repro_offload_bytes_total", _blob_nbytes(blob))
    return blob


def start_offload(cache: Any, bs, metrics=None
                  ) -> Dict[int, list]:
    """First half of a checkpoint of slots ``bs``: gather each due slot's
    rows of every leaf (and its ``pos`` entry) into fresh device buffers,
    one :func:`extract_slot` dispatch a slot, and start their copies to
    the host.  Only the due slots' bytes move; each slot's leaf is its
    own buffer, so it arrives contiguous, laid out as its blob keeps it.
    The buffers are new, so the caller may donate ``cache`` at once.
    Returns slot -> ``(key, device array)`` pairs for
    :func:`finish_offload`; ``repro_checkpoint_transfer_bytes_total``
    and ``repro_offload_bytes_total`` count the bytes moved."""
    parts: Dict[int, list] = {}
    for b in bs:
        parts[b] = _keyed_leaves(extract_slot(cache, b))
        for _, leaf in parts[b]:
            leaf.copy_to_host_async()
    nbytes = sum(leaf.nbytes for p in parts.values() for _, leaf in p)
    _count_bytes(metrics, "repro_checkpoint_transfer_bytes_total", nbytes)
    _count_bytes(metrics, "repro_offload_bytes_total", nbytes)
    return parts


def finish_offload(part: list, telemetry,
                   tags: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Second half, for one slot of :func:`start_offload`: wait for its
    host copy (span ``checkpoint.transfer``), then build its blob (span
    ``checkpoint.pack``), bit-identical to :func:`offload_slot`'s for the
    same slot.  Safe on a worker thread: ``zlib.crc32`` releases the
    GIL."""
    with telemetry.span("checkpoint.transfer"):
        out = {key: np.asarray(leaf) for key, leaf in part}
    with telemetry.span("checkpoint.pack"):
        return _finalize_blob(out, tags=tags)


def offload_slots(cache: Any, bs, telemetry, metrics=None,
                  tags: Optional[Dict[int, Dict[str, Any]]] = None
                  ) -> Dict[int, Dict[str, Any]]:
    """Host-offload SEVERAL slots at once: :func:`start_offload`, then
    :func:`finish_offload` of each slot in turn on the calling thread.
    Each returned blob is bit-identical to an :func:`offload_slot` call
    for the same slot (same keys, same ``__meta__`` record), so
    restore/validate treat them identically.  ``tags`` maps slot index ->
    that slot's tag dict."""
    parts = start_offload(cache, bs, metrics=metrics)
    return {b: finish_offload(parts[b], telemetry, (tags or {}).get(b))
            for b in bs}


def validate_blob(blob: Dict[str, Any], template_keys,
                  rid=None) -> Dict[str, np.ndarray]:
    """Check a blob's key set against ``template_keys`` and its payload
    against its own ``__meta__`` record.  Returns the payload dict (meta
    stripped); raises :class:`CacheCorruption` on the first violation —
    key-set mismatches report the full missing/extra diff, schema and
    checksum mismatches name the offending key."""
    data = {k: v for k, v in blob.items() if k != BLOB_META_KEY}
    got, want = set(data), set(template_keys)
    if got != want:
        missing = sorted(want - got)
        extra = sorted(got - want)
        raise CacheCorruption(
            "blob key set does not match the slot template: "
            f"missing={missing or '[]'} extra={extra or '[]'}", rid=rid)
    meta_raw = blob.get(BLOB_META_KEY)
    if meta_raw is None:
        return data                  # legacy blob: key-set check only
    try:
        meta = json.loads(meta_raw)
        schema, crcs = meta["schema"], meta["crc"]
        fingerprint = meta["fingerprint"]
        live = meta.get("live", {})
    except (ValueError, KeyError, TypeError) as e:
        raise CacheCorruption(f"unreadable blob __meta__ record: {e}",
                              rid=rid) from None
    if fingerprint != _schema_fingerprint(schema):
        raise CacheCorruption("blob schema fingerprint mismatch "
                              f"(recorded {fingerprint})", rid=rid)
    for k in sorted(data):
        a = data[k]
        decl = schema.get(k)
        if decl is None or decl != [list(a.shape), str(a.dtype)]:
            raise CacheCorruption(
                f"schema mismatch: got {a.shape}/{a.dtype}, blob declares "
                f"{decl}", rid=rid, key=k)
        rows = live.get(k)
        if rows is not None and not (
                a.ndim > 2 and 0 <= int(rows) < a.shape[2]):
            raise CacheCorruption(
                f"blob declares live-prefix crc over {rows} rows, which "
                f"does not fit shape {a.shape}", rid=rid, key=k)
        if _payload_crc_live(a, rows) != crcs.get(k):
            raise CacheCorruption("payload crc32 mismatch", rid=rid, key=k)
    return data


def restore_slot(cache: Any, blob: Dict[str, Any], b: int,
                 rid=None, metrics=None,
                 expect_tags: Optional[Dict[str, Any]] = None) -> Any:
    """Re-admit a previously offloaded slot.  The blob is validated first
    (:func:`validate_blob`): a malformed or bit-flipped blob raises
    :class:`CacheCorruption` describing exactly what is wrong instead of
    a bare ``KeyError`` / silent garbage scatter.  ``expect_tags`` pins
    identity: every given key must match the blob's recorded tag (legacy
    tag-less blobs pass) — restoring request A's slot from request B's
    blob is corruption even when every checksum is intact."""
    if expect_tags:
        tags = blob_tags(blob)
        for k, v in expect_tags.items():
            if k in tags and tags[k] != v:
                raise CacheCorruption(
                    f"blob identity tag {k!r} mismatch: blob carries "
                    f"{tags[k]!r}, restore expects {v!r}", rid=rid)
    one = extract_slot(cache, b)   # template structure
    leaves = _keyed_leaves(one)
    keys = [k for k, _ in leaves]
    data = validate_blob(blob, keys, rid=rid)
    for k, (_, tmpl) in zip(keys, leaves):
        if tuple(data[k].shape) != tuple(tmpl.shape):
            raise CacheCorruption(
                f"blob leaf shape {data[k].shape} does not fit the slot "
                f"template {tuple(tmpl.shape)}", rid=rid, key=k)
    vals = [jnp.asarray(data[k]) for k in keys]
    treedef = jax.tree_util.tree_structure(one)
    restored = jax.tree_util.tree_unflatten(treedef, vals)
    _count_bytes(metrics, "repro_restore_bytes_total", _blob_nbytes(data))
    return insert_slot(cache, restored, b)
