"""Deterministic fault injection for the serving engine.

Every recovery path in the fault-tolerance layer (divergence sentinels +
checkpoint replay, blob-integrity validation, the no-progress watchdog)
is exercised reproducibly in CI by flipping faults at *exact* points: a
seeded, env-driven plan says which engine iteration poisons which slot,
which request's offload blob gets bit-flipped, and when prefill progress
freezes.  Nothing here is probabilistic at run time — the only RNG is a
``numpy`` generator seeded from ``REPRO_FAULT_SEED`` used to pick the
flipped bit, so the same spec + seed corrupts the same byte every run.

Spec grammar (``REPRO_FAULT_SPEC``)::

    spec    := clause ("," clause)*
    clause  := kind ["@" param (":" param)*]
    param   := key "=" value          # value: int, or rNN for rid keys

    nan_decode@iter=I[:slot=S][:n=N]   poison slot S's cache with NaN
                                       right before the decode burst of
                                       engine iteration >= I (N times;
                                       n=-1 -> every iteration from I on)
    nan_prefill@chunk=C[:row=R][:n=N]  poison row R of the in-flight
                                       prefill group's cache before its
                                       group-local chunk C runs
    corrupt_blob@rid=R[:n=N]           flip one bit in request R's next
                                       offload blob (preemption or
                                       checkpoint)
    stall@iter=I[:n=N]                 freeze prefill progress starting
                                       at engine iteration I (for N
                                       iterations; default forever —
                                       the watchdog's trip condition)
    kill@iter=I[:point=P][:n=N]        raise :class:`SimulatedCrash` at
                                       engine iteration >= I — a process
                                       death the durable checkpoint
                                       store must survive.  point=0
                                       (default) kills between
                                       iterations (before any state
                                       mutates); point=1 kills inside
                                       ``_checkpoint``, after blob files
                                       are staged but before the
                                       manifest commit lands

Example::

    REPRO_FAULT_SPEC="nan_decode@iter=7:slot=2,corrupt_blob@rid=r3,stall@iter=12"

The engine consumes a :class:`FaultPlan` (``FaultPlan.from_env()`` by
default, or passed explicitly for in-process tests/benches); an empty
plan short-circuits every hook, so the healthy path pays a single ``if``.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

KINDS = ("nan_decode", "nan_prefill", "corrupt_blob", "stall", "kill")

_DEFAULTS = {
    "nan_decode": {"slot": 0, "n": 1},
    "nan_prefill": {"row": 0, "n": 1},
    "corrupt_blob": {"n": 1},
    "stall": {"n": -1},
    "kill": {"point": 0, "n": 1},
}
_REQUIRED = {"nan_decode": ("iter",), "nan_prefill": ("chunk",),
             "corrupt_blob": ("rid",), "stall": ("iter",),
             "kill": ("iter",)}


class SimulatedCrash(RuntimeError):
    """A deterministic process death injected by a ``kill`` clause.

    Deliberately NOT a :class:`repro.serving.faults.RequestError`: it
    models the whole engine dying, not one request failing, so it
    escapes ``ServingEngine.run`` instead of being quarantined — exactly
    like a real SIGKILL would.  Restart-recovery tests construct a fresh
    engine over the same :class:`~repro.serving.store.CheckpointStore`
    and assert the resumed stream is bit-identical."""


@dataclass
class FaultClause:
    kind: str
    params: Dict[str, int]
    fired: int = 0

    @property
    def budget(self) -> int:
        return int(self.params["n"])

    def _spend(self) -> bool:
        if self.budget >= 0 and self.fired >= self.budget:
            return False
        self.fired += 1
        return True


def _parse_value(key: str, val: str) -> int:
    if key == "rid" and val[:1] == "r":
        val = val[1:]
    try:
        return int(val)
    except ValueError:
        raise ValueError(f"fault spec: non-integer value {val!r} for "
                         f"{key!r}") from None


def parse_spec(spec: str) -> List[FaultClause]:
    clauses = []
    for raw in filter(None, (c.strip() for c in spec.split(","))):
        kind, _, rest = raw.partition("@")
        kind = kind.strip()
        if kind not in KINDS:
            raise ValueError(f"fault spec: unknown kind {kind!r} in {raw!r} "
                             f"(known: {', '.join(KINDS)})")
        params = dict(_DEFAULTS[kind])
        for p in filter(None, rest.split(":")):
            key, eq, val = p.partition("=")
            if not eq:
                raise ValueError(f"fault spec: malformed param {p!r} in "
                                 f"{raw!r} (want key=value)")
            params[key.strip()] = _parse_value(key.strip(), val.strip())
        for req in _REQUIRED[kind]:
            if req not in params:
                raise ValueError(f"fault spec: {kind!r} clause needs "
                                 f"{req}=... ({raw!r})")
        clauses.append(FaultClause(kind, params))
    return clauses


@dataclass
class FaultPlan:
    """A parsed, stateful injection plan.  Each clause tracks how many
    times it has fired; a budget of ``n=-1`` never exhausts."""

    clauses: List[FaultClause] = field(default_factory=list)
    seed: int = 0

    @classmethod
    def from_spec(cls, spec: str, seed: int = 0) -> "FaultPlan":
        return cls(parse_spec(spec), seed)

    @classmethod
    def from_env(cls) -> "FaultPlan":
        spec = os.environ.get("REPRO_FAULT_SPEC", "")
        seed = int(os.environ.get("REPRO_FAULT_SEED", "0") or 0)
        return cls.from_spec(spec, seed) if spec else cls([], seed)

    @property
    def active(self) -> bool:
        return bool(self.clauses)

    # ------------------------------------------------------------ hooks
    def nan_decode_slots(self, it: int) -> List[int]:
        """Slots to poison before iteration ``it``'s decode burst."""
        out = []
        for c in self.clauses:
            if (c.kind == "nan_decode" and it >= c.params["iter"]
                    and c._spend()):
                out.append(int(c.params["slot"]))
        return out

    def nan_prefill_rows(self, chunk_idx: int) -> List[int]:
        """Group rows to poison before group-local chunk ``chunk_idx``."""
        out = []
        for c in self.clauses:
            if (c.kind == "nan_prefill" and chunk_idx >= c.params["chunk"]
                    and c._spend()):
                out.append(int(c.params["row"]))
        return out

    def stalled(self, it: int) -> bool:
        """True when prefill progress is frozen at iteration ``it``."""
        for c in self.clauses:
            if c.kind != "stall":
                continue
            start, n = c.params["iter"], c.params["n"]
            if it >= start and (n < 0 or it < start + n):
                return True
        return False

    def kill_now(self, it: int, point: int = 0) -> bool:
        """True when a ``kill`` clause for crash-point ``point`` fires at
        engine iteration ``it`` — the engine raises
        :class:`SimulatedCrash` at that exact spot."""
        for c in self.clauses:
            if (c.kind == "kill" and c.params["point"] == point
                    and it >= c.params["iter"] and c._spend()):
                return True
        return False

    def corrupt_blob(self, rid: int,
                     blob: Dict[str, Any]) -> Dict[str, Any]:
        """Bit-flip one payload byte of ``blob`` if a clause targets
        ``rid``; returns the (possibly copied+damaged) blob."""
        return self.damage_blob(rid, blob) if self.blob_hit(rid) else blob

    def blob_hit(self, rid: int) -> bool:
        """Spend the ``corrupt_blob`` clauses targeting ``rid``: True when
        ``rid``'s blob taken now is to be damaged.  The engine asks when
        it takes a checkpoint and damages the blob when it is finished,
        so clauses are spent in the order the checkpoints were taken."""
        hit = False
        for c in self.clauses:
            if (c.kind == "corrupt_blob" and c.params["rid"] == rid
                    and c._spend()):
                hit = True
        return hit

    def damage_blob(self, rid: int,
                    blob: Dict[str, Any]) -> Dict[str, Any]:
        """The copy of ``blob`` with one payload bit flipped, chosen from
        the plan's seed and ``rid``."""
        keys = sorted(k for k, v in blob.items()
                      if isinstance(v, np.ndarray) and v.nbytes > 0)
        if not keys:
            return blob
        rng = np.random.default_rng((self.seed, rid & 0x7FFFFFFF))
        key = keys[int(rng.integers(len(keys)))]
        arr = np.array(blob[key])              # private copy
        # flip inside the checksummed region: KV leaves carry a
        # live-prefix-bounded crc (dead tail rows are zeros, masked on
        # read, and excluded from validation — a flip there would model
        # corruption that cannot affect any output)
        live = {}
        try:
            live = json.loads(blob.get("__meta__", "{}")).get("live", {})
        except (TypeError, ValueError):
            pass
        rows = live.get(key)
        region = arr if rows is None else arr[:, :, :int(rows)]
        if region.nbytes == 0:
            rows, region = None, arr
        flat = np.ascontiguousarray(region).view(np.uint8).reshape(-1)
        byte = int(rng.integers(flat.size))
        flat[byte] ^= np.uint8(1 << int(rng.integers(8)))
        if rows is None:
            arr = flat.view(arr.dtype).reshape(arr.shape)
        else:
            arr[:, :, :int(rows)] = flat.view(arr.dtype).reshape(
                region.shape)
        out = dict(blob)
        out[key] = arr
        return out


def poison_slot(cache: Any, b: int) -> Any:
    """Overwrite every float cache leaf's slot ``b`` with NaN (segment
    leaves are stacked ``[n_rep, B, ...]``; ``pos`` and other integer
    leaves are untouched).  Models NaN contamination of one request's
    KV/conv/SSM state: the next forward produces non-finite activations
    for that row only, which is exactly what the divergence sentinel must
    catch without disturbing co-batched rows."""
    def f(leaf):
        if (leaf.ndim >= 2
                and jnp.issubdtype(leaf.dtype, jnp.floating)):
            return leaf.at[:, b].set(jnp.nan)
        return leaf
    segs = [jax.tree_util.tree_map(f, seg) for seg in cache["segments"]]
    return {"segments": segs, "pos": cache["pos"]}
