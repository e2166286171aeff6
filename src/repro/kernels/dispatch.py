"""Kernel backend selection.

Backends:
  * "ref"       — pure-jnp oracle (used for the CPU multi-pod dry-run; GSPMD
                  partitions it; named_scope tags keep the operator taxonomy).
  * "pallas"    — Pallas TPU kernels (Mosaic). The deployment path on TPU.
  * "interpret" — Pallas kernels executed with interpret=True (CPU validation).

Default: "pallas" on TPU, "ref" elsewhere.  Off the TPU the
REPRO_KERNEL_BACKEND environment variable overrides it (read once per call
site: ``REPRO_KERNEL_BACKEND=interpret pytest`` runs the whole suite
through the Pallas interpreter).  On a TPU the variable may only say
"pallas": a chip run never silently serves through the interpreter or the
reference.  ``set_backend()`` / the ``use_backend`` context manager select a
backend explicitly in-process (e.g. a reference run to compare against).
A failure to enumerate devices propagates; it is never read as "no TPU".
"""
from __future__ import annotations

import os
import threading

import jax

_LOCAL = threading.local()


def decode_split_k():
    """Split-K override for the flash-decode kernel: ``REPRO_DECODE_SPLIT_K``
    pins the number of parallel partial-softmax KV segments; unset or any
    value < 1 (e.g. 0) lets the kernel pick from the KV length."""
    env = os.environ.get("REPRO_DECODE_SPLIT_K")
    if not env:
        return None
    val = int(env)
    return val if val >= 1 else None


def _env_flag(name: str, default: bool = True) -> bool:
    env = os.environ.get(name)
    if env is None or env.strip() == "":
        return default
    return env.strip().lower() not in ("0", "false", "no", "off")


def prefill_kv_buckets() -> bool:
    """``REPRO_PREFILL_KV_BUCKETS`` (default on): KV bucketing of chunked
    prefill.  Off = every chunk attends the full-extent cache — a debug
    escape hatch for bucket-related miscompares (outputs are bit-identical
    either way; only FLOPs/IO and compile counts change)."""
    return _env_flag("REPRO_PREFILL_KV_BUCKETS")


def ring_buckets() -> bool:
    """``REPRO_RING_BUCKETS`` (default on): allow bucket-slicing rolling
    (ring-buffer) KV caches while their live prefix hasn't wrapped.  Off =
    ring caches always span the full window inside bucketed programs (the
    append-only leaves still slice) — safe either way, useful to isolate
    ring-slice interactions."""
    return _env_flag("REPRO_RING_BUCKETS")


def default_backend() -> str:
    env = os.environ.get("REPRO_KERNEL_BACKEND")
    if jax.devices()[0].platform == "tpu":
        if env and env != "pallas":
            raise RuntimeError(
                f"REPRO_KERNEL_BACKEND={env!r} on a TPU: the chip serves "
                "through the Pallas kernels only (unset it, or set 'pallas')")
        return "pallas"
    return env or "ref"


def get_backend() -> str:
    return getattr(_LOCAL, "backend", None) or default_backend()


def set_backend(name: str) -> None:
    assert name in ("ref", "pallas", "interpret"), name
    _LOCAL.backend = name


class use_backend:
    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.prev = getattr(_LOCAL, "backend", None)
        set_backend(self.name)

    def __exit__(self, *exc):
        _LOCAL.backend = self.prev
