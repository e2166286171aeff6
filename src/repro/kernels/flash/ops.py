"""Backend-dispatching entry point for (prefill) attention."""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import dispatch
from repro.kernels.flash import ref as _ref


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    q_offset=None,
                    kv_wrap=None,
                    ring_len: Optional[int] = None) -> jax.Array:
    """``q_offset`` (None, scalar, or [B] int32): per-row query-position
    offset for chunked prefill against an already-filled KV prefix.

    ``kv_wrap`` ([B] int32) + static ``ring_len``: ring-buffer KV layout
    for chunked prefill over rolling sliding-window caches — the first
    ``ring_len`` KV slots are a ring with modulus ``window`` and per-row
    write cursor ``kv_wrap``, the rest are the in-flight chunk (see
    ``repro.kernels.flash.ref.ring_kv_positions``).

    Callers bound ``Skv`` to the live prefix via KV bucketing
    (``repro.serving.bucketing``); inside the kernel the per-row causal
    block-skip early-exits past each row's ``q_offset + Sq``."""
    backend = dispatch.get_backend()
    with jax.named_scope("attn_core"):
        if backend == "ref":
            return _ref.attention_ref(q, k, v, causal=causal, window=window,
                                      q_offset=0 if q_offset is None
                                      else q_offset,
                                      kv_wrap=kv_wrap, ring_len=ring_len)
        from repro.kernels.flash.kernel import flash_attention_pallas
        # the kernel's call takes this scope's name in the compiled program
        # (and in a device trace), with the KV rows it was given, since
        # its result shape does not show them
        with jax.named_scope(f"flash_prefill_kv{k.shape[2]}"):
            return flash_attention_pallas(
                q, k, v, causal=causal, window=window, q_offset=q_offset,
                kv_wrap=kv_wrap, ring_len=ring_len,
                interpret=(backend == "interpret"))
