"""jit'd wrappers selecting Pallas kernels (TPU) or jnp oracles (CPU).

Models call these; ``REPRO_KERNEL_MODE`` picks the backend:
  auto      — Pallas on TPU, reference elsewhere (default)
  interpret — Pallas in interpret mode (CPU correctness runs; refused on a
              TPU, where it would quietly run the interpreter)
  ref       — always the jnp oracle

Block sizes are picked to divide the shapes; a shape no legal tiling
divides runs the oracle.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

from repro.kernels import ref as _ref

MODES = ("auto", "interpret", "ref")


def kernel_mode() -> str:
    """The resolved mode: ``pallas``, ``interpret`` or ``ref``."""
    m = os.environ.get("REPRO_KERNEL_MODE", "auto")
    if m not in MODES:
        raise ValueError(f"REPRO_KERNEL_MODE={m!r}; expected one of {MODES}")
    on_tpu = jax.default_backend() == "tpu"
    if m == "auto":
        return "pallas" if on_tpu else "ref"
    if m == "interpret" and on_tpu:
        raise ValueError(
            "REPRO_KERNEL_MODE=interpret would run the Pallas interpreter on "
            "the TPU; unset it to compile the kernels")
    return m


def _block(n: int, pref: int, align: int) -> Optional[int]:
    """Largest tile <= ``pref`` that divides ``n`` and is a multiple of
    ``align``; the whole dimension when ``n <= pref`` (always a legal
    block); None when no such tile exists."""
    if n <= pref:
        return n
    for b in range(pref - pref % align, 0, -align):
        if n % b == 0:
            return b
    return None


def flash_attention(q, k, v, *, causal=True, window=0, positions=None):
    mode = kernel_mode()
    blk = _block(q.shape[1], 128, 8)
    if mode == "ref" or blk is None:
        return _ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                        positions=positions)
    from repro.kernels.flash_attention import flash_attention as fa

    return fa(q, k, v, causal=causal, window=window, q_block=blk,
              k_block=blk, interpret=(mode == "interpret"))


def decode_attention(q, k_cache, v_cache, length):
    mode = kernel_mode()
    if mode == "ref":
        return _ref.decode_attention_ref(q, k_cache, v_cache, length)
    from repro.kernels.decode_attention import decode_attention as da

    return da(q, k_cache, v_cache, length, interpret=(mode == "interpret"))


def decode_attention_capable(*, n_q_heads: int, n_kv_heads: int,
                             capacity: int, window: int = 0,
                             seq_shards: int = 1) -> bool:
    """Shape-capability probe for the flash-decode kernel: the Pallas path
    covers the plain append-cache layout only — no rolling-window ring
    validity, no sequence-sharded partial softmax — and needs whole-group
    query heads plus a cache capacity the grid can tile (C % c_block == 0
    with c_block = min(512, C)).  Callers fall back to the jnp path when
    this returns False, so ``use_pallas`` is safe to pass for any layer."""
    if window or seq_shards > 1:
        return False
    if n_kv_heads <= 0 or n_q_heads % n_kv_heads:
        return False
    return capacity <= 512 or capacity % 512 == 0


def swiglu(x, w_gate, w_up):
    mode = kernel_mode()
    orig = x.shape
    x2 = x.reshape(-1, orig[-1])
    (T, d), f = x2.shape, w_gate.shape[1]
    blocks = (_block(T, 256, 8), _block(f, 512, 128), _block(d, 512, 128))
    if mode == "ref" or None in blocks:
        out = _ref.swiglu_ref(x2, w_gate, w_up)
    else:
        from repro.kernels.swiglu import swiglu as sg

        tb, fb, db = blocks
        out = sg(x2, w_gate, w_up, t_block=tb, f_block=fb, d_block=db,
                 interpret=(mode == "interpret"))
    return out.reshape(*orig[:-1], w_gate.shape[1])
