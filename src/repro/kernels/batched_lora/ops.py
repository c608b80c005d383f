"""Public dispatchers for the batched-LoRA (BGMV) kernels.

``bgmv`` / ``bgmv_mag`` route to the Pallas TPU kernel on TPU backends
and to the vectorized einsum oracle elsewhere (``kernels.dispatch``).
Tests force the kernel body with ``impl="interpret"``; ``impl="pallas"``
off-TPU raises.

Inputs accept (B, S, d_in) token blocks or (B, d_in) single-token decode
rows; ``idx`` is the (B,) int32 pool-slot vector from the AdapterStore.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.batched_lora.bgmv import bgmv_matmul, bgmv_mag_matmul
from repro.kernels.batched_lora.ref import bgmv_ref, bgmv_mag_ref
from repro.kernels.dispatch import resolve_impl
from repro.obs.tracing import named_scope

_BS = 256                       # token-block size for the Pallas grid


def _pad_tokens(x):
    """Pad S up to a block multiple for the Pallas grid (zero token rows
    contribute zero delta and are sliced back off)."""
    S = x.shape[1]
    bs = min(_BS, S)
    pad = -S % bs
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x, S, bs


def bgmv(x, a_pool, b_pool, idx, *, scale: float = 1.0, impl=None,
         ranks=None):
    """y[i] = scale · (x[i] @ a_pool[idx[i]]) @ b_pool[idx[i]].

    ``ranks`` (L,) int32: heterogeneous pool — rank rows ≥ ranks[idx[i]]
    are masked out of row i (see bgmv.py)."""
    impl = resolve_impl(impl, "bgmv")
    squeeze = x.ndim == 2
    if squeeze:
        x = x[:, None, :]
    with named_scope("kernels/bgmv"):
        if impl == "einsum":
            y = bgmv_ref(x, a_pool, b_pool, idx, scale, ranks=ranks)
        else:
            xp, S, bs = _pad_tokens(x)
            y = bgmv_matmul(xp, a_pool, b_pool, idx, ranks, scale=scale,
                            bs=bs, interpret=impl == "interpret")
            y = y[:, :S]
    return y[:, 0] if squeeze else y


def bgmv_mag(x, a_dir, a_mag, b_mag, dmag_pool, b_dir, idx, *,
             scale: float = 1.0, impl=None, ranks=None):
    """Decomposed-DoRA magnitude path (raw-delta pool):
    y[i] = scale · (((x[i] ⊙ a_mag) @ a_dir)
                    ⊙ (b_mag + dmag_pool[idx[i]])) @ b_dir.

    ``ranks`` (L,) int32: heterogeneous pool — the magnitude product ≥
    the slot's rank is masked per row (shared b_mag rows included, so a
    rank-0 slot serves the bare backbone)."""
    impl = resolve_impl(impl, "bgmv_mag")
    squeeze = x.ndim == 2
    if squeeze:
        x = x[:, None, :]
    with named_scope("kernels/bgmv_mag"):
        if impl == "einsum":
            y = bgmv_mag_ref(x, a_dir, a_mag, b_mag, dmag_pool, b_dir, idx,
                             scale, ranks=ranks)
        else:
            xp, S, bs = _pad_tokens(x)
            y = bgmv_mag_matmul(xp, a_dir, a_mag, b_mag, dmag_pool, b_dir,
                                idx, ranks, scale=scale, bs=bs,
                                interpret=impl == "interpret")
            y = y[:, :S]
    return y[:, 0] if squeeze else y


__all__ = ["bgmv", "bgmv_mag", "bgmv_ref", "bgmv_mag_ref"]
