"""jit'd public wrapper for the fused DoRA-LoRA linear.

``fused_dora(...)`` runs the compiled Pallas kernel on a TPU and the jnp
oracle elsewhere; ``interpret=True`` runs the kernel body in the Pallas
interpreter and ``interpret=False`` off-TPU raises (``kernels.dispatch``).
Batched inputs (..., K) are flattened to (M, K) and padded to tile
boundaries.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.dispatch import impl_of_interpret, resolve_impl
from repro.kernels.fused_dora.fused_dora import fused_dora_matmul
from repro.kernels.fused_dora.ref import fused_dora_ref


def fused_dora(x, w0, a_dir, a_mag, b_dir, b_mag, da_dir=None, db_mag=None,
               *, scale: float = 1.0, interpret: bool | None = None):
    if da_dir is None:
        da_dir = jnp.zeros_like(a_dir)
    if db_mag is None:
        db_mag = jnp.zeros_like(b_mag)
    impl = resolve_impl(impl_of_interpret(interpret), "fused_dora")
    if impl == "einsum":
        return fused_dora_ref(x, w0, a_dir, a_mag, b_dir, b_mag, da_dir,
                              db_mag, scale)
    batch_shape = x.shape[:-1]
    K = x.shape[-1]
    N = w0.shape[1]
    xm = x.reshape(-1, K)
    M = xm.shape[0]

    # tile sizes: shrink for small problems, keep MXU-aligned when possible
    bm = 256 if M % 256 == 0 else (128 if M % 128 == 0 else M)
    bn = 256 if N % 256 == 0 else (128 if N % 128 == 0 else N)
    bk = 512 if K % 512 == 0 else (128 if K % 128 == 0 else K)
    y = fused_dora_matmul(xm, w0, a_dir, a_mag, b_dir, b_mag, da_dir, db_mag,
                          scale=scale, bm=bm, bn=bn, bk=bk,
                          interpret=impl == "interpret")
    return y.reshape(*batch_shape, N)


__all__ = ["fused_dora", "fused_dora_ref"]
