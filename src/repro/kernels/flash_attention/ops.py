"""jit'd public wrapper: (B,S,H,dh)-layout flash attention w/ GQA.

``interpret=None`` runs the compiled kernel on a TPU and the jnp oracle
elsewhere; ``interpret=True`` runs the kernel body in the Pallas
interpreter; ``interpret=False`` off-TPU raises (``kernels.dispatch``).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.dispatch import impl_of_interpret, resolve_impl
from repro.kernels.flash_attention.flash_attention import flash_attention_bhsd
from repro.kernels.flash_attention.ref import attention_ref


def _pick_block(S, pref):
    for b in (pref, 512, 256, 128, 64):
        if S % b == 0 and b <= S:
            return b
    return S


def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    scale: float | None = None, bq: int = 512, bk: int = 512,
                    interpret: bool | None = None):
    """q (B,Sq,H,dh); k/v (B,Sk,K,dh) GQA → (B,Sq,H,dh)."""
    impl = resolve_impl(impl_of_interpret(interpret), "flash_attention")
    if impl == "einsum":
        return attention_ref(q, k, v, causal=causal, window=window,
                             scale=scale)
    B, Sq, H, dh = q.shape
    Sk, K = k.shape[1], k.shape[2]
    if scale is None:
        scale = float(1.0 / jnp.sqrt(dh))
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, dh)
    kf = k.transpose(0, 2, 1, 3).reshape(B * K, Sk, dh)
    vf = v.transpose(0, 2, 1, 3).reshape(B * K, Sk, dh)
    out = flash_attention_bhsd(
        qf, kf, vf, scale=scale, causal=causal, window=window,
        bq=_pick_block(Sq, bq), bk=_pick_block(Sk, bk),
        q_offset=Sk - Sq, interpret=impl == "interpret")
    return out.reshape(B, H, Sq, dh).transpose(0, 2, 1, 3)


__all__ = ["flash_attention", "attention_ref"]
