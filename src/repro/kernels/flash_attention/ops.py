"""jit'd public wrappers: (B,S,H,dh)-layout flash attention w/ GQA.

``flash_attention_causal`` is the attention core the models train and
prefill through: forward, dq and dkv kernels under one ``custom_vjp``
(``flash_causal``), which keep scores and softmax in VMEM and skip key
blocks above the causal diagonal.  ``impl`` resolves through
``kernels.dispatch``: the compiled kernels on a TPU, the kernel bodies in
the Pallas interpreter for tests, and the jnp oracle ``attention_ref``
elsewhere.  (The models choose their off-TPU core themselves:
``models.layers.attention_path``.)

``flash_attention`` is the older forward-only kernel
(``flash_attention.flash_attention_bhsd``); no model path calls it.
``interpret=None`` runs the compiled kernel on a TPU and the jnp oracle
elsewhere; ``interpret=True`` runs the kernel body in the Pallas
interpreter; ``interpret=False`` off-TPU raises (``kernels.dispatch``).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.dispatch import impl_of_interpret, resolve_impl
from repro.kernels.flash_attention.flash_attention import flash_attention_bhsd
from repro.kernels.flash_attention.flash_causal import flash_causal
from repro.kernels.flash_attention.ref import attention_ref


def flash_block(S: int, window: int | None = None) -> int:
    """Query and key block of the forward and both backward kernels: 1024
    where it divides S and no sliding window is narrower, else 512.  On a
    v5e at S 2048, 32 heads of 128, forward and backward took 3.71 ms at
    1024, 4.31 at 512 and 7.32 at 256: the grid's per-step cost outweighs
    the masked work a larger block does on the diagonal.  At head_dim 256
    (S 4096, 8 heads) 1024 and 512 took 3.31 and 3.36 ms, but under a
    512-token window 2.69 and 2.13: every live block of a window narrower
    than the block straddles its edge, and most of its scores are masked."""
    if S % 1024 or (window is not None and window < 1024):
        return 512
    return 1024


def flash_attention_causal(q, k, v, *, window: int | None = None,
                           block: int | None = None, impl=None):
    """Causal (optionally sliding-window) self-attention.

    q (B,S,H,dh) already carries the softmax scale 1/√dh; k/v (B,S,K,dh)
    with H % K == 0 (grouped heads, K/V never repeated) → (B,S,H,dh).
    ``block`` defaults to ``flash_block(S, window)`` and must divide S.
    """
    impl = resolve_impl(impl, "flash_attention_causal")
    B, S, H, dh = q.shape
    K = k.shape[2]
    block = block or flash_block(S, window)
    if S % block:
        raise ValueError(f"block {block} does not divide the {S} tokens")
    if impl == "einsum":
        return attention_ref(q, k, v, causal=True, window=window, scale=1.0)

    # head-major copies: on a v5e they cost less end to end than kernels
    # that read each head's columns of (B, S, H·dh) in place
    def heads_major(t, n):                       # (B,S,n,dh) → (B·n, S, dh)
        return t.transpose(0, 2, 1, 3).reshape(B * n, S, dh)
    out = flash_causal(heads_major(q, H), heads_major(k, K),
                       heads_major(v, K), window, block,
                       impl == "interpret")
    return out.reshape(B, H, S, dh).transpose(0, 2, 1, 3)


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


__all__ = ["flash_attention", "flash_attention_causal", "attention_ref"]
