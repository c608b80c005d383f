"""Pallas TPU kernels: causal / sliding-window flash attention and its
backward, for self-attention with grouped heads.

Three kernels under one ``custom_vjp``, all with one block size b for
queries and keys:

  forward  grid (B·H, S/b, S/b), key blocks innermost: the online softmax
           keeps scores, running max and denominator in VMEM; writes the
           output and each row's logsumexp.
  dq       grid (B·H, S/b, S/b), key blocks innermost: dq accumulates in
           VMEM.
  dkv      grid (B·K, S/b, H/K, S/b): one key block's dk and dv accumulate
           in VMEM over the query blocks of every head in its group, so
           K/V are never repeated and dk/dv are written once.

A key block above the causal diagonal, or wholly outside the sliding
window, is skipped: its step computes nothing, and the index maps clamp
to the nearest live block so the pipeline fetches nothing new for it.
Only blocks that straddle an edge of the mask compute the mask.

q arrives multiplied by the softmax scale.  Matmul operands keep the
input dtype with float32 accumulation; softmax statistics are float32.
The primal (prefill) writes its output in the input dtype.  Under
differentiation the forward writes it in float32, which the backward
keeps for di = rowsum(o · do): from an output rounded to the input
dtype, di would miss rowsum(p · dp) by the rounding of the component all values share,
and every ds of the row would carry that error into dq through the
component all keys share (on the CPU at S 2048 in bf16, dq's error
against float64 was 4.1 % that way and is 0.64 %; the chunked XLA
path's is 0.35 %).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
NT = (((1,), (1,)), ((), ()))          # a @ b.T
NN = (((1,), (0,)), ((), ()))          # a @ b


def _first_key_block(i, b, window):
    """First key block holding a key that query block i attends."""
    if window is None:
        return 0
    return jnp.maximum(i * b - window + 1, 0) // b


def _last_query_block(j, b, window, n):
    """Last query block that attends a key of key block j."""
    if window is None:
        return n - 1
    return jnp.minimum((j * b + b + window - 2) // b, n - 1)


def _run(i, j, b, window, step):
    """``step(masked)`` on a live (query block i, key block j) pair, with
    the mask only where the block straddles the diagonal or the window's
    far edge."""
    live = (j <= i) & (j >= _first_key_block(i, b, window))
    edge = j == i
    if window is not None:
        edge |= j * b <= i * b + b - 1 - window

    @pl.when(live & edge)
    def _masked():
        step(True)

    @pl.when(live & jnp.logical_not(edge))
    def _full():
        step(False)


def _mask(i, j, b, window, transposed=False):
    """(b, b) keep-mask of query block i against key block j (keys by
    query, when transposed)."""
    a0 = lax.broadcasted_iota(jnp.int32, (b, b), 0)
    a1 = lax.broadcasted_iota(jnp.int32, (b, b), 1)
    q, k = (a1, a0) if transposed else (a0, a1)
    q = q + i * b
    k = k + j * b
    keep = k <= q
    if window is not None:
        keep &= k > q - window
    return keep


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, b, window, n):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(masked):
        s = lax.dot_general(q_ref[...], k_ref[...], NT,
                            preferred_element_type=jnp.float32)
        if masked:
            s = jnp.where(_mask(i, j, b, window), s, NEG_INF)
        m_prev = m_ref[...]                            # (b, LANES), lanes equal
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_next[:, :1])
        alpha = jnp.exp(m_prev - m_next)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha[:, :1] * acc_ref[...] + lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...], NN,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_next

    _run(i, j, b, window, step)

    @pl.when(j == n - 1)
    def _finish():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / l[:, :1]).astype(o_ref.dtype)
        lse_ref[...] = jnp.transpose(m_ref[...] + jnp.log(l))[:1]


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, acc_ref,
               *, b, window, n):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(masked):
        k = k_ref[...]
        s = lax.dot_general(q_ref[...], k, NT,
                            preferred_element_type=jnp.float32)
        if masked:
            s = jnp.where(_mask(i, j, b, window), s, NEG_INF)
        p = jnp.exp(s - jnp.expand_dims(lse_ref[0], -1))
        dp = lax.dot_general(do_ref[...], v_ref[...], NT,
                             preferred_element_type=jnp.float32)
        ds = p * (dp - jnp.expand_dims(di_ref[0], -1))
        acc_ref[...] += lax.dot_general(ds.astype(k.dtype), k, NN,
                                        preferred_element_type=jnp.float32)

    _run(i, j, b, window, step)

    @pl.when(j == n - 1)
    def _finish():
        dq_ref[...] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, b, window, n, rep):
    j, r, i = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when((r == 0) & (i == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def step(masked):
        q, do = q_ref[...], do_ref[...]
        s_t = lax.dot_general(k_ref[...], q, NT,
                              preferred_element_type=jnp.float32)
        if masked:
            s_t = jnp.where(_mask(i, j, b, window, transposed=True), s_t,
                            NEG_INF)
        p_t = jnp.exp(s_t - lse_ref[...])              # (bk, bq)
        dv_acc[...] += lax.dot_general(p_t.astype(do.dtype), do, NN,
                                       preferred_element_type=jnp.float32)
        dp_t = lax.dot_general(v_ref[...], do, NT,
                               preferred_element_type=jnp.float32)
        ds_t = p_t * (dp_t - di_ref[...])
        dk_acc[...] += lax.dot_general(ds_t.astype(q.dtype), q, NN,
                                       preferred_element_type=jnp.float32)

    _run(i, j, b, window, step)

    @pl.when((r == rep - 1) & (i == n - 1))
    def _finish():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics)


def _by_query_block(b, dh, rep, window):
    """BlockSpecs of a (query head h, query block i, key block j) grid:
    the query-row block, the key/value block (dead steps clamped to a live
    block) and the per-row statistics block."""
    row = pl.BlockSpec((None, b, dh), lambda h, i, j: (h, i, 0))
    kv = pl.BlockSpec((None, b, dh), lambda h, i, j: (
        h // rep, jnp.clip(j, _first_key_block(i, b, window), i), 0))
    stat = pl.BlockSpec((None, 1, b), lambda h, i, j: (h, 0, i))
    return row, kv, stat


def _forward(q, k, v, window, b, interpret, out_dtype):
    BH, S, dh = q.shape
    n = S // b
    row, kv, stat = _by_query_block(b, dh, BH // k.shape[0], window)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, b=b, window=window, n=n),
        grid=(BH, n, n),
        in_specs=[row, kv, kv],
        out_specs=[row, stat],
        out_shape=[jax.ShapeDtypeStruct(q.shape, out_dtype),
                   jax.ShapeDtypeStruct((BH, 1, S), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((b, LANES), jnp.float32),   # running max
                        pltpu.VMEM((b, LANES), jnp.float32),   # denominator
                        pltpu.VMEM((b, dh), jnp.float32)],     # output acc
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret, name="flash_fwd",
    )(q, k, v)


def _backward(q, k, v, o, lse, do, window, b, interpret):
    BH, S, dh = q.shape
    BK = k.shape[0]
    rep = BH // BK
    n = S // b
    di = jnp.sum(o * do.astype(jnp.float32), axis=-1)[:, None, :]  # (BH,1,S)
    row, kv, stat = _by_query_block(b, dh, rep, window)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, b=b, window=window, n=n),
        grid=(BH, n, n),
        in_specs=[row, kv, kv, row, stat, stat],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((b, dh), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret, name="flash_dq",
    )(q, k, v, do, lse, di)

    def q_block(j, i):                   # clamp dead steps to a live block
        return jnp.clip(i, j, _last_query_block(j, b, window, n))

    q_row = pl.BlockSpec((None, b, dh),
                         lambda g, j, r, i: (g * rep + r, q_block(j, i), 0))
    q_stat = pl.BlockSpec((None, 1, b),
                          lambda g, j, r, i: (g * rep + r, 0, q_block(j, i)))
    kv_row = pl.BlockSpec((None, b, dh), lambda g, j, r, i: (g, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, b=b, window=window, n=n, rep=rep),
        grid=(BK, n, rep, n),
        in_specs=[q_row, kv_row, kv_row, q_row, q_stat, q_stat],
        out_specs=[kv_row, kv_row],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((b, dh), jnp.float32),
                        pltpu.VMEM((b, dh), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary",
                                "arbitrary"),
        interpret=interpret, name="flash_dkv",
    )(q, k, v, do, lse, di)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_causal(q, k, v, window, block, interpret):
    """q (B·H, S, dh), pre-scaled; k/v (B·K, S, dh), query head h reading
    key head h // (H/K) → (B·H, S, dh).  ``block`` divides S."""
    return _forward(q, k, v, window, block, interpret, q.dtype)[0]


def _fwd_rule(q, k, v, window, block, interpret):
    o, lse = _forward(q, k, v, window, block, interpret, jnp.float32)
    return o.astype(q.dtype), (q, k, v, o, lse)


def _bwd_rule(window, block, interpret, res, do):
    q, k, v, o, lse = res
    return _backward(q, k, v, o, lse, do, window, block, interpret)


flash_causal.defvjp(_fwd_rule, _bwd_rule)
