"""Pallas TPU kernel: dequant-fused weight-only quantized matmul.

Decode is bytes-bound on backbone weights: at serving batch sizes the
MXU idles while HBM streams each (d_in, d_out) f32 kernel.  Storing the
kernel as int8 (or packed int4) plus per-group f32 scales cuts that
stream ~4× (~8×), and this kernel dequantizes INSIDE the matmul tile:
the quantized block and its scales are DMA'd to VMEM, widened and
scaled in-register, and fed straight to the MXU — a full-precision
weight matrix never exists in HBM.

Grid (M/bm, N/bn, Kq/tk), the K axis innermost: each step streams one
(tk, bn) quantized weight block against (bm, tk) activation blocks and
adds into an f32 (bm, bn) VMEM accumulator, written out on the last K
step.  Tiling K keeps the working set bounded at any d_in: full-K tiles
ran out of VMEM at the d_ff=11008 down-projection.

Layouts are ``ref.py``'s: int8 plain; int4 packed two-nibbles-per-byte
along K with a +8 bias — packed row p holds rows 2p (low nibble) and
2p+1 (high nibble).  Rather than interleave the nibbles back into rows
in VMEM, the int4 path takes the activations split into their even and
odd columns (``x[:, 0::2]``, ``x[:, 1::2]``) and sums two matmuls over
the packed rows.  Scales ride as (G, 1, N) so a K tile that spans ``ng``
whole groups takes an (ng, 1, bn) block whose last two dims tile
legally; K tiles never straddle a group boundary.

VMEM working set (bm=bn=256, tk=512, f32 dequant): x(256·512·4) +
q(512·256) + w(512·256·4) + acc(256·256·4) ≈ 1.4 MB, double-buffered
inputs included well under the scoped VMEM limit.  Activations are
widened to f32 before the MXU — correct, not yet tuned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dequant(codes, s):
    """(tk, bn) int32 codes × (ng, 1, bn) group scales → (tk, bn) f32."""
    tk, bn = codes.shape
    ng = s.shape[0]
    w = codes.astype(jnp.float32)
    if ng == 1:
        return w * s[0]
    return (w.reshape(ng, tk // ng, bn) * s).reshape(tk, bn)


def _qmm_kernel(*refs, n_x: int):
    x_refs, (q_ref, s_ref, o_ref, acc_ref) = refs[:n_x], refs[n_x:]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.int32)                      # (tk, bn)
    if n_x == 1:                                          # int8
        codes = (q,)
    else:                                                 # packed int4
        codes = ((q & 0xF) - 8, (q >> 4) - 8)             # rows 2p, 2p+1
    s = s_ref[...]                                        # (ng, 1, bn)
    for x_ref, c in zip(x_refs, codes):
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...].astype(jnp.float32), _dequant(c, s),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def pick_tk(kq: int, rows_per_group: int, target: int = 512) -> int:
    """K tile: a divisor of ``kq`` that is a multiple of 128 (the
    activation block's lane dim) and covers whole groups or sits inside
    one — the largest such tile ≤ ``target``, else the smallest above
    it, else the full K."""
    legal = [t for t in range(128, kq + 1, 128)
             if kq % t == 0 and (t % rows_per_group == 0
                                 or rows_per_group % t == 0)]
    small = [t for t in legal if t <= target]
    return max(small) if small else min(legal, default=kq)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def quant_matmul_kernel(x, q, scale, *, bm: int = 256, bn: int = 256,
                        interpret: bool = False):
    """x (M, d_in) @ dequant(q, scale) → (M, d_out).

    q int8 (d_in, d_out) or packed-int4 uint8 (d_in/2, d_out); scale
    (G, d_out) f32.  M and d_out must be block multiples — the ops.py
    dispatcher pads and slices."""
    M, _ = x.shape
    kq, N = q.shape                                       # kq: K or K/2
    assert M % bm == 0 and N % bn == 0, (M, N, bm, bn)
    G = scale.shape[0]
    gq = kq // G                                          # q rows per group
    tk = pick_tk(kq, gq)
    ng = max(1, tk // gq)                                 # groups per tile
    xs = (x,) if q.dtype == jnp.int8 else (x[:, 0::2], x[:, 1::2])
    x_spec = pl.BlockSpec((bm, tk), lambda i, j, k: (i, k))
    return pl.pallas_call(
        functools.partial(_qmm_kernel, n_x=len(xs)),
        grid=(M // bm, N // bn, kq // tk),
        in_specs=[x_spec] * len(xs) + [
            pl.BlockSpec((tk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((ng, 1, bn),
                         lambda i, j, k: (k * tk // (gq * ng), 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*xs, q, scale.reshape(G, 1, N))
