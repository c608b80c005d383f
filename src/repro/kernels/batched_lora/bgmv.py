"""Pallas TPU kernel: batched-gather LoRA (BGMV, Punica / S-LoRA style).

One mixed batch crosses many tenants: row i of ``x`` carries the tokens
of the tenant whose adapter occupies pool slot ``idx[i]``.  The kernel
computes, per row,

    y[i] = scale · (x[i] @ A[idx[i]]) @ B[idx[i]]

without ever merging an adapter into the backbone and without
materializing gathered per-row adapter copies: the index vector rides in
scalar-prefetch memory, so each grid step's BlockSpec index map selects
the right pool slot and the DMA engine streams exactly one
(d_in, r) + (r, d_out) adapter pair per row into VMEM.

Grid: (B, S/bs) — token blocks innermost, so a row's adapter pair keeps
the same block index across its token blocks and Pallas skips the
re-fetch (revisiting an unchanged block index is a no-op DMA).

A second entry point covers the paper's decomposed-DoRA deployment
shape, where tenants share every direction/magnitude factor and differ
only in their RAW per-rank magnitude delta (ΔB_M — a few hundred bytes
per tenant); the effective magnitude forms inside the kernel:

    y[i] = scale · (((x[i] ⊙ A_mag) @ A_dir) ⊙ (B_mag + Δmag[idx[i]])) @ B_dir

Here only the tiny (1, 1, r) delta block is gathered per row; the
shared factors load once and stay VMEM-resident across the whole grid.
The 1-D factors ride as (1, n) rows and the delta pool as
(n_slots, 1, r), so every block's last two dims either equal the
array's or tile by (8, 128) — Mosaic's layout rule.

Heterogeneous pools: slots may hold adapters of different ranks, padded
to the pool's r_max.  A second scalar-prefetch vector carries each row's
rank and the kernel masks intermediate columns ≥ that rank before the
up-projection — so a freed slot re-registered at a lower rank can never
leak its previous occupant's high-rank rows, and the masked result is
bit-identical to running the tenant's own-rank adapter unpadded.
Because the magnitude pool stores the delta raw, the same mask covers
the magnitude path: a rank-r tenant is served the first r rank rows of
the *shared* model plus its delta (exactly the federated re-mask
semantics), and a rank-0 slot — the null slot, or a freed one —
contributes nothing at all.

VMEM working set (bs=256, d=1024, r=16, f32): x(256·1024) + a(1024·16)
+ b(16·1024) + out(256·1024) ≈ 2.2 MB « 16 MB v5e VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _imap(block):
    """Adapt a BlockSpec index map to absorb trailing scalar-prefetch
    refs: index maps see every prefetch operand, and the ranked kernels
    add a per-row rank vector that block selection never consults."""
    def f(i, s, idx_ref, *rest):
        return block(i, s, idx_ref)
    return f


def _bgmv_kernel(idx_ref, x_ref, a_ref, b_ref, o_ref, *, scale: float):
    del idx_ref  # consumed by the BlockSpec index maps
    x = x_ref[0]                                          # (bs, d_in)
    h = jax.lax.dot_general(
        x, a_ref[0].astype(x.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # (bs, r)
    y = jax.lax.dot_general(
        h.astype(x.dtype), b_ref[0].astype(x.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # (bs, d_out)
    o_ref[0] = (y * scale).astype(o_ref.dtype)


def _bgmv_ranked_kernel(idx_ref, rank_ref, x_ref, a_ref, b_ref, o_ref, *,
                        scale: float):
    """Mixed-rank variant: a second scalar-prefetch vector carries this
    row's adapter rank; intermediate columns at or above it are masked
    before the up-projection, so a slot padded to r_max — or holding
    stale rows from a previous higher-rank occupant — contributes exactly
    its own rank."""
    del idx_ref
    i = pl.program_id(0)
    x = x_ref[0]                                          # (bs, d_in)
    h = jax.lax.dot_general(
        x, a_ref[0].astype(x.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # (bs, r_max)
    keep = (jax.lax.broadcasted_iota(jnp.int32, h.shape, 1)
            < rank_ref[i])
    h = jnp.where(keep, h, 0.0)
    y = jax.lax.dot_general(
        h.astype(x.dtype), b_ref[0].astype(x.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # (bs, d_out)
    o_ref[0] = (y * scale).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "bs", "interpret"))
def bgmv_matmul(x, a_pool, b_pool, idx, ranks=None, *, scale: float = 1.0,
                bs: int = 256, interpret: bool = False):
    """x (B, S, d_in), pools (n_slots, d_in, r) / (n_slots, r, d_out),
    idx (B,) int32 → (B, S, d_out) per-row adapter deltas.  ``ranks``
    (n_slots,) int32: per-slot adapter ranks for heterogeneous pools —
    rank rows ≥ ranks[idx[i]] are masked out of row i."""
    B, S, d_in = x.shape
    r = a_pool.shape[-1]
    d_out = b_pool.shape[-1]
    bs = min(bs, S)
    assert S % bs == 0, (S, bs)
    grid = (B, S // bs)
    ranked = ranks is not None
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 if ranked else 1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bs, d_in),
                         _imap(lambda i, s, idx_ref: (i, s, 0))),
            pl.BlockSpec((1, d_in, r),
                         _imap(lambda i, s, idx_ref: (idx_ref[i], 0, 0))),
            pl.BlockSpec((1, r, d_out),
                         _imap(lambda i, s, idx_ref: (idx_ref[i], 0, 0))),
        ],
        out_specs=pl.BlockSpec((1, bs, d_out),
                               _imap(lambda i, s, idx_ref: (i, s, 0))),
    )
    kernel = (functools.partial(_bgmv_ranked_kernel, scale=scale) if ranked
              else functools.partial(_bgmv_kernel, scale=scale))
    args = (idx.astype(jnp.int32),)
    if ranked:
        # gather per-row ranks host-side of the grid: rank_ref[i] in the
        # kernel is then a plain scalar-prefetch load
        args = args + (jnp.take(jnp.asarray(ranks, jnp.int32),
                                idx.astype(jnp.int32), axis=0),)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, d_out), x.dtype),
        interpret=interpret,
    )(*args, x, a_pool, b_pool)


def _bgmv_mag_kernel(idx_ref, x_ref, adir_ref, amag_ref, bmag_ref, dmag_ref,
                     bdir_ref, o_ref, *, scale: float):
    del idx_ref
    x = x_ref[0]                                          # (bs, d_in)
    xs = x * amag_ref[...].astype(x.dtype)                # (1, d_in) row
    h = jax.lax.dot_general(
        xs, adir_ref[...].astype(x.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # (bs, r)
    # effective magnitude: shared B_mag + this row's raw ΔB_M — the same
    # single addition the merged lora_delta path performs
    h = h * (bmag_ref[...] + dmag_ref[0])                 # (1, r) row
    y = jax.lax.dot_general(
        h.astype(x.dtype), bdir_ref[...].astype(x.dtype),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # (bs, d_out)
    o_ref[0] = (y * scale).astype(o_ref.dtype)


def _bgmv_mag_ranked_kernel(idx_ref, rank_ref, x_ref, adir_ref, amag_ref,
                            bmag_ref, dmag_ref, bdir_ref, o_ref, *,
                            scale: float):
    """Mixed-rank magnitude variant: intermediate columns at or above
    this row's rank are masked AFTER the magnitude product, so a rank-r
    tenant is served the first r rank rows of the shared model plus its
    delta — and a rank-0 (null/freed) slot contributes nothing."""
    del idx_ref
    i = pl.program_id(0)
    x = x_ref[0]                                          # (bs, d_in)
    xs = x * amag_ref[...].astype(x.dtype)
    h = jax.lax.dot_general(
        xs, adir_ref[...].astype(x.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # (bs, r)
    h = h * (bmag_ref[...] + dmag_ref[0])
    keep = (jax.lax.broadcasted_iota(jnp.int32, h.shape, 1)
            < rank_ref[i])
    h = jnp.where(keep, h, 0.0)
    y = jax.lax.dot_general(
        h.astype(x.dtype), bdir_ref[...].astype(x.dtype),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # (bs, d_out)
    o_ref[0] = (y * scale).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "bs", "interpret"))
def bgmv_mag_matmul(x, a_dir, a_mag, b_mag, dmag_pool, b_dir, idx,
                    ranks=None, *, scale: float = 1.0, bs: int = 256,
                    interpret: bool = False):
    """Decomposed-DoRA magnitude path: shared a_dir (d_in, r) /
    a_mag (d_in,) / b_mag (r,) / b_dir (r, d_out); raw-delta pool
    dmag_pool (n_slots, r) gathered per row via idx (B,).
    x (B, S, d_in) → (B, S, d_out).  ``ranks`` (n_slots,) int32 masks
    the magnitude product ≥ the slot's rank (shared rows included)."""
    B, S, d_in = x.shape
    r = a_dir.shape[-1]
    d_out = b_dir.shape[-1]
    bs = min(bs, S)
    assert S % bs == 0, (S, bs)
    grid = (B, S // bs)
    ranked = ranks is not None
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 if ranked else 1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bs, d_in),
                         _imap(lambda i, s, idx_ref: (i, s, 0))),
            pl.BlockSpec((d_in, r), _imap(lambda i, s, idx_ref: (0, 0))),
            pl.BlockSpec((1, d_in), _imap(lambda i, s, idx_ref: (0, 0))),
            pl.BlockSpec((1, r), _imap(lambda i, s, idx_ref: (0, 0))),
            # the pool rides as (n_slots, 1, r): a (1, 1, r) block's last
            # two dims equal the array's, which Mosaic's (8, 128) tiling
            # rule needs — a (1, r) block of (n_slots, r) breaks it
            pl.BlockSpec((1, 1, r),
                         _imap(lambda i, s, idx_ref: (idx_ref[i], 0, 0))),
            pl.BlockSpec((r, d_out), _imap(lambda i, s, idx_ref: (0, 0))),
        ],
        out_specs=pl.BlockSpec((1, bs, d_out),
                               _imap(lambda i, s, idx_ref: (i, s, 0))),
    )
    kernel = (functools.partial(_bgmv_mag_ranked_kernel, scale=scale)
              if ranked else functools.partial(_bgmv_mag_kernel, scale=scale))
    args = (idx.astype(jnp.int32),)
    if ranked:
        args = args + (jnp.take(jnp.asarray(ranks, jnp.int32),
                                idx.astype(jnp.int32), axis=0),)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, d_out), x.dtype),
        interpret=interpret,
    )(*args, x, a_dir, a_mag.astype(jnp.float32).reshape(1, d_in),
      b_mag.astype(jnp.float32).reshape(1, r),
      dmag_pool.astype(jnp.float32).reshape(-1, 1, r), b_dir)
