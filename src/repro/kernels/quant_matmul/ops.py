"""Public dispatcher for the dequant-fused quantized matmul, plus the
``quantize_backbone`` pass that produces the quantized param tree.

``quant_matmul`` routes to the Pallas TPU kernel on TPU backends and to
the jnp oracle elsewhere (``kernels.dispatch``).  Tests force the kernel
body with ``impl="interpret"``; ``impl="pallas"`` off-TPU raises.

A quantized leaf is a dict ``{"kernel_q", "kernel_scale"}`` replacing
the f32 ``{"kernel"}`` — ``models/layers.linear`` detects the shape and
dispatches here; the LoRA/BGMV overlay leaves ride alongside untouched,
so adapters stay full precision on top of the quantized backbone.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import resolve_impl
from repro.kernels.quant_matmul.quant_matmul import quant_matmul_kernel
from repro.kernels.quant_matmul.ref import (dequantize, quant_matmul_ref,
                                            quantize_int4, quantize_int8,
                                            unpack_int4)
from repro.obs.tracing import named_scope
from repro.utils import pytree as pt

_BM = 256                       # token-block size for the Pallas grid
_BN = 256                       # output-channel block size

# the backbone leaves that quantize: attention + FFN projection kernels.
# Embeddings, norms, biases, the LM head, and MoE router/expert tables
# stay f32 (see docs/quantization.md) — they either carry logit-critical
# precision or bypass layers.linear entirely.
_PROJ_RX = re.compile(r"(?:^|/)(?:q|k|v|o|gate|up|down)_proj/kernel$")


def quant_matmul(x, q, scale, *, impl=None):
    """x (..., d_in) @ dequant(q, scale) → (..., d_out).

    ``q`` int8 (d_in, d_out) or packed-int4 uint8 (d_in/2, d_out);
    ``scale`` (G, d_out) f32 per-channel (G=1) or per-group scales."""
    impl = resolve_impl(impl, "quant_matmul")
    with named_scope("kernels/quant_matmul"):
        if impl == "einsum":
            return quant_matmul_ref(x, q, scale)
        lead, d_in = x.shape[:-1], x.shape[-1]
        xm = x.reshape(-1, d_in)
        M, N = xm.shape[0], q.shape[-1]
        bm, bn = min(_BM, M), min(_BN, N)
        pm, pn = -M % bm, -N % bn
        if pm:
            xm = jnp.pad(xm, ((0, pm), (0, 0)))
        if pn:                   # zero scales → padded columns dequant to 0
            q = jnp.pad(q, ((0, 0), (0, pn)))
            scale = jnp.pad(scale, ((0, 0), (0, pn)))
        y = quant_matmul_kernel(xm, q, scale, bm=bm, bn=bn,
                                interpret=impl == "interpret")
        return y[:M, :N].reshape(*lead, N)


def quantize_backbone(base, mode: str, *, group_size=None):
    """Return a copy of the base param tree with every attention/FFN
    projection kernel replaced by ``{kernel_q, kernel_scale}`` in
    ``mode`` ("int8" | "int4").

    Stacked block kernels (n_sb, d_in, d_out) quantize per superblock
    slice (the leading axis broadcasts through the per-channel max), so
    ``lax.scan`` over the blocks hands each layer a clean 2-D quantized
    leaf.  Everything else — embeddings, norms, biases, the LM head,
    MoE router/experts — is carried through untouched, as is any LoRA
    overlay already merged into the tree."""
    if mode not in ("int8", "int4"):
        raise ValueError(
            f"backbone_quant must be 'int8' or 'int4', got {mode!r}")
    quant = quantize_int8 if mode == "int8" else quantize_int4
    out: dict = {}
    for p, leaf in jax.tree_util.tree_leaves_with_path(base):
        path = pt.path_str(p)
        if _PROJ_RX.search(path) and leaf.ndim in (2, 3):
            qv, s = quant(leaf, group_size=group_size)
            stem = path[: -len("kernel")]
            pt.set_leaf(out, stem + "kernel_q", qv)
            pt.set_leaf(out, stem + "kernel_scale", s)
        else:
            pt.set_leaf(out, path, leaf)
    return out


__all__ = ["quant_matmul", "quant_matmul_ref", "quant_matmul_kernel",
           "quantize_backbone", "quantize_int8", "quantize_int4",
           "dequantize", "unpack_int4"]
