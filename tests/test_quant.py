"""Quantized-backbone numerics (tentpole a of the quantized hot paths).

Covers the int8/int4 weight codecs (round-trip error within half a
quantization bin, exact zeros, pack/unpack inverses), the dequant-fused
Pallas matmul against its XLA oracle — forced through the kernel body
with ``impl="interpret"`` on CPU — including shapes that exercise the
pad-and-slice grid path, the ``quantize_backbone`` leaf-coverage
contract (projection kernels quantize, logit-critical leaves stay f32),
and end-to-end quantized serving: bit-exact engine↔reference parity on
the same quantized tree plus bounded logit drift vs the f32 backbone.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import given_seeds

from repro.kernels.quant_matmul.ops import quant_matmul, quantize_backbone
from repro.kernels.quant_matmul.ref import (dequantize, quant_matmul_ref,
                                            quantize_int4, quantize_int8,
                                            unpack_int4)
from repro.models import model as M
from repro.models.config import ArchConfig
from repro.utils import pytree as pt

CFG = ArchConfig(name="quant-t", family="dense", n_layers=2, d_model=32,
                 n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=64,
                 dtype="float32", lora_rank=4, lora_dropout=0.0)
RNG = np.random.default_rng(7)


def _w(d_in, d_out, seed=0):
    return np.random.default_rng(seed).normal(
        size=(d_in, d_out)).astype(np.float32) * 0.1


# ---------------------------------------------------------------------------
# codec round-trips
# ---------------------------------------------------------------------------

@given_seeds()
def test_int8_roundtrip_within_half_bin(seed):
    """Round-to-nearest: |dequant(quantize(w)) − w| ≤ scale/2 everywhere,
    per-channel and per-group."""
    w = _w(32, 24, seed)
    for gs in (None, 8):
        q, s = quantize_int8(w, group_size=gs)
        assert q.dtype == jnp.int8 and s.shape == ((1, 24) if gs is None
                                                  else (4, 24))
        err = np.abs(np.asarray(dequantize(q, s)) - w)
        bound = np.repeat(np.asarray(s), 32 // s.shape[0], axis=0) / 2
        assert (err <= bound + 1e-7).all()


@given_seeds()
def test_int4_roundtrip_within_half_bin(seed):
    w = _w(32, 24, seed)
    for gs in (None, 16):
        q, s = quantize_int4(w, group_size=gs)
        assert q.dtype == jnp.uint8 and q.shape == (16, 24)
        codes = np.asarray(unpack_int4(q))
        assert codes.shape == (32, 24)
        assert codes.min() >= -7 and codes.max() <= 7
        err = np.abs(np.asarray(dequantize(q, s)) - w)
        bound = np.repeat(np.asarray(s), 32 // s.shape[0], axis=0) / 2
        assert (err <= bound + 1e-7).all()


def test_zero_channels_dequantize_to_exact_zero():
    """The scale floor keeps all-zero channels exactly zero through the
    round-trip — rank-masked rows must survive quantization bit-for-bit."""
    w = _w(16, 8, 3)
    w[:, -2:] = 0.0
    for quant in (quantize_int8, quantize_int4):
        out = np.asarray(dequantize(*quant(w)))
        np.testing.assert_array_equal(out[:, -2:], 0.0)


def test_stacked_superblock_leaves_quantize_per_slice():
    """A scanned (n_sb, d_in, d_out) kernel stack quantizes each slice
    with its own scales — identical to quantizing the slices alone."""
    w = np.stack([_w(16, 12, s) for s in range(3)])
    q, s = quantize_int8(w)
    assert q.shape == (3, 16, 12) and s.shape == (3, 1, 12)
    for i in range(3):
        qi, si = quantize_int8(w[i])
        np.testing.assert_array_equal(np.asarray(q[i]), np.asarray(qi))
        np.testing.assert_array_equal(np.asarray(s[i]), np.asarray(si))


def test_codec_error_cases():
    with pytest.raises(ValueError, match="even d_in"):
        quantize_int4(_w(15, 8))
    with pytest.raises(ValueError, match="does not divide"):
        quantize_int8(_w(16, 8), group_size=5)
    with pytest.raises(ValueError, match="unknown quant_matmul impl"):
        quant_matmul(jnp.ones((2, 16)), *quantize_int8(_w(16, 8)),
                     impl="cuda")
    with pytest.raises(ValueError, match="backbone_quant"):
        quantize_backbone({}, "fp8")


# ---------------------------------------------------------------------------
# kernel vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(8, 64, 48),      # single tile
                                   (300, 96, 80),    # pad M and N
                                   (2, 3, 32, 24)])  # leading batch dims
@pytest.mark.parametrize("mode", ["int8", "int4"])
@pytest.mark.parametrize("gs", [None, 16])
def test_kernel_matches_oracle(shape, mode, gs):
    """The Pallas kernel body (interpret mode on CPU) must match the XLA
    dequant-matmul oracle on every layout: int8/int4, per-channel and
    grouped scales, and grids that need the pad-and-slice path."""
    *lead, d_in, d_out = (1,) * (3 - len(shape)) + shape \
        if len(shape) < 3 else shape
    x = jnp.asarray(RNG.normal(size=(*lead, d_in)), jnp.float32)
    quant = quantize_int8 if mode == "int8" else quantize_int4
    q, s = quant(jnp.asarray(_w(d_in, d_out, 5)), group_size=gs)
    got = quant_matmul(x, q, s, impl="interpret")
    want = quant_matmul_ref(x, q, s)
    assert got.shape == want.shape == (*lead, d_out)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_einsum_impl_is_the_oracle():
    x = jnp.asarray(RNG.normal(size=(4, 32)), jnp.float32)
    q, s = quantize_int8(jnp.asarray(_w(32, 16, 9)))
    np.testing.assert_array_equal(
        np.asarray(quant_matmul(x, q, s, impl="einsum")),
        np.asarray(quant_matmul_ref(x, q, s)))


# ---------------------------------------------------------------------------
# quantize_backbone coverage
# ---------------------------------------------------------------------------

def test_quantize_backbone_leaf_coverage():
    """Projection kernels become {kernel_q, kernel_scale}; embeddings,
    norms, and the LM head stay f32 — and the quantized tree is
    materially smaller than the f32 one."""
    base = M.init_params(jax.random.PRNGKey(0), CFG)
    qt = quantize_backbone(base, "int8")
    paths = pt.tree_paths(qt)
    assert not any(p.endswith("_proj/kernel") for p in paths)
    n_q = sum(p.endswith("kernel_q") for p in paths)
    n_s = sum(p.endswith("kernel_scale") for p in paths)
    assert n_q == n_s and n_q > 0
    for p, leaf in zip(paths, jax.tree.leaves(qt)):
        if p.endswith("kernel_q"):
            assert leaf.dtype == jnp.int8
        elif "embed" in p or "norm" in p or p.endswith("head/kernel"):
            assert leaf.dtype == jnp.float32, p
    assert pt.tree_bytes(qt) < 0.55 * pt.tree_bytes(base)
    # int4 packs two codes per byte along d_in
    q4 = quantize_backbone(base, "int4")
    for p, leaf in zip(pt.tree_paths(q4), jax.tree.leaves(q4)):
        if p.endswith("kernel_q"):
            assert leaf.dtype == jnp.uint8
            assert leaf.shape[-2] == pt.tree_get(
                qt, p).shape[-2] // 2, p


def test_quantized_forward_drift_bounded():
    """End-to-end forward through the quantized backbone stays within
    the codec's noise band of the f32 model (int8 ≪ int4).  The tokens
    come from a generator of the test's own, so the drift measured does
    not depend on which tests drew from the module's RNG before."""
    base = M.init_params(jax.random.PRNGKey(0), CFG)
    rng = np.random.default_rng(7)
    batch = {"tokens": jnp.asarray(rng.integers(5, 64, size=(2, 16)),
                                   jnp.int32)}
    ref = np.asarray(M.forward(base, batch, CFG)[0])
    drift = {}
    for mode, tol in [("int8", 2e-2), ("int4", 2e-1)]:
        got = np.asarray(
            M.forward(quantize_backbone(base, mode), batch, CFG)[0])
        drift[mode] = np.abs(got - ref).max()
        assert drift[mode] < tol, (mode, drift[mode])
    assert drift["int8"] < drift["int4"]


def test_quantized_engine_matches_quantized_reference():
    """ServeEngine with cfg.backbone_quant set serves the *same* tokens
    as greedy decoding over the quantized tree directly — quantization
    happens once at engine build, not per path."""
    from repro.launch.serve import greedy_generate
    from repro.serve import AdapterStore, ServeEngine

    base = M.init_params(jax.random.PRNGKey(0), CFG)
    qcfg = dataclasses.replace(CFG, backbone_quant="int8")
    store = AdapterStore(base, CFG, n_slots=2, kind="pairs")
    eng = ServeEngine(base, qcfg, store, max_rows=2, max_prompt_len=8,
                      max_len=24, decode_chunk=4)
    prompts = np.asarray(RNG.integers(5, 64, size=(1, 8)), np.int32)
    out = eng.generate([(None, prompts[0])], n_new=5)[0]
    ref = greedy_generate(quantize_backbone(base, "int8"),
                          {"tokens": jnp.asarray(prompts)}, CFG, n_new=5)
    np.testing.assert_array_equal(out, np.asarray(ref[0]))


def test_backbone_quant_group_threads_to_engine():
    """``ArchConfig.backbone_quant_group`` must reach the engine-build
    ``quantize_backbone`` call: a grouped engine serves exactly what
    greedy decoding over the *grouped* quantized tree serves, and the
    grouped codec is a genuinely different program (finer scale grid,
    different codes) than the per-channel default."""
    from repro.launch.serve import greedy_generate
    from repro.serve import AdapterStore, ServeEngine

    assert CFG.backbone_quant_group is None          # default: per-channel
    base = M.init_params(jax.random.PRNGKey(0), CFG)

    perchan = quantize_backbone(base, "int8")
    grouped = quantize_backbone(base, "int8", group_size=16)
    for p in pt.tree_paths(grouped):
        if p.endswith("kernel_scale"):
            gs, ps = pt.tree_get(grouped, p), pt.tree_get(perchan, p)
            assert gs.shape[-2] == ps.shape[-2] * (gs.size // ps.size), p
            assert gs.size > ps.size                 # finer grid
    diff = any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(grouped),
                               jax.tree.leaves(perchan)))
    assert diff

    qcfg = dataclasses.replace(CFG, backbone_quant="int8",
                               backbone_quant_group=16)
    store = AdapterStore(base, CFG, n_slots=2, kind="pairs")
    eng = ServeEngine(base, qcfg, store, max_rows=2, max_prompt_len=8,
                      max_len=24, decode_chunk=4)
    prompts = np.asarray(RNG.integers(5, 64, size=(1, 8)), np.int32)
    out = eng.generate([(None, prompts[0])], n_new=5)[0]
    ref = greedy_generate(grouped, {"tokens": jnp.asarray(prompts)},
                          CFG, n_new=5)
    np.testing.assert_array_equal(out, np.asarray(ref[0]))
