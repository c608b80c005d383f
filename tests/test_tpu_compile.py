"""Ahead-of-time compiles of the serving kernels and the training
attention kernels for a TPU v5e chip, at DeepSeek-7B widths (d_model
4096, d_ff 11008, LoRA r=8, 32 heads of 128).

No chip is needed: the TPU compiler is installed and compiles for a
described topology.  This catches what interpret mode cannot — blocks
that break Mosaic's (8, 128) tiling rule, kernels that overflow VMEM —
before any chip time is spent.  The topology is described inside a
module fixture, never at import, so every test worker collects the same
tests and only the worker running this file loads the TPU library.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro import obs
from repro.kernels.batched_lora.bgmv import bgmv_mag_matmul, bgmv_matmul
from repro.kernels.flash_attention.flash_causal import flash_causal
from repro.kernels.flash_attention.ops import flash_block
from repro.kernels.quant_matmul.quant_matmul import quant_matmul_kernel

D, D_FF, R, SLOTS, ROWS = 4096, 11008, 8, 17, 8


@pytest.fixture(scope="module")
def v5e():
    """A v5e 2x2 host, described; the persistent compile cache is off
    while these compiles run (an entry written for a described chip
    cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:            # no TPU compiler in this install
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e):
    return SingleDeviceSharding(v5e.devices[0])


def _compile(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("ranked", [False, True])
@pytest.mark.parametrize("S", [1, 256])          # decode row, prefill block
def test_bgmv_compiles(one_chip, S, ranked):
    shapes = [((ROWS, S, D), jnp.bfloat16), ((SLOTS, D, R), jnp.float32),
              ((SLOTS, R, D), jnp.float32), ((ROWS,), jnp.int32)]
    if ranked:
        shapes.append(((SLOTS,), jnp.int32))
    _compile(one_chip, lambda x, a, b, i, *rk: bgmv_matmul(
        x, a, b, i, *rk, scale=4.0, bs=S), *shapes)


@pytest.mark.parametrize("ranked", [False, True])
@pytest.mark.parametrize("S", [1, 256])
def test_bgmv_mag_compiles(one_chip, S, ranked):
    shapes = [((ROWS, S, D), jnp.bfloat16), ((D, R), jnp.float32),
              ((D,), jnp.float32), ((R,), jnp.float32),
              ((SLOTS, R), jnp.float32), ((R, D), jnp.float32),
              ((ROWS,), jnp.int32)]
    if ranked:
        shapes.append(((SLOTS,), jnp.int32))
    _compile(one_chip, lambda x, ad, am, bm, dm, bd, i, *rk: bgmv_mag_matmul(
        x, ad, am, bm, dm, bd, i, *rk, scale=4.0, bs=S), *shapes)


@pytest.mark.parametrize("group", [None, 128])    # per-channel, grouped
@pytest.mark.parametrize("mode", ["int8", "int4"])
@pytest.mark.parametrize("K,N", [(D, D_FF), (D_FF, D)])   # up, down proj
def test_quant_matmul_compiles(one_chip, K, N, mode, group):
    kq, dt = (K, jnp.int8) if mode == "int8" else (K // 2, jnp.uint8)
    G = 1 if group is None else K // group
    _compile(one_chip,
             lambda x, q, s: quant_matmul_kernel(x, q, s, bm=256, bn=256),
             ((256, K), jnp.bfloat16), ((kq, N), dt), ((G, N), jnp.float32))


CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


@pytest.mark.parametrize("B,S,H,K,dh,window", [
    (2, 2048, 32, 32, 128, None),        # the training cell's attention
    (1, 4096, 4, 1, 256, 512),           # grouped heads, sliding window
    (1, 4096, 8, 8, 256, None),          # head_dim 256, global
])
def test_flash_causal_forward_and_backward_compile(one_chip, B, S, H, K, dh,
                                                   window):
    """The forward, dq and dkv kernels, each its own custom call, at the
    block the models run (``flash_block``)."""
    def loss(q, k, v):
        return flash_causal(q, k, v, window, flash_block(S, window),
                            False).astype(jnp.float32).sum()
    args = [jax.ShapeDtypeStruct((B * n, S, dh), jnp.bfloat16,
                                 sharding=one_chip) for n in (H, K, K)]
    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(*args).compile().as_text()
    assert text.count(CUSTOM_CALL) == 3


@pytest.mark.parametrize("H,K,manual_data", [
    (32, 32, True),       # the federated round: manual over data, TP auto
    (32, 8, True),        # grouped heads
    (32, 32, False),      # GSPMD over both axes
])
def test_attention_on_a_tensor_parallel_mesh_compiles(v5e, monkeypatch, H, K,
                                                      manual_data):
    """The attention sublayer's forward and backward at 2,048 tokens on a
    (data 2, model 2) mesh, q/k/v projections split over 'model' as the
    sharding rules lay them out: the flash kernels run per shard, and no
    all-gather brings q/k/v (or anything else) together."""
    from repro.kernels import dispatch
    from repro.models import layers
    from repro.models.config import ArchConfig
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    mesh = Mesh(np.asarray(v5e.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    B, S, dh = 2, 2048, 128
    cfg = ArchConfig(name="tp", family="dense", n_layers=1, d_model=D,
                     n_heads=H, n_kv_heads=K, d_head=dh, d_ff=256,
                     vocab_size=128, dtype="bfloat16")

    def arg(shape, spec):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                    sharding=NamedSharding(mesh, spec))
    p = {f"{n}_proj": {"kernel": arg((D, heads * dh), P(None, "model"))}
         for n, heads in (("q", H), ("k", K), ("v", K))}
    p["o_proj"] = {"kernel": arg((H * dh, D), P("model", None))}
    x = arg((B, S, D), P("data"))

    def grad_x(p, x):
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), x.shape[:2])
        return jax.grad(lambda x: layers.attention(p, x, pos, cfg)[0].astype(
            jnp.float32).sum())(x)

    step = grad_x if not manual_data else jax.shard_map(
        grad_x, mesh=mesh, in_specs=(P(), P("data")), out_specs=P("data"),
        axis_names={"data"}, check_vma=False)
    obs.enable()
    try:
        with jax.set_mesh(mesh) if not manual_data else contextlib.nullcontext():
            text = jax.jit(step).lower(p, x).compile().as_text()
        counts = {(c["labels"]["path"], c["labels"]["why"]): c["value"]
                  for c in obs.active().metrics.snapshot()["counters"]
                  ["attn_path"]}
    finally:
        obs.disable()
    assert counts == {("flash", "none"): 1}
    assert text.count(CUSTOM_CALL) == 3
    assert not re.search(r"= \S+ all-gather(-start)?\(", text)
