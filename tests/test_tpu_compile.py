"""Ahead-of-time compiles of the serving kernels for a TPU v5e chip, at
DeepSeek-7B widths (d_model 4096, d_ff 11008, LoRA r=8).

No chip is needed: the TPU compiler is installed and compiles for a
described topology.  This catches what interpret mode cannot — blocks
that break Mosaic's (8, 128) tiling rule, kernels that overflow VMEM —
before any chip time is spent.  The topology is described inside a
module fixture, never at import, so every test worker collects the same
tests and only the worker running this file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.batched_lora.bgmv import bgmv_mag_matmul, bgmv_matmul
from repro.kernels.quant_matmul.quant_matmul import quant_matmul_kernel

D, D_FF, R, SLOTS, ROWS = 4096, 11008, 8, 17, 8


@pytest.fixture(scope="module")
def one_chip():
    """One v5e chip, described; the persistent compile cache is off
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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


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
