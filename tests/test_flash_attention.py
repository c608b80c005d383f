"""The flash attention kernels the models train through (interpret mode).

* ``flash_attention_causal`` — forward, dq and dkv kernels under one
  ``custom_vjp`` — against the float32 ``attention_ref`` and the model's
  chunked XLA path ``layers._sdpa_chunked``: output and q/k/v gradients,
  over causal, sliding-window, MHA, GQA and MQA cases.
* ``layers.attention_path``: which calls take the kernel, and why the
  others fall back.
* A smoke-size model with the kernel path forced to the interpreter: the
  loss and the adapter gradients equal the chunked path's, the
  ``attn_path`` counter records the choice, and every kernel call of the
  compiled training step carries the ``attn`` scope in its ``op_name``
  (so device time per scope keeps counting it as attention).
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.kernels import attention_ref, flash_attention_causal
from repro.kernels.flash_attention.ops import flash_block
from repro.models import layers
from repro.models.layers import _sdpa_chunked, attention_path

RNG = np.random.default_rng(11)


@pytest.mark.parametrize("B,S,H,K,dh,window,dt", [
    (2, 256, 4, 4, 128, None, jnp.float32),     # causal MHA
    (1, 512, 4, 2, 128, None, jnp.float32),     # GQA
    (1, 256, 4, 1, 128, None, jnp.bfloat16),    # MQA, bf16 operands
    (1, 512, 2, 2, 128, 200, jnp.float32),      # sliding window
    (1, 384, 4, 2, 256, 128, jnp.float32),      # window = block, dh 256
])
def test_flash_causal_matches_references(B, S, H, K, dh, window, dt):
    q, k, v = (jnp.asarray(RNG.normal(size=(B, S, n, dh)), dt)
               for n in (H, K, K))
    w = jnp.asarray(RNG.normal(size=(B, S, H, dh)), jnp.float32)
    scale = dh ** -0.5

    def vjp(fn, q, k, v, w):
        out, back = jax.vjp(fn, q, k, v)
        return (out, *back(w.astype(out.dtype)))

    fns = {"flash": lambda q, k, v: flash_attention_causal(
               (q * scale).astype(dt), k, v, window=window, block=128,
               impl="interpret"),
           "attention_ref": lambda q, k, v: attention_ref(
               q, k, v, causal=True, window=window),
           "chunked": lambda q, k, v: _sdpa_chunked(
               q, k, v, scale, window, True, q_block=128)}
    refs = jax.jit(lambda *a: {n: vjp(f, *a) for n, f in fns.items()})(
        q, k, v, w)
    got = refs.pop("flash")
    tol = 2e-2 if dt == jnp.bfloat16 else 2e-5
    for name, ref in refs.items():
        for what, a, b in zip(("out", "dq", "dk", "dv"), got, ref):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            err = np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-6)
            assert err < tol, (name, what, err)


def test_flash_causal_backward_precision_near_the_chunked_path():
    """bf16 inputs whose keys and values share a component, as trained
    models' do: the kernels' q/k/v gradients stay within 3x of the
    chunked XLA path's error against float32.  (A di taken from the
    output rounded to bf16 leaks that shared component into dq: 11x.)"""
    S, H, dh = 1024, 2, 128
    rng = np.random.default_rng(5)
    q = rng.normal(size=(1, S, H, dh))
    k = rng.normal(size=(1, S, H, dh)) + 2.0 * rng.normal(size=(1, 1, H, dh))
    v = rng.normal(size=(1, S, H, dh)) + rng.normal(size=(1, 1, H, dh))
    w = rng.normal(size=(1, S, H, dh))
    q, k, v, w = (jnp.asarray(t, jnp.bfloat16) for t in (q, k, v, w))
    scale = dh ** -0.5

    def grads(fn, q, k, v, w):
        return jax.vjp(fn, q, k, v)[1](w)

    def run(q, k, v, w):
        f32 = [t.astype(jnp.float32) for t in (q, k, v, w)]
        return (grads(lambda q, k, v: attention_ref(q, k, v, causal=True),
                      *f32),
                grads(lambda q, k, v: flash_attention_causal(
                    (q.astype(jnp.float32) * scale).astype(q.dtype), k, v,
                    block=256, impl="interpret"), q, k, v, w),
                grads(lambda q, k, v: _sdpa_chunked(
                    q, k, v, scale, None, True, q_block=256), q, k, v, w))
    exact, flash, chunked = jax.jit(run)(q, k, v, w)

    def err(g):
        return [float(jnp.linalg.norm(a.astype(jnp.float32) - b)
                      / jnp.linalg.norm(b)) for a, b in zip(g, exact)]
    for what, f, c in zip(("dq", "dk", "dv"), err(flash), err(chunked)):
        assert f < 3 * c, (what, f, c)


def test_flash_causal_refuses_a_block_that_does_not_divide_s():
    q = jnp.zeros((1, 384, 2, 128), jnp.float32)    # default block 512
    with pytest.raises(ValueError, match="does not divide"):
        flash_attention_causal(q, q, q, impl="interpret")


@pytest.mark.parametrize("S,window,block", [
    (2048, None, 1024), (4096, 4096, 1024), (4096, 1024, 1024),
    (2560, None, 512),                  # 1024 does not divide S
    (4096, 512, 512),                   # a window narrower than 1024
])
def test_flash_block(S, window, block):
    assert flash_block(S, window) == block


# ---------------------------------------------------------------------------
# which calls take the kernel

@pytest.mark.parametrize("call,expect", [
    (dict(S=2048, dh=128, H=32, K=32), ("flash", "none")),       # deepseek
    (dict(S=4096, dh=128, H=64, K=8), ("flash", "none")),        # qwen3 GQA
    (dict(S=2048, dh=256, H=4, K=1), ("flash", "none")),         # gemma-3
    (dict(S=2560, dh=128, H=32, K=32), ("flash", "none")),
    (dict(S=2048, dh=128, H=32, K=32, impl="interpret"), ("flash", "none")),
    (dict(S=1024, dh=128, H=32, K=32), ("dense", "short")),
    (dict(S=2304, dh=128, H=32, K=32), ("dense", "seq_len % 512")),
    (dict(S=2048, dh=128, H=32, K=32, cross=True),
     ("chunked", "cross-attention")),
    (dict(S=2048, dh=64, H=16, K=16, causal=False),              # seamless
     ("chunked", "non-causal")),                                 # encoder
    (dict(S=2048, dh=64, H=16, K=16), ("chunked", "head_dim % 128")),
    (dict(S=2048, dh=128, H=6, K=4), ("chunked", "heads % kv_heads")),
    (dict(S=2048, dh=128, H=32, K=32, impl="einsum"), ("chunked", "no TPU")),
    (dict(S=2048, dh=128, H=32, K=8, B=2,                        # per shard
          mesh_axes={"data": 2, "model": 2}), ("flash", "none")),
    (dict(S=2048, dh=128, H=32, K=32, mesh_axes={"model": 1}),
     ("flash", "none")),
    (dict(S=2048, dh=128, H=8, K=1, mesh_axes={"model": 2}),
     ("chunked", "kv_heads % model")),
    (dict(S=2048, dh=128, H=32, K=32, B=1, mesh_axes={"data": 2}),
     ("chunked", "batch % data")),
])
def test_attention_path(call, expect):
    kw = dict(causal=True, cross=False, impl="pallas") | call
    assert attention_path(**kw) == expect


# ---------------------------------------------------------------------------
# the model's training step through the kernel

CFG = dict(name="flash-t", family="dense", n_layers=2, d_model=256,
           n_heads=2, n_kv_heads=1, d_head=128, d_ff=256, vocab_size=128,
           dtype="bfloat16", lora_rank=4, lora_dropout=0.0,
           local_global=1, sliding_window=640)      # a local + a global layer
SEQ = 2048
KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")
SCOPES = ("attn", "ffn", "lora", "ce", "optimizer", "aggregate")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def _train_step(impl):
    """(loss, adapter grads, {path, why: count}, compiled HLO text) of a
    training step whose kernel path resolves to ``impl``; the kernel's
    step is rematted, as the pipeline's are."""
    from repro.core import peft
    from repro.models import model as M
    from repro.models.config import ArchConfig
    from repro.utils import pytree as pt
    cfg = ArchConfig(**CFG)
    base = jax.jit(M.init_params, static_argnums=1)(jax.random.PRNGKey(0),
                                                     cfg)
    ad = jax.jit(lambda b, r: peft.add_lora(b, cfg, r, decomposed=True))(
        base, jax.random.PRNGKey(1))
    ad = pt.tree_map_with_path(
        lambda p, x: x + 0.3 if p.endswith("B_mag") else x, ad)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(5, 128, size=(1, SEQ)),
                                   jnp.int32),
             "loss_mask": jnp.ones((1, SEQ), jnp.float32)}
    step = jax.jit(jax.value_and_grad(lambda a: M.loss_and_metrics(
        pt.merge_trees(base, a), batch, cfg, remat=impl != "einsum")[0]))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(layers, "_flash_impl", lambda: impl)
        obs.enable()
        try:
            compiled = step.lower(ad).compile()
            counts = {(c["labels"]["path"], c["labels"]["why"]): c["value"]
                      for c in obs.active().metrics.snapshot()["counters"]
                      ["attn_path"]}
        finally:
            obs.disable()
    loss, grads = compiled(ad)
    return float(loss), grads, counts, compiled.as_text()


@pytest.fixture(scope="module")
def steps():
    return {impl: _train_step(impl) for impl in ("interpret", "einsum")}


def test_model_loss_and_grads_match_chunked(steps):
    loss_f, g_f, _, _ = steps["interpret"]
    loss_c, g_c, _, _ = steps["einsum"]
    assert abs(loss_f - loss_c) < 1e-4 * abs(loss_c)
    a = np.concatenate([np.ravel(np.asarray(x, np.float32))
                        for x in jax.tree.leaves(g_f)])
    b = np.concatenate([np.ravel(np.asarray(x, np.float32))
                        for x in jax.tree.leaves(g_c)])
    assert np.linalg.norm(a - b) < 3e-2 * np.linalg.norm(b)


def test_attn_path_counter_records_the_choice(steps):
    """The layer scan traces its body once: one count per sublayer."""
    assert steps["interpret"][2] == {("flash", "none"): 2}
    assert steps["einsum"][2] == {("chunked", "no TPU"): 2}
    assert not any(set(KERNELS) & set(n.split("/"))
                   for n in OP_NAME.findall(steps["einsum"][3]))


def _scope_of(op_name):
    return next((p for p in reversed(op_name.split("/")) if p in SCOPES),
                None)


@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_calls_carry_the_attn_scope(steps, kernel):
    names = [n for n in OP_NAME.findall(steps["interpret"][3])
             if kernel in n.split("/")]
    assert names and all(_scope_of(n) == "attn" for n in names), kernel
    remat = any("rematted_computation" in n.split("/") for n in names)
    assert remat == (kernel == "flash_fwd")     # recomputed in the backward
