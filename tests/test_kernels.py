"""Per-kernel shape/dtype sweeps vs pure-jnp oracles (interpret mode)."""
import functools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (attention_ref, flash_attention,
                           flash_attention_causal, fused_dora,
                           fused_dora_ref, ssd_naive, ssd_ref, ssd_scan)

RNG = np.random.default_rng(7)


def _tol(dt):
    return dict(rtol=2e-2, atol=2e-2) if dt == jnp.bfloat16 else \
        dict(rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("M,K,N,r", [(128, 256, 128, 8), (256, 512, 256, 16),
                                     (64, 128, 384, 4), (128, 128, 128, 32)])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_fused_dora_sweep(M, K, N, r, dt):
    x = jnp.asarray(RNG.normal(size=(M, K)), dt)
    w0 = jnp.asarray(RNG.normal(size=(K, N)) * 0.05, dt)
    ad = jnp.asarray(RNG.normal(size=(K, r)) * 0.3, jnp.float32)
    am = jnp.asarray(RNG.uniform(0.5, 1.5, size=(K,)), jnp.float32)
    bd = jnp.asarray(RNG.normal(size=(r, N)) * 0.3, jnp.float32)
    bm = jnp.asarray(RNG.uniform(0.1, 0.5, size=(r,)), jnp.float32)
    dad = jnp.asarray(RNG.normal(size=(K, r)) * 0.05, jnp.float32)
    dbm = jnp.asarray(RNG.normal(size=(r,)) * 0.05, jnp.float32)
    y = fused_dora(x, w0, ad, am, bd, bm, dad, dbm, scale=2.0, interpret=True)
    yr = fused_dora_ref(x, w0, ad, am, bd, bm, dad, dbm, 2.0)
    scale = float(jnp.max(jnp.abs(yr))) + 1e-6
    err = float(jnp.max(jnp.abs(y.astype(jnp.float32) - yr.astype(jnp.float32))))
    tol = 2e-2 if dt == jnp.bfloat16 else 1e-4
    assert err / scale < tol, (err, scale)


@pytest.fixture
def fused_kernel_body(monkeypatch):
    """Route layers.linear's fused path through the Pallas kernel body
    (interpreter): off-TPU the wrapper's default is the jnp oracle."""
    import repro.kernels
    monkeypatch.setattr(repro.kernels, "fused_dora",
                        functools.partial(fused_dora, interpret=True))


def test_linear_fused_flag_matches_jnp_path(fused_kernel_body):
    """layers.linear(fused=True) (ArchConfig.use_fused_dora) must agree
    with the plain jnp base+lora_delta path on decomposed adapters."""
    from repro.models.layers import linear
    p = {"kernel": jnp.asarray(RNG.normal(size=(64, 128)) * 0.05, jnp.float32),
         "A_dir": jnp.asarray(RNG.normal(size=(64, 8)) * 0.3, jnp.float32),
         "A_mag": jnp.asarray(RNG.uniform(0.5, 1.5, size=(64,)), jnp.float32),
         "B_dir": jnp.asarray(RNG.normal(size=(8, 128)) * 0.3, jnp.float32),
         "B_mag": jnp.asarray(RNG.uniform(0.1, 0.5, size=(8,)), jnp.float32),
         "dA_dir": jnp.asarray(RNG.normal(size=(64, 8)) * 0.05, jnp.float32),
         "dB_mag": jnp.asarray(RNG.normal(size=(8,)) * 0.05, jnp.float32)}
    x = jnp.asarray(RNG.normal(size=(2, 16, 64)), jnp.float32)
    y_fused = linear(p, x, lora_scale=2.0, fused=True)
    y_ref = linear(p, x, lora_scale=2.0, fused=False)
    assert y_fused.shape == y_ref.shape == (2, 16, 128)
    np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)
    # flag is inert for raw-LoRA / plain params
    p_raw = {"kernel": p["kernel"],
             "lora_A": jnp.asarray(RNG.normal(size=(64, 4)), jnp.float32),
             "lora_B": jnp.asarray(RNG.normal(size=(4, 128)), jnp.float32)}
    np.testing.assert_allclose(
        np.asarray(linear(p_raw, x, lora_scale=2.0, fused=True)),
        np.asarray(linear(p_raw, x, lora_scale=2.0, fused=False)))


def test_model_forward_with_use_fused_dora_flag(fused_kernel_body):
    """End-to-end: ArchConfig.use_fused_dora routes the decomposed-LoRA
    projections through the fused kernel with matching loss."""
    import dataclasses
    import jax
    from repro.core import peft
    from repro.models import model as M
    from repro.models.config import ArchConfig
    from repro.utils import pytree as pt
    cfg = ArchConfig(name="t", family="dense", n_layers=1, d_model=32,
                     n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=64,
                     dtype="float32", lora_rank=4, lora_dropout=0.0)
    base = M.init_params(jax.random.PRNGKey(0), cfg)
    ad = peft.add_lora(base, cfg, jax.random.PRNGKey(1), decomposed=True)
    # give B nonzero magnitude so the adapter path actually contributes
    ad = pt.tree_map_with_path(
        lambda p, x: x + 0.3 if p.endswith("B_mag") else x, ad)
    params = pt.merge_trees(base, ad)
    batch = {"tokens": jnp.asarray(RNG.integers(5, 64, size=(2, 16)),
                                   jnp.int32),
             "loss_mask": jnp.ones((2, 16), jnp.float32)}
    loss_ref, _ = M.loss_and_metrics(params, batch, cfg)
    cfg_fused = dataclasses.replace(cfg, use_fused_dora=True)
    loss_fused, _ = M.loss_and_metrics(params, batch, cfg_fused)
    np.testing.assert_allclose(float(loss_fused), float(loss_ref),
                               rtol=1e-4, atol=1e-4)


def test_fused_dora_batched_input():
    x = jnp.asarray(RNG.normal(size=(2, 64, 128)), jnp.float32)
    w0 = jnp.asarray(RNG.normal(size=(128, 128)) * 0.05, jnp.float32)
    ad = jnp.asarray(RNG.normal(size=(128, 8)), jnp.float32)
    am = jnp.ones((128,), jnp.float32)
    bd = jnp.asarray(RNG.normal(size=(8, 128)), jnp.float32)
    bm = jnp.ones((8,), jnp.float32)
    y = fused_dora(x, w0, ad, am, bd, bm, interpret=True)
    assert y.shape == (2, 64, 128)


@pytest.mark.parametrize("case", [
    dict(B=2, Sq=256, Sk=256, H=4, K=2, dh=64, causal=True, window=None),
    dict(B=1, Sq=128, Sk=128, H=4, K=4, dh=32, causal=True, window=48),
    dict(B=2, Sq=256, Sk=256, H=8, K=1, dh=64, causal=False, window=None),
    dict(B=1, Sq=512, Sk=512, H=2, K=2, dh=128, causal=True, window=128),
    dict(B=1, Sq=128, Sk=256, H=2, K=2, dh=64, causal=True, window=None),
])
@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(case, dt):
    c = case
    q = jnp.asarray(RNG.normal(size=(c["B"], c["Sq"], c["H"], c["dh"])), dt)
    k = jnp.asarray(RNG.normal(size=(c["B"], c["Sk"], c["K"], c["dh"])), dt)
    v = jnp.asarray(RNG.normal(size=(c["B"], c["Sk"], c["K"], c["dh"])), dt)
    y = flash_attention(q, k, v, causal=c["causal"], window=c["window"],
                        interpret=True)
    yr = attention_ref(q, k, v, causal=c["causal"], window=c["window"])
    err = float(jnp.max(jnp.abs(y.astype(jnp.float32) - yr.astype(jnp.float32))))
    assert err < (2e-2 if dt == jnp.bfloat16 else 2e-5)


@pytest.mark.parametrize("b,S,H,G,P,N,Q", [
    (2, 64, 4, 2, 16, 8, 16),
    (1, 128, 2, 1, 32, 16, 32),
    (2, 32, 4, 4, 8, 8, 8),
    (1, 64, 2, 2, 16, 16, 64),   # single chunk
])
def test_ssd_scan_sweep(b, S, H, G, P, N, Q):
    x = jnp.asarray(RNG.normal(size=(b, S, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, size=(b, S, H)), jnp.float32)
    A_log = jnp.asarray(np.log(RNG.uniform(0.5, 4.0, size=(H,))), jnp.float32)
    B = jnp.asarray(RNG.normal(size=(b, S, G, N)), jnp.float32)
    C = jnp.asarray(RNG.normal(size=(b, S, G, N)), jnp.float32)
    y_k, st_k = ssd_scan(x, dt, A_log, B, C, chunk=Q, interpret=True)
    y_n, st_n = ssd_naive(x, dt, A_log, B, C)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_n),
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st_k), np.asarray(st_n),
                               rtol=1e-3, atol=1e-4)


def test_ssd_model_ref_matches_naive():
    b, S, H, G, P, N = 1, 48, 2, 1, 8, 4
    x = jnp.asarray(RNG.normal(size=(b, S, H, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.05, 0.3, size=(b, S, H)), jnp.float32)
    A_log = jnp.zeros((H,), jnp.float32)
    B = jnp.asarray(RNG.normal(size=(b, S, G, N)), jnp.float32)
    C = jnp.asarray(RNG.normal(size=(b, S, G, N)), jnp.float32)
    y_r, st_r = ssd_ref(x, dt, A_log, B, C, 16)
    y_n, st_n = ssd_naive(x, dt, A_log, B, C)
    np.testing.assert_allclose(np.asarray(y_r), np.asarray(y_n),
                               rtol=1e-3, atol=1e-4)


def _explicit_compiled_calls():
    from repro.kernels import bgmv, bgmv_mag, quant_matmul, quantize_int8
    x3 = jnp.ones((2, 4, 16), jnp.float32)
    pool_a, pool_b = jnp.ones((3, 16, 4)), jnp.ones((3, 4, 16))
    idx = jnp.zeros((2,), jnp.int32)
    q, s = quantize_int8(jnp.ones((16, 8), jnp.float32))
    qkv = jnp.ones((1, 128, 2, 64), jnp.float32)
    ssd_in = (jnp.ones((1, 32, 2, 8)), jnp.full((1, 32, 2), 0.1),
              jnp.zeros((2,)), jnp.ones((1, 32, 1, 4)), jnp.ones((1, 32, 1, 4)))
    w0 = jnp.ones((128, 128), jnp.float32)
    return {
        "bgmv": lambda: bgmv(x3, pool_a, pool_b, idx, impl="pallas"),
        "bgmv_mag": lambda: bgmv_mag(
            x3, jnp.ones((16, 4)), jnp.ones((16,)), jnp.ones((4,)),
            jnp.ones((3, 4)), jnp.ones((4, 16)), idx, impl="pallas"),
        "quant_matmul": lambda: quant_matmul(x3, q, s, impl="pallas"),
        "flash_attention": lambda: flash_attention(qkv, qkv, qkv,
                                                   interpret=False),
        "flash_attention_causal": lambda: flash_attention_causal(
            qkv, qkv, qkv, impl="pallas"),
        "ssd_scan": lambda: ssd_scan(*ssd_in, chunk=16, interpret=False),
        "fused_dora": lambda: fused_dora(
            jnp.ones((128, 128)), w0, jnp.ones((128, 4)), jnp.ones((128,)),
            jnp.ones((4, 128)), jnp.ones((4,)), interpret=False),
    }


@pytest.mark.parametrize("op", ["bgmv", "bgmv_mag", "quant_matmul",
                                "flash_attention", "flash_attention_causal",
                                "ssd_scan", "fused_dora"])
def test_explicit_compiled_kernel_refuses_non_tpu(op, monkeypatch):
    """Asking for the compiled kernel off-TPU raises — it never quietly
    runs the Pallas interpreter in its place.  The backend probe is
    pinned to "not a TPU" so the check holds on any host."""
    from repro.kernels import dispatch
    monkeypatch.setattr(dispatch, "on_tpu", lambda: False)
    with pytest.raises(RuntimeError, match="needs a TPU backend"):
        _explicit_compiled_calls()[op]()
