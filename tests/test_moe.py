"""MoE routing/dispatch correctness (single device)."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers as L
from repro.models.config import ArchConfig
from repro.models.layers import moe_ffn_dense_ref, moe_ffn_local

CFG = ArchConfig(name="m", family="moe", n_layers=2, d_model=32, n_heads=2,
                 n_kv_heads=1, d_ff=64, vocab_size=64, dtype="float32",
                 n_experts=4, top_k=2, capacity_factor=8.0)  # no drops


def _params(rng, cfg, fsplit=1):
    E = cfg.n_experts * fsplit
    F = cfg.d_ff // fsplit
    k = jax.random.split(rng, 4)
    return {
        "router": {"kernel": jax.random.normal(k[0], (cfg.d_model, cfg.n_experts)) * 0.2},
        "experts": {
            "gate": jax.random.normal(k[1], (E, cfg.d_model, F)) * 0.2,
            "up": jax.random.normal(k[2], (E, cfg.d_model, F)) * 0.2,
            "down": jax.random.normal(k[3], (E, F, cfg.d_model)) * 0.2,
        },
    }


def test_grouped_matches_dense_ref_when_no_drops():
    p = _params(jax.random.PRNGKey(0), CFG)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, CFG.d_model))
    y_g, aux_g = moe_ffn_local(p, x, CFG)
    y_d, aux_d = moe_ffn_dense_ref(p, x, CFG)
    np.testing.assert_allclose(np.asarray(y_g), np.asarray(y_d),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(float(aux_g), float(aux_d), rtol=1e-5)


def test_capacity_drops_only_reduce_output():
    tight = dataclasses.replace(CFG, capacity_factor=0.25)
    p = _params(jax.random.PRNGKey(0), tight)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, CFG.d_model))
    y, _ = moe_ffn_local(p, x, tight)
    assert bool(jnp.all(jnp.isfinite(y)))


def test_fsplit_slot_layout_matches_logical_experts():
    """ep_fsplit=2: two half-d_ff slots per expert must reproduce the
    fsplit=1 output exactly (same logical weights, re-laid-out)."""
    cfg1 = CFG
    cfg2 = dataclasses.replace(CFG, ep_fsplit=2)
    p1 = _params(jax.random.PRNGKey(0), cfg1)
    p2 = relay(p1, 2)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg1.d_model))
    y1, _ = moe_ffn_local(p1, x, cfg1)
    y2, _ = moe_ffn_local(p2, x, cfg2)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-5)


def test_router_aux_penalizes_imbalance():
    from repro.models.layers import moe_router
    # positive inputs so the +5 column is the max logit for EVERY token
    xt = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (64, CFG.d_model)))
    # balanced-ish random router vs collapsed router
    p_rand = {"router": {"kernel": jax.random.normal(jax.random.PRNGKey(3), (CFG.d_model, 4)) * 0.01}}
    collapse = jnp.zeros((CFG.d_model, 4)).at[:, 0].set(5.0)
    p_coll = {"router": {"kernel": collapse}}
    _, _, aux_r = moe_router(p_rand, xt, CFG, 1)
    _, _, aux_c = moe_router(p_coll, xt, CFG, 1)
    assert float(aux_c) > float(aux_r)


# ---------------------------------------------------------------------------
# gather-only row movement against the scatter form it replaced
# ---------------------------------------------------------------------------

def scatter_moe(p, x, cfg):
    """Oracle: the expert layer with rows moved by scatter-adds into a
    buffer with one dump row for dropped pairs, and combined by a
    weighted scatter-add back to the tokens (the capacity semantics of
    ``moe_ffn_local``: top-k, token-order drops, fsplit slots)."""
    B, S, D = x.shape
    T = B * S
    xt = x.reshape(T, D)
    fsplit = cfg.ep_fsplit
    E_slots = cfg.n_experts * fsplit
    C = L.moe_capacity(cfg, T)
    top_i, top_w, aux = L.moe_router(p, xt, cfg, fsplit)
    k = top_i.shape[1]
    if fsplit > 1:
        top_i = (top_i[..., None] * fsplit
                 + jnp.arange(fsplit)[None, None, :]).reshape(T, k * fsplit)
        top_w = jnp.repeat(top_w, fsplit, axis=-1)
        k = k * fsplit
    flat_e = top_i.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], jnp.repeat(jnp.arange(T), k)[order], \
        top_w.reshape(-1)[order]
    pos = jnp.arange(T * k) - jnp.searchsorted(se, se, side="left")
    keep = pos < C
    dest = jnp.where(keep, se * C + pos, E_slots * C)        # dump row
    xg = jnp.zeros((E_slots * C + 1, D), xt.dtype).at[dest].add(xt[st])[:-1]
    ex = p["experts"]
    yg = L._expert_mlp(xg.reshape(E_slots, C, D), ex["gate"], ex["up"],
                       ex["down"]).reshape(E_slots * C, D)
    yg1 = jnp.concatenate([yg, jnp.zeros((1, D), yg.dtype)], axis=0)
    vals = yg1[dest] * (sw * keep)[:, None].astype(yg.dtype)
    y = jnp.zeros((T, D), yg.dtype).at[st].add(vals)
    return y.reshape(B, S, D), aux


def relay(p1, fsplit):
    """Plain-layout expert weights in the fsplit slot layout: gate/up
    (E, D, F) → (E, fsplit, D, F/fsplit) → (E·fsplit, D, F/fsplit)."""
    if fsplit == 1:
        return p1
    ex = p1["experts"]
    E, D, F = ex["gate"].shape
    up = lambda w: w.reshape(E, D, fsplit, F // fsplit).transpose(
        0, 2, 1, 3).reshape(E * fsplit, D, F // fsplit)
    return {"router": p1["router"],
            "experts": {"gate": up(ex["gate"]), "up": up(ex["up"]),
                        "down": ex["down"].reshape(E * fsplit, F // fsplit, D)}}


def skewed_case(capacity_factor, fsplit, seed=0):
    """A config, slot-layout params and positive inputs whose router
    prefers expert 0 for every token, so that it overflows first."""
    cfg = dataclasses.replace(CFG, capacity_factor=capacity_factor,
                              ep_fsplit=fsplit)
    p1 = _params(jax.random.PRNGKey(seed), CFG)
    p1["router"]["kernel"] = p1["router"]["kernel"].at[:, 0].add(0.5)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(seed + 1),
                                  (2, 16, CFG.d_model)))
    return cfg, p1, relay(p1, fsplit), x


def value_and_grads(fn, p, x):
    """(y, aux) and the gradients to (params, x) of a loss that weighs
    every output element differently and adds the aux term, under the
    layer's remat."""
    g = jnp.cos(jnp.arange(x.size, dtype=jnp.float32).reshape(x.shape))

    def loss(p, x):
        y, aux = jax.checkpoint(fn)(p, x)
        return jnp.sum(y * g) + aux, (y, aux)

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True))(p, x)
    return out, grads


def assert_trees_close(a, b, rtol, atol, scaled=0.0):
    """Leaf by leaf; ``scaled`` adds that share of the leaf's largest
    entry to ``atol``."""
    for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        v = np.asarray(v)
        np.testing.assert_allclose(np.asarray(u), v, rtol=rtol,
                                   atol=atol + scaled * np.abs(v).max())


ROUNDOFF = dict(rtol=1e-5, atol=1e-6)
DENSE = dict(rtol=1e-4, atol=1e-5)     # sums over every expert, in another order
# the 'model' axis sums the experts' d_ff halves apart, and the router's
# gradient cancels in the top-k softmax: round-off of about 1e-4 of the
# leaf's largest entry
SHARDED = dict(rtol=1e-5, atol=1e-6, scaled=2e-4)


@pytest.mark.parametrize("fsplit", [1, 2])
@pytest.mark.parametrize("drops", [True, False])
def test_gather_path_matches_scatter_oracle(fsplit, drops):
    """Output, aux term and the gradients to x, the router kernel and the
    three expert weights equal the scatter form's to float32 round-off;
    with no drops, also the dense oracle's."""
    cfg, p1, p, x = skewed_case(0.5 if drops else 8.0, fsplit)
    T = x.shape[0] * x.shape[1]
    top_i, _, _ = L.moe_router(p, x.reshape(T, -1), cfg, 1)
    loads = np.bincount(np.asarray(top_i).ravel(), minlength=cfg.n_experts)
    assert (loads.max() > L.moe_capacity(cfg, T)) == drops, loads

    got = value_and_grads(lambda p, x: moe_ffn_local(p, x, cfg), p, x)
    want = value_and_grads(lambda p, x: scatter_moe(p, x, cfg), p, x)
    assert_trees_close(got, want, **ROUNDOFF)
    if not drops:
        (y, aux), (gp, gx) = value_and_grads(
            lambda p, x: moe_ffn_dense_ref(p, x, cfg), p1, x)
        assert_trees_close(got[0], (y, aux), **DENSE)
        assert_trees_close(got[1], (relay(gp, fsplit), gx), **DENSE)


SCATTER = re.compile(r"= (\w+)\[([\d,]*)\]\S* scatter\(")


@pytest.mark.parametrize("body", ["local", "ep"])
@pytest.mark.parametrize("fsplit", [1, 2])
def test_no_row_scatter_in_expert_layer_gradient(fsplit, body):
    """The compiled gradient of the expert layer under remat scatters no
    D-wide rows: dispatch, combine and both backward passes gather."""
    cfg, _, p, x = skewed_case(0.5, fsplit)
    x = x[:, :12]                              # T = 24: no dim equals D
    D = cfg.d_model
    if body == "local":
        fn = lambda p, x: moe_ffn_local(p, x, cfg)[0]
    else:
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        fn = lambda p, x: L.moe_ffn_ep(p, x, cfg, mesh)[0]
    loss = lambda p, x: jnp.sum(jax.checkpoint(fn)(p, x))
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        p, x).compile().as_text()
    shapes = [m.group(2) for m in SCATTER.finditer(text)]
    assert shapes, "no scatter at all: the pattern no longer matches"
    assert not [s for s in shapes if s.split(",")[-1] == str(D)], shapes
