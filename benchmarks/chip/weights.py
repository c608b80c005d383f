"""Weights and adapter state made from the seed, on the device.

The trees follow the program's parameter layout (a stacked ``blocks/sub0``
superblock of every layer, kernels in (d_in, d_out) layout), but are made
here, so the reference reads weights that the program never produced.
Each maker runs as one jitted call (``on_device``): nothing is made leaf
by leaf or on the host.
"""
from __future__ import annotations

import math
import jax
import jax.numpy as jnp

from dims import Dims

KERNEL_SCALE = 0.02
NORM_JITTER = 0.05
B_MAG_SCALE = 0.02        # mid-training magnitudes of B
DA_DIR_SCALE = 1e-3       # the global stage's direction delta so far
DB_MAG_SCALE = 0.02       # a client's personal magnitude delta so far


def _norm(key, shape):
    return 1.0 + NORM_JITTER * jax.random.normal(key, shape, jnp.float32)


def _kernel(key, shape, scale):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(
        jnp.bfloat16)


def make_base(key, d: Dims):
    """The frozen backbone: bf16 kernels, float32 norms and router."""
    L, D, H, K, dh = d.layers, d.d_model, d.heads, d.kv_heads, d.head_dim
    out = KERNEL_SCALE / math.sqrt(2 * L)
    ks = iter(jax.random.split(key, 16))
    attn = {"q_proj": {"kernel": _kernel(next(ks), (L, D, H * dh),
                                         KERNEL_SCALE)},
            "k_proj": {"kernel": _kernel(next(ks), (L, D, K * dh),
                                         KERNEL_SCALE)},
            "v_proj": {"kernel": _kernel(next(ks), (L, D, K * dh),
                                         KERNEL_SCALE)},
            "o_proj": {"kernel": _kernel(next(ks), (L, H * dh, D), out)}}
    if d.qk_norm:
        attn["q_norm"] = _norm(next(ks), (L, dh))
        attn["k_norm"] = _norm(next(ks), (L, dh))
    block = {"input_norm": _norm(next(ks), (L, D)), "attn": attn,
             "ffn_norm": _norm(next(ks), (L, D))}
    if d.experts:
        E, F = d.experts, d.d_ff
        block["moe"] = {
            "router": {"kernel": KERNEL_SCALE * jax.random.normal(
                next(ks), (L, D, E), jnp.float32)},
            "experts": {"gate": _kernel(next(ks), (L, E, D, F), KERNEL_SCALE),
                        "up": _kernel(next(ks), (L, E, D, F), KERNEL_SCALE),
                        "down": _kernel(next(ks), (L, E, F, D), out)}}
    else:
        F = d.d_ff
        block["mlp"] = {
            "gate_proj": {"kernel": _kernel(next(ks), (L, D, F),
                                            KERNEL_SCALE)},
            "up_proj": {"kernel": _kernel(next(ks), (L, D, F), KERNEL_SCALE)},
            "down_proj": {"kernel": _kernel(next(ks), (L, F, D), out)}}
    return {"embed": {"embedding": _kernel(next(ks), (d.vocab, D),
                                           KERNEL_SCALE)},
            "final_norm": _norm(next(ks), (D,)),
            "blocks": {"sub0": block},
            "lm_head": {"kernel": _kernel(next(ks), (D, d.vocab),
                                          KERNEL_SCALE)}}


def _unit_rows(x):
    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


def make_adapters(key, d: Dims, clients: int):
    """Decomposed LoRA on every target, as a mid-training client state:
    A = A_mag ⊙ (A_dir + dA_dir), B = (B_mag + dB_mag) ⊙ B_dir, every
    leaf float32 with a leading client axis.  The shared factors are the
    same on every client (a round ends in a rebroadcast); dB_mag, the
    personal delta, differs per client."""
    L, D, r = d.layers, d.d_model, d.rank
    out: dict = {}
    for i, (name, d_out) in enumerate(d.targets()):
        k = jax.random.split(jax.random.fold_in(key, i), 5)
        A = jax.random.normal(k[0], (L, D, r), jnp.float32) / math.sqrt(r)
        leaf = {"A_mag": jnp.linalg.norm(A, axis=-1),
                "A_dir": _unit_rows(A),
                "B_dir": _unit_rows(jax.random.normal(k[1], (L, r, d_out),
                                                      jnp.float32)),
                "B_mag": B_MAG_SCALE * jax.random.normal(k[2], (L, r)),
                "dA_dir": DA_DIR_SCALE * jax.random.normal(k[3], (L, D, r))}
        leaf = {n: jnp.broadcast_to(v, (clients,) + v.shape)
                for n, v in leaf.items()}
        leaf["dB_mag"] = DB_MAG_SCALE * jax.random.normal(
            k[4], (clients, L, r), jnp.float32)
        out[name] = leaf
    return {"blocks": {"sub0": {"attn": out}}}


def make_tenant_deltas(key, d: Dims, tenants: int):
    """Per-tenant ΔB_M for every target: {target: (tenants, L, r)}."""
    return {name: DB_MAG_SCALE * jax.random.normal(
        jax.random.fold_in(key, i), (tenants, d.layers, d.rank), jnp.float32)
        for i, (name, _) in enumerate(d.targets())}


def on_device(maker, *args, sharding=None):
    """Run ``maker(key, *static)`` as one jitted call, its result laid
    out by ``sharding`` (a pytree prefix) or on the default device."""
    key, static = args[0], args[1:]
    fn = jax.jit(lambda k: maker(k, *static), out_shardings=sharding)
    return fn(key)


def seed_key(seed: int):
    """A PRNG key from a seed of any size, 32 bits or more."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)
