"""Multi-tenant personalized serving demo — one mixed batch.

    PYTHONPATH=src python examples/serve_personalized.py

One frozen backbone + per-tenant DoRA-decomposed adapters where only the
ΔB_M magnitude vectors differ per tenant (the paper's local-optimizer
output — a few hundred *bytes* per tenant).  The AdapterStore pools the
magnitudes behind integer slots; the ServeEngine then serves N tenants
in ONE batch, the BGMV path gathering each row's adapter per token —
the backbone is never merged with anybody's adapter.  Tenants produce
different continuations from identical prompts while sharing every
backbone byte, and the mixed batch beats the old merge-per-tenant loop
by an order of magnitude in tokens/s.
"""
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import peft  # noqa: E402
from repro.launch.serve import greedy_generate, merge_adapters  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models.config import ArchConfig  # noqa: E402
from repro.serve import AdapterStore, ServeEngine  # noqa: E402
from repro.utils.pytree import (filter_tree, tree_bytes,  # noqa: E402
                                tree_map_with_path)

CFG = ArchConfig(name="serve-demo", family="dense", n_layers=4, d_model=256,
                 n_heads=4, n_kv_heads=2, d_ff=512, vocab_size=1024,
                 dtype="float32", lora_rank=8, lora_dropout=0.0)

N_TENANTS = 6
PROMPT = 24
N_NEW = 8


def _tenant_variant(shared, tenant: int):
    """Per-tenant personalization = only the dB_mag leaves differ."""
    return tree_map_with_path(
        lambda p, x: x + 0.3 * (tenant + 1) * jnp.sign(
            jnp.sin(jnp.arange(x.size, dtype=jnp.float32) + tenant)
        ).reshape(x.shape) if p.endswith("dB_mag") else x, shared)


def main():
    params = M.init_params(jax.random.PRNGKey(0), CFG)
    shared = peft.add_lora(params, CFG, jax.random.PRNGKey(1),
                           decomposed=True)
    shared = tree_map_with_path(
        lambda p, x: x + 0.2 if p.endswith("B_mag") else x, shared)

    rng = np.random.default_rng(0)
    prompt = np.asarray(rng.integers(5, CFG.vocab_size, size=(PROMPT,)),
                        np.int32)

    store = AdapterStore(params, CFG, n_slots=N_TENANTS, kind="dora_mag",
                         shared=shared)
    variants = {}
    for t in range(N_TENANTS):
        variants[t] = _tenant_variant(shared, t)
        store.register(f"tenant{t}", filter_tree(
            variants[t], lambda p: p.endswith("dB_mag")))

    print(f"backbone: {tree_bytes(params)/1e6:.1f} MB shared across tenants; "
          f"ΔB_M payload {store.bytes_per_tenant()} B/tenant")

    engine = ServeEngine(params, CFG, store, max_rows=N_TENANTS,
                         max_prompt_len=PROMPT,
                         max_len=PROMPT + N_NEW + 8, decode_chunk=8)
    # every tenant gets the SAME prompt — one mixed batch, N tenants
    reqs = [(f"tenant{t}", prompt) for t in range(N_TENANTS)]
    outs = engine.generate(reqs, n_new=N_NEW)           # also compiles
    for t, out in enumerate(outs):
        print(f"tenant {t}: mixed-batch continuation: {out.tolist()}")

    # naive path: merge each tenant's adapter into the backbone, generate
    # one tenant at a time (the seed deployment story)
    def naive():
        outs = []
        for t in range(N_TENANTS):
            merged = merge_adapters(params, variants[t])
            out = greedy_generate(merged, {"tokens": jnp.asarray(prompt[None])},
                                  CFG, n_new=N_NEW)
            outs.append(np.asarray(out[0]))
        return outs

    naive_outs = naive()                                # compile + check
    for t in range(N_TENANTS):
        assert np.array_equal(outs[t], naive_outs[t]), t
    t0 = time.perf_counter()
    engine.generate(reqs, n_new=N_NEW)
    t_mixed = time.perf_counter() - t0
    t0 = time.perf_counter()
    naive()
    t_naive = time.perf_counter() - t0
    tok = N_TENANTS * N_NEW
    print(f"one mixed batch : {tok/t_mixed:8.1f} tok/s")
    print(f"merge-per-tenant: {tok/t_naive:8.1f} tok/s "
          f"(same tokens, bit-identical — {t_naive/t_mixed:.1f}x slower)")


if __name__ == "__main__":
    from repro.utils.runtime import enable_compile_cache
    enable_compile_cache()
    main()
