"""Multi-tenant serving: mixed-tenant batch vs naive merge-per-tenant loop.

    PYTHONPATH=src python -m benchmarks.serve_multitenant

The paper's deployment story: one frozen backbone, per-tenant ΔB_M
magnitude vectors (a few hundred bytes each).  The seed path served this
by merging each tenant's adapter and generating one tenant at a time;
the ServeEngine runs all tenants as ONE batch, with the BGMV pooled-
adapter path keeping rows separated.  Same greedy decode, same
float32 numerics — the mixed batch amortizes every backbone matmul
across tenants, so tokens/s scales with batch size instead of being
pinned at batch-1 per tenant.

Reports tokens/s for both paths on the shared demo config
(``benchmarks.common.BENCH_CFG``) at 8 tenants, perf_micro-style
(interleaved reps, min as the noise-robust estimator).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import BENCH_CFG
from repro.core import peft
from repro.launch.serve import greedy_generate, merge_adapters
from repro.models import model as M
from repro.serve import AdapterStore, ServeEngine, TieredAdapterStore
from repro.utils import pytree as pt

N_TENANTS = 8
PROMPT = 16
N_NEW = 32

# churn bench (run_churn): a 10k-tenant registry over a 32-slot pool
CHURN_TENANTS = 10_000
CHURN_SLOTS = 32
CHURN_ROWS = 16
CHURN_T1 = 256
CHURN_REQS = 32
CHURN_NEW = 16
CHURN_ZIPF_S = 1.1


def _setting(n_tenants: int):
    cfg = BENCH_CFG
    base = M.init_params(jax.random.PRNGKey(0), cfg)
    shared = peft.add_lora(base, cfg, jax.random.PRNGKey(1), decomposed=True)
    shared = pt.tree_map_with_path(
        lambda p, x: x + 0.25 if p.endswith("B_mag") else x, shared)
    tenants = {}
    for t in range(n_tenants):
        tenants[f"tenant{t}"] = pt.tree_map_with_path(
            lambda p, x: x + 0.1 * (t + 1) * jnp.sign(jnp.sin(
                jnp.arange(x.size, dtype=jnp.float32) + t)).reshape(x.shape)
            if p.endswith("dB_mag") else x, shared)
    rng = np.random.default_rng(0)
    prompts = np.asarray(rng.integers(5, cfg.vocab_size,
                                      size=(n_tenants, PROMPT)), np.int32)
    return cfg, base, shared, tenants, prompts


def _naive_loop(base, cfg, tenants, prompts):
    outs = []
    for t in range(len(tenants)):
        merged = merge_adapters(base, tenants[f"tenant{t}"])
        out = greedy_generate(merged, {"tokens": jnp.asarray(prompts[t:t+1])},
                              cfg, n_new=N_NEW)
        outs.append(np.asarray(out[0]))
    return outs


def run(log=print, n_tenants: int = N_TENANTS, reps: int = 3):
    cfg, base, shared, tenants, prompts = _setting(n_tenants)

    store = AdapterStore(base, cfg, n_slots=n_tenants, kind="dora_mag",
                         shared=shared)
    for name, tree in tenants.items():
        store.register(name, pt.filter_tree(
            tree, lambda p: p.endswith("dB_mag")))
    engine = ServeEngine(base, cfg, store, max_rows=n_tenants,
                         max_prompt_len=PROMPT, max_len=PROMPT + N_NEW + 8,
                         decode_chunk=8)
    reqs = [(f"tenant{t}", prompts[t]) for t in range(n_tenants)]

    # warm/compile both paths, check they agree, then interleave reps
    mixed_outs = engine.generate(reqs, n_new=N_NEW)
    naive_outs = _naive_loop(base, cfg, tenants, prompts)
    for a, b in zip(mixed_outs, naive_outs):
        np.testing.assert_array_equal(a, b)

    ts_mixed, ts_naive = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        engine.generate(reqs, n_new=N_NEW)
        ts_mixed.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        _naive_loop(base, cfg, tenants, prompts)
        ts_naive.append(time.perf_counter() - t0)

    tok = n_tenants * N_NEW
    tps_mixed = tok / min(ts_mixed)
    tps_naive = tok / min(ts_naive)
    speedup = tps_mixed / tps_naive
    log(f"[bench] serve/mixed_batch      {tps_mixed:9.1f} tok/s  "
        f"({n_tenants} tenants x {N_NEW} new, one batch)")
    log(f"[bench] serve/naive_merge_loop {tps_naive:9.1f} tok/s  "
        f"(merge+generate per tenant)")
    log(f"[bench] serve speedup {speedup:.2f}x  "
        f"(ΔB_M payload {store.bytes_per_tenant()} B/tenant)")
    return [{"arch": "serve/mixed_batch", "tokens_s": tps_mixed,
             "us": min(ts_mixed) * 1e6},
            {"arch": "serve/naive_merge_loop", "tokens_s": tps_naive,
             "us": min(ts_naive) * 1e6}], speedup


def run_quant(log=print, n_tenants: int = N_TENANTS, reps: int = 3):
    """Mixed-tenant decode on the int8 backbone (f32 ΔB_M deltas on
    top) vs the f32 backbone.  Batch-1..N decode is weight-bytes-bound,
    so the analytic speedup is the f32/int8 weight-byte ratio — reported
    alongside the honest wall-clock of this CPU container (where XLA's
    dequant-fused fallback roughly ties f32 and the bytes win needs a
    bandwidth-bound accelerator).  Output drift vs the f32 backbone is
    checked against the documented int8 band (docs/quantization.md:
    ~4e-2 observed on this config, asserted < 1e-1)."""
    import dataclasses

    from repro.kernels.quant_matmul.ops import quantize_backbone

    cfg, base, shared, tenants, prompts = _setting(n_tenants)

    def build(run_cfg):
        store = AdapterStore(base, cfg, n_slots=n_tenants, kind="dora_mag",
                             shared=shared)
        for name, tree in tenants.items():
            store.register(name, pt.filter_tree(
                tree, lambda p: p.endswith("dB_mag")))
        return ServeEngine(base, run_cfg, store, max_rows=n_tenants,
                           max_prompt_len=PROMPT,
                           max_len=PROMPT + N_NEW + 8, decode_chunk=8)

    eng_f32 = build(cfg)
    eng_q8 = build(dataclasses.replace(cfg, backbone_quant="int8"))
    reqs = [(f"tenant{t}", prompts[t]) for t in range(n_tenants)]

    # documented tolerance: int8 drift stays in the ~4e-2 band on the
    # bench config, so greedy tokens agree except at near-ties
    batch = {"tokens": jnp.asarray(prompts)}
    drift = float(jnp.abs(
        M.forward(quantize_backbone(base, "int8"), batch, cfg)[0]
        - M.forward(base, batch, cfg)[0]).max())
    assert drift < 1e-1, f"int8 backbone drift {drift} out of band"

    outs_f32 = eng_f32.generate(reqs, n_new=N_NEW)     # compile + warm
    outs_q8 = eng_q8.generate(reqs, n_new=N_NEW)
    agree = np.mean([np.mean(a == b)
                     for a, b in zip(outs_f32, outs_q8)])

    ts_f32, ts_q8 = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        eng_f32.generate(reqs, n_new=N_NEW)
        ts_f32.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        eng_q8.generate(reqs, n_new=N_NEW)
        ts_q8.append(time.perf_counter() - t0)

    tok = n_tenants * N_NEW
    tps_f32, tps_q8 = tok / min(ts_f32), tok / min(ts_q8)
    bytes_ratio = pt.tree_bytes(base) / pt.tree_bytes(
        quantize_backbone(base, "int8"))
    log(f"[bench] serve/decode_f32  {tps_f32:9.1f} tok/s")
    log(f"[bench] serve/decode_int8 {tps_q8:9.1f} tok/s  "
        f"analytic_speedup={bytes_ratio:.2f}x (weight-byte ratio; "
        f"wall={tps_q8 / tps_f32:.2f}x on CPU)")
    log(f"[bench] serve int8 drift {drift:.2e} (band 1e-1, ~4e-2 typical), "
        f"token agreement {agree:.3f}")
    return [{"arch": "serve/decode_f32", "tokens_s": tps_f32,
             "us": min(ts_f32) * 1e6},
            {"arch": "serve/decode_int8", "tokens_s": tps_q8,
             "us": min(ts_q8) * 1e6, "bytes_ratio": bytes_ratio,
             "drift": drift, "token_agreement": float(agree)}], bytes_ratio


def run_churn(log=print, n_tenants: int = CHURN_TENANTS,
              n_slots: int = CHURN_SLOTS, reps: int = 5):
    """10k-tenant Zipf churn over a 32-slot tiered pool.

    Three measured settings, all on the shared bench config:

      * ``serve/tier_flat32``  — flat 32-slot pool, 32 resident tenants
        (the all-resident reference the tiered store must not tax);
      * ``serve/tier_warm``    — TieredAdapterStore serving the same 32
        tenants once they are T0-resident: every lookup is a pure T0
        hit, so this bounds the steady-state overhead of the tier
        bookkeeping (gate: within 1.05x of flat);
      * ``serve/tier_churn``   — Zipf(s=1.1) arrivals over all 10k
        registered tenants.  Most requests promote through T1/T2
        mid-serve (batched donated scatters between decode chunks,
        async prefetch from the batcher queue), so this measures the
        hot-swap cost under realistic skewed churn (gate: at least
        0.5x of the all-resident throughput).

    Registration itself (10k ``register`` calls spilling ~10k msgpack
    shards through the capacity-bounded T1) is timed and reported but
    not gated — it is a control-plane path.
    """
    import shutil
    import tempfile

    from repro import obs

    cfg = BENCH_CFG
    base = M.init_params(jax.random.PRNGKey(0), cfg)
    shared = peft.add_lora(base, cfg, jax.random.PRNGKey(1), decomposed=True)
    shared = pt.tree_map_with_path(
        lambda p, x: x + 0.25 if p.endswith("B_mag") else x, shared)
    # per-tenant ΔB_M payloads: tiny host trees stamped from one template
    template = jax.tree.map(np.asarray, pt.filter_tree(
        shared, lambda p: p.endswith("dB_mag")))

    def overlay(t: int):
        d = np.float32(0.05 * ((t % 37) + 1))
        return jax.tree.map(lambda x: x + d, template)

    rng = np.random.default_rng(0)
    prompts = np.asarray(rng.integers(5, cfg.vocab_size,
                                      size=(CHURN_REQS, PROMPT)), np.int32)

    def make_engine(store):
        return ServeEngine(base, cfg, store, max_rows=CHURN_ROWS,
                           max_prompt_len=PROMPT,
                           max_len=PROMPT + CHURN_NEW + 8, decode_chunk=8)

    def timed(engine, reqs):
        t0 = time.perf_counter()
        engine.generate(reqs, n_new=CHURN_NEW)
        return time.perf_counter() - t0

    # -- flat all-resident reference (32 tenants == 32 slots) ----------
    flat = AdapterStore(base, cfg, n_slots=n_slots, kind="dora_mag",
                        shared=shared)
    for t in range(n_slots):
        flat.register(f"tenant{t}", overlay(t))
    eng_flat = make_engine(flat)
    reqs32 = [(f"tenant{i % n_slots}", prompts[i]) for i in range(CHURN_REQS)]
    out_flat = eng_flat.generate(reqs32, n_new=CHURN_NEW)   # compile + warm
    tok = CHURN_REQS * CHURN_NEW

    shard_dir = tempfile.mkdtemp(prefix="tier_churn_")
    tel = obs.enable()          # metrics-only sink: tier counters below
    try:
        tiered = TieredAdapterStore(base, cfg, shard_dir=shard_dir,
                                    host_capacity=CHURN_T1, n_slots=n_slots,
                                    kind="dora_mag", shared=shared)
        t0 = time.perf_counter()
        for t in range(n_tenants):
            tiered.register(f"tenant{t}", overlay(t))
        reg_s = time.perf_counter() - t0
        eng_tier = make_engine(tiered)

        # warm-T0: same 32 tenants, all resident after the first pass —
        # and bit-identical to the flat pool
        out_warm = eng_tier.generate(reqs32, n_new=CHURN_NEW)
        for a, b in zip(out_flat, out_warm):
            np.testing.assert_array_equal(a, b)

        # Zipf churn schedules over the full registry
        ranks = np.arange(1, n_tenants + 1, dtype=np.float64)
        p = 1.0 / ranks ** CHURN_ZIPF_S
        p /= p.sum()
        ids = rng.choice(n_tenants, size=((reps + 1) * CHURN_REQS), p=p)
        scheds = [
            [(f"tenant{ids[r * CHURN_REQS + i]}", prompts[i])
             for i in range(CHURN_REQS)]
            for r in range(reps + 1)]
        eng_tier.generate(scheds[0], n_new=CHURN_NEW)       # warm the path

        # interleaved reps (perf_micro idiom): this container's wall
        # clock drifts across seconds, so the gated ratios must come
        # from measurements taken side by side, min as the estimator
        t_flat = t_warm = t_churn = float("inf")
        for r in range(reps):
            t_flat = min(t_flat, timed(eng_flat, reqs32))
            eng_tier.generate(reqs32, n_new=CHURN_NEW)  # re-pin tenants
            t_warm = min(t_warm, timed(eng_tier, reqs32))
            t_churn = min(t_churn, timed(eng_tier, scheds[r + 1]))
        tps_flat = tok / t_flat
        tps_warm = tok / t_warm
        tps_churn = tok / t_churn
        warm_ratio = t_warm / t_flat
        churn_ratio = tps_churn / tps_flat
        resident = len(tiered.resident_tenants)
        m = tel.metrics
        tier_stats = {
            "t0_hits": m.counter("pool/tier_hits").value(tier="t0"),
            "t1_hits": m.counter("pool/tier_hits").value(tier="t1"),
            "t1_misses": m.counter("pool/tier_misses").value(tier="t1"),
            "t1_promotions": m.counter("pool/promotions").value(src="t1"),
            "t2_promotions": m.counter("pool/promotions").value(src="t2"),
            "prefetched": m.counter("pool/prefetched").value(),
            "t1_spills": m.counter("pool/t1_spills").value(),
        }
    finally:
        obs.disable()
        shutil.rmtree(shard_dir, ignore_errors=True)

    log(f"[bench] serve/tier_flat32 {tps_flat:9.1f} tok/s  "
        f"({n_slots} resident tenants, flat pool)")
    log(f"[bench] serve/tier_warm   {tps_warm:9.1f} tok/s  "
        f"(warm T0 hits; {warm_ratio:.3f}x flat wall, bar 1.05x)")
    log(f"[bench] serve/tier_churn  {tps_churn:9.1f} tok/s  "
        f"(Zipf s={CHURN_ZIPF_S} over {n_tenants} tenants, "
        f"{churn_ratio:.2f}x all-resident throughput, bar 0.5x)")
    log(f"[bench] tier registration {n_tenants} tenants in {reg_s:.1f}s "
        f"(T1 cap {CHURN_T1}, {tiered.bytes_per_tenant()} B/tenant, "
        f"{resident} resident at end)")
    log(f"[bench] tier telemetry "
        + " ".join(f"{k}={int(v)}" for k, v in tier_stats.items()))
    return [{"arch": "serve/tier_flat32", "tokens_s": tps_flat,
             "us": t_flat * 1e6},
            {"arch": "serve/tier_warm", "tokens_s": tps_warm,
             "us": t_warm * 1e6, "ratio": warm_ratio},
            {"arch": "serve/tier_churn", "tokens_s": tps_churn,
             "us": t_churn * 1e6, "ratio": churn_ratio,
             "n_tenants": n_tenants, "n_slots": n_slots,
             "register_s": reg_s, **tier_stats}], (warm_ratio, churn_ratio)


def main():
    rows, speedup = run()
    qrows, bytes_ratio = run_quant()
    print("name,us_per_call,derived")
    for r in rows:
        print(f"serve/{r['arch'].split('/')[1]},{r['us']:.0f},"
              f"tokens_s={r['tokens_s']:.1f}")
    for r in qrows:
        print(f"serve/{r['arch'].split('/')[1]},{r['us']:.0f},"
              f"tokens_s={r['tokens_s']:.1f}")
    print(f"# mixed-batch speedup over merge-per-tenant: {speedup:.2f}x")
    print(f"# int8 decode analytic speedup (weight-byte ratio): "
          f"{bytes_ratio:.2f}x")
    return rows + qrows


if __name__ == "__main__":
    from repro.utils.runtime import enable_compile_cache
    enable_compile_cache()
    main()
