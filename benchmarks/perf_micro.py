"""CPU micro-benchmarks: wall time of one forward/train/decode step per
reduced architecture, plus the federated round engine — the scanned
``FedSim.local_round`` (one jitted lax.scan over local steps) against the
seed-style per-step loop (``local_round_reference``) at paper-scale
settings (4 clients, 5 local steps).  Real measured numbers on this
container; the TPU numbers live in the roofline table, which is analytic
by necessity."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_smoke_config
from repro.models import model as M
from repro.models.config import ArchConfig

B, S = 2, 64

FED_CFG = ArchConfig(name="fed-bench", family="dense", n_layers=4,
                     d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
                     vocab_size=512, dtype="float32", lora_rank=8,
                     lora_dropout=0.0)


def _batch(cfg, rng):
    S_tok = S
    extras = {}
    if cfg.frontend and not cfg.n_enc_layers:
        S_tok = S - cfg.frontend_tokens
        extras["frontend_emb"] = jnp.asarray(
            rng.normal(size=(B, cfg.frontend_tokens, cfg.d_model)), jnp.float32)
    if cfg.n_enc_layers:
        extras["frontend_emb"] = jnp.asarray(
            rng.normal(size=(B, 16, cfg.d_model)), jnp.float32)
    return {"tokens": jnp.asarray(rng.integers(5, cfg.vocab_size,
                                               size=(B, S_tok)), jnp.int32),
            "loss_mask": jnp.ones((B, S_tok), jnp.float32), **extras}


def _time(fn, *args, reps=5):
    fn(*args)                                  # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def run(log=print):
    rng = np.random.default_rng(0)
    rows = []
    for arch in ARCH_IDS:
        cfg = get_smoke_config(arch)
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        batch = _batch(cfg, rng)
        fwd = jax.jit(lambda p, b: M.loss_and_metrics(p, b, cfg)[0])
        us_f = _time(fwd, params, batch)
        cache = M.init_cache(cfg, B, S)
        dec = jax.jit(lambda p, t, c, i: M.decode_step(
            p, t, c, i, cfg,
            enc_out=jnp.zeros((B, 16, cfg.d_model)) if cfg.n_enc_layers else None)[0])
        us_d = _time(dec, params, jnp.ones((B,), jnp.int32), cache,
                     jnp.asarray(5))
        rows.append({"arch": arch, "fwd_us": us_f, "dec_us": us_d})
        log(f"[perf] {arch:24s} fwd={us_f:9.0f}us decode={us_d:9.0f}us")
    return rows


def run_fed_round(log=print, n_clients: int = 4, local_steps: int = 5,
                  reps: int = 8):
    """Scanned round engine vs the seed per-step loop (paper-scale
    settings: 4 clients × 5 local steps, fedlora_opt).  The scan wins on
    (a) no per-step host sync or Python/jit dispatch, (b) donated adapter
    and optimizer buffers, (c) activation temporaries reused across the
    local steps of one round instead of reallocated per dispatch."""
    from repro.fed.simulate import FedHyper, FedSim

    hp = FedHyper(method="fedlora_opt", n_clients=n_clients,
                  local_steps=local_steps, batch=32, seq_len=64)
    rng = np.random.default_rng(0)
    batches = [{"tokens": jnp.asarray(
                    rng.integers(5, FED_CFG.vocab_size,
                                 size=(n_clients, hp.batch, hp.seq_len)),
                    jnp.int32),
                "loss_mask": jnp.ones((n_clients, hp.batch, hp.seq_len),
                                      jnp.float32)}
               for _ in range(local_steps)]
    key = jax.random.PRNGKey(0)

    def one(round_fn, sim):
        t0 = time.perf_counter()
        round_fn(batches, key)
        jax.block_until_ready(sim.client_adapters)
        return time.perf_counter() - t0

    # warm/compile both, then interleave reps so box noise hits both
    # paths equally; min over reps is the noise-robust estimator on a
    # shared machine (interference only ever adds time).
    sim_scan, sim_ref = FedSim(FED_CFG, hp), FedSim(FED_CFG, hp)
    one(sim_scan.local_round, sim_scan)
    one(sim_ref.local_round_reference, sim_ref)
    ts_scan, ts_ref = [], []
    for _ in range(reps):
        ts_scan.append(one(sim_scan.local_round, sim_scan))
        ts_ref.append(one(sim_ref.local_round_reference, sim_ref))
    us_scan, us_ref = min(ts_scan) * 1e6, min(ts_ref) * 1e6
    speedup = us_ref / us_scan
    log(f"[perf] fed_round/scan     {us_scan:9.0f}us  "
        f"({n_clients} clients x {local_steps} steps)")
    log(f"[perf] fed_round/per_step {us_ref:9.0f}us  speedup={speedup:.2f}x")
    return [{"arch": "fed_round/scan", "us": us_scan},
            {"arch": "fed_round/per_step", "us": us_ref}], speedup


def run_het_round(log=print, n_clients: int = 6, local_steps: int = 5,
                  reps: int = 6):
    """Masked mixed-rank round vs the uniform-rank round (same engine,
    same allocated rank).  The heterogeneous fleet rides the identical
    jitted lax.scan with per-client rank masks multiplied into the
    updates — adapter-sized elementwise work, so the masked round should
    sit within ~1.2× of the uniform one (the acceptance bar for not
    paying a second program for scenario diversity)."""
    from repro.fed.simulate import FedHyper, FedSim

    ranks = tuple([2, 4, 8] * (n_clients // 3 + 1))[:n_clients]
    hp_uni = FedHyper(method="fedlora_opt", n_clients=n_clients,
                      local_steps=local_steps, batch=32, seq_len=64)
    hp_het = FedHyper(method="fedlora_opt", n_clients=n_clients,
                      local_steps=local_steps, batch=32, seq_len=64,
                      client_ranks=ranks)
    rng = np.random.default_rng(0)
    batches = [{"tokens": jnp.asarray(
                    rng.integers(5, FED_CFG.vocab_size,
                                 size=(n_clients, hp_uni.batch,
                                       hp_uni.seq_len)), jnp.int32),
                "loss_mask": jnp.ones((n_clients, hp_uni.batch,
                                       hp_uni.seq_len), jnp.float32)}
               for _ in range(local_steps)]
    key = jax.random.PRNGKey(0)

    def one(sim):
        t0 = time.perf_counter()
        sim.local_round(batches, key)
        jax.block_until_ready(sim.client_adapters)
        return time.perf_counter() - t0

    sim_uni, sim_het = FedSim(FED_CFG, hp_uni), FedSim(FED_CFG, hp_het)
    one(sim_uni), one(sim_het)                  # compile + warm
    ts_uni, ts_het = [], []
    for _ in range(reps):                        # interleave (box noise)
        ts_uni.append(one(sim_uni))
        ts_het.append(one(sim_het))
    us_uni, us_het = min(ts_uni) * 1e6, min(ts_het) * 1e6
    ratio = us_het / us_uni
    log(f"[perf] fed_round/uniform    {us_uni:9.0f}us  "
        f"({n_clients} clients x {local_steps} steps, r=8)")
    log(f"[perf] fed_round/het_masked {us_het:9.0f}us  "
        f"ranks={ranks} ratio={ratio:.2f}x (bar: 1.2x)")
    return [{"arch": "fed_round/uniform", "us": us_uni, "ratio": 1.0},
            {"arch": "fed_round/het_masked", "us": us_het,
             "ratio": ratio}], ratio


def run_cohort(log=print, n_clients: int = 4, local_steps: int = 5,
               n_total: int = 16, reps: int = 6):
    """Sampled-cohort round (ClientBank gather → faulted round →
    masked scatter, stragglers buffered host-side) vs the bare
    full-participation round at the same cohort size.  Everything the
    cross-device layer adds is host work plus adapter-sized elementwise
    fault transforms, so the sampled round must stay within ~1.2× of
    the full-fleet round — the acceptance bar for fleet scale-out not
    taxing the jitted round."""
    from repro.fed import CohortSim, FaultPlan
    from repro.fed.simulate import FedHyper, FedSim

    hp = FedHyper(method="fedlora_opt", n_clients=n_clients,
                  local_steps=local_steps, batch=32, seq_len=64)
    rng = np.random.default_rng(0)
    batches = [{"tokens": jnp.asarray(
                    rng.integers(5, FED_CFG.vocab_size,
                                 size=(n_clients, hp.batch, hp.seq_len)),
                    jnp.int32),
                "loss_mask": jnp.ones((n_clients, hp.batch, hp.seq_len),
                                      jnp.float32)}
               for _ in range(local_steps)]
    key = jax.random.PRNGKey(0)

    sim_full = FedSim(FED_CFG, hp)
    cs = CohortSim(FedSim(FED_CFG, hp), n_total=n_total,
                   faults=FaultPlan(dropout_rate=0.125,
                                    straggler_rate=0.125, seed=0), seed=0)

    def one_full():
        t0 = time.perf_counter()
        sim_full.run_round(batches, key)
        jax.block_until_ready(sim_full.client_adapters)
        return time.perf_counter() - t0

    def one_cohort():
        t0 = time.perf_counter()
        cs.run_round(batches, key)
        jax.block_until_ready(cs.sim.client_adapters)
        return time.perf_counter() - t0

    one_full(), one_cohort()                    # compile + warm (both
    # programs: the faulted round is a distinct jitted specialization)
    ts_full, ts_coh = [], []
    for _ in range(reps):                        # interleave (box noise)
        ts_full.append(one_full())
        ts_coh.append(one_cohort())
    us_full, us_coh = min(ts_full) * 1e6, min(ts_coh) * 1e6
    ratio = us_coh / us_full
    log(f"[perf] fed_round/full_fleet     {us_full:9.0f}us  "
        f"({n_clients} clients x {local_steps} steps)")
    log(f"[perf] fed_round/sampled_cohort {us_coh:9.0f}us  "
        f"(cohort {n_clients} of {n_total}, faults on) "
        f"ratio={ratio:.2f}x (bar: 1.2x)")
    return [{"arch": "fed_round/full_fleet", "us": us_full, "ratio": 1.0},
            {"arch": "fed_round/sampled_cohort", "us": us_coh,
             "ratio": ratio}], ratio


def run_dist_round(log=print, local_steps: int = 5, reps: int = 6):
    """Production shard_map collective round (launch/train) vs the
    single-process FedSim engine round at matched settings, on a
    data-only client mesh over every visible device (1 on a default CPU
    run; under the --dist lane's XLA flag, 8 virtual devices → 8
    clients).  The adapter payload is tiny, so the ratio isolates what
    the move from a vmapped client axis to one-client-per-shard
    collectives costs in dispatch + collective overhead."""
    import jax

    from repro.fed.simulate import FedHyper, FedSim
    from repro.launch.mesh import make_client_mesh
    from repro.launch.train import TrainSettings, make_fed_train_step

    C = jax.device_count()
    hp = FedHyper(method="fedlora_opt", n_clients=C,
                  local_steps=local_steps, batch=8, seq_len=64)
    sim = FedSim(FED_CFG, hp)
    mesh = make_client_mesh(C)
    st = TrainSettings(lr=hp.lr, micro_batches=1, clip=hp.clip, remat=False,
                       method=hp.method, local_steps=local_steps)
    step_fn = jax.jit(make_fed_train_step(FED_CFG, mesh, st)[0])
    rng = np.random.default_rng(0)
    batches = [{"tokens": jnp.asarray(
                    rng.integers(5, FED_CFG.vocab_size,
                                 size=(C, hp.batch, hp.seq_len)), jnp.int32),
                "loss_mask": jnp.ones((C, hp.batch, hp.seq_len),
                                      jnp.float32)}
               for _ in range(local_steps)]
    big = {k: jnp.concatenate([b[k] for b in batches], axis=1)
           for k in batches[0]}
    key = jax.random.PRNGKey(0)

    ad, ost = sim.client_adapters, sim.opt_state
    step0 = jnp.zeros((), jnp.int32)

    def one_prod():
        nonlocal ad, ost, step0
        t0 = time.perf_counter()
        ad, ost, _ = step_fn(sim.base, ad, ost, step0, big)
        jax.block_until_ready(ad)
        step0 = step0 + local_steps
        return time.perf_counter() - t0

    def one_sim():
        t0 = time.perf_counter()
        sim.run_round(batches, key)
        jax.block_until_ready(sim.client_adapters)
        return time.perf_counter() - t0

    one_prod(), one_sim()                       # compile + warm
    ts_prod, ts_sim = [], []
    for _ in range(reps):                        # interleave (box noise)
        ts_prod.append(one_prod())
        ts_sim.append(one_sim())
    us_prod, us_sim = min(ts_prod) * 1e6, min(ts_sim) * 1e6
    ratio = us_prod / us_sim
    log(f"[perf] fed_round/engine    {us_sim:9.0f}us  "
        f"({C} clients x {local_steps} steps)")
    log(f"[perf] fed_round/shardmap  {us_prod:9.0f}us  "
        f"ratio={ratio:.2f}x vs engine ({len(jax.devices())} devices)")
    return [{"arch": "fed_round/engine", "us": us_sim, "ratio": 1.0},
            {"arch": "fed_round/shardmap", "us": us_prod,
             "ratio": ratio}], ratio


def run_pipeline(log=print, local_steps: int = 3, global_steps: int = 2,
                 personal_steps: int = 2, reps: int = 5):
    """Full three-stage paper pipeline: the shard_map pipeline engine
    (launch/train.make_fed_pipeline_step — stage-1 round + collective,
    stage-2 global optimizer on replicated server batches, stage-3
    per-client personalization) vs the FedSim three-stage sequence
    (run_round → global_stage → personalize) at matched settings, on a
    data-only client mesh over every visible device.  At 1 device both
    paths run the same math once, so the ratio isolates the pipeline's
    dispatch + collective overhead — the bar is ~1.00x."""
    import jax

    from repro.fed.simulate import FedHyper, FedSim
    from repro.launch.mesh import make_client_mesh
    from repro.launch.train import TrainSettings, make_fed_pipeline_step

    C = jax.device_count()
    hp = FedHyper(method="fedlora_opt", n_clients=C, local_steps=local_steps,
                  global_steps=global_steps, personal_steps=personal_steps,
                  batch=8, seq_len=64)
    sim = FedSim(FED_CFG, hp)
    mesh = make_client_mesh(C)
    st = TrainSettings(lr=hp.lr, micro_batches=1, clip=hp.clip, remat=False,
                       method=hp.method, local_steps=local_steps,
                       server_lr=hp.server_lr, global_steps=global_steps,
                       personal_steps=personal_steps, lam=hp.lam)
    pipe = make_fed_pipeline_step(FED_CFG, mesh, st)
    rng = np.random.default_rng(0)

    def cbatches(n):
        return [{"tokens": jnp.asarray(
                    rng.integers(5, FED_CFG.vocab_size,
                                 size=(C, hp.batch, hp.seq_len)), jnp.int32),
                 "loss_mask": jnp.ones((C, hp.batch, hp.seq_len),
                                       jnp.float32)}
                for _ in range(n)]

    def sbatches(n):
        return [{"tokens": jnp.asarray(
                    rng.integers(5, FED_CFG.vocab_size,
                                 size=(hp.batch, hp.seq_len)), jnp.int32),
                 "loss_mask": jnp.ones((hp.batch, hp.seq_len), jnp.float32)}
                for _ in range(n)]

    def flat(bs, axis):
        return {k: jnp.concatenate([b[k] for b in bs], axis=axis)
                for k in bs[0]}

    cb, sb, pb = (cbatches(local_steps), sbatches(global_steps),
                  cbatches(personal_steps))
    big_c, big_s, big_p = flat(cb, 1), flat(sb, 0), flat(pb, 1)
    key = jax.random.PRNGKey(0)

    ad, ost = sim.client_adapters, sim.opt_state
    step0 = jnp.zeros((), jnp.int32)

    def one_prod():
        nonlocal ad, ost, step0
        t0 = time.perf_counter()
        ad, ost, _, _, _ = pipe.run_pipeline(sim.base, ad, ost, step0,
                                             big_c, big_s, big_p)
        jax.block_until_ready(ad)
        step0 = step0 + local_steps
        return time.perf_counter() - t0

    def one_sim():
        t0 = time.perf_counter()
        sim.local_round(cb, key)
        agg = sim.aggregate()
        sim.global_stage(agg, sb, key)
        sim.personalize(pb, key)
        jax.block_until_ready(sim.client_adapters)
        return time.perf_counter() - t0

    one_prod(), one_sim()                       # compile + warm
    ts_prod, ts_sim = [], []
    for _ in range(reps):                        # interleave (box noise)
        ts_prod.append(one_prod())
        ts_sim.append(one_sim())
    us_prod, us_sim = min(ts_prod) * 1e6, min(ts_sim) * 1e6
    ratio = us_prod / us_sim
    log(f"[perf] pipeline/engine    {us_sim:9.0f}us  "
        f"({C} clients, {local_steps}+{global_steps}+{personal_steps} steps)")
    log(f"[perf] pipeline/shardmap  {us_prod:9.0f}us  "
        f"ratio={ratio:.2f}x vs engine ({len(jax.devices())} devices, "
        f"bar: 1.00x at 1 device)")
    return [{"arch": "pipeline/engine", "us": us_sim, "ratio": 1.0},
            {"arch": "pipeline/shardmap", "us": us_prod,
             "ratio": ratio}], ratio


def run_quant(log=print, reps: int = 6):
    """Quantized-backbone decode: wall time of one decode step on the
    f32 vs int8 vs int4 backbone, plus the *analytic* decode byte ratio
    — batch-1 decode is weight-bytes-bound, so bytes(f32 tree) /
    bytes(quantized tree) is the roofline speedup on a bandwidth-bound
    accelerator.  CPU wall-clock is reported honestly (this container's
    XLA dequant-fused fallback roughly ties f32; the win is the byte
    ratio, which is what the CI gate checks)."""
    from repro.kernels.quant_matmul.ops import quantize_backbone
    from repro.utils import pytree as pt

    cfg = FED_CFG
    base = M.init_params(jax.random.PRNGKey(0), cfg)
    cache = M.init_cache(cfg, 1, 64)
    tok = jnp.ones((1,), jnp.int32)

    def dec(params):
        f = jax.jit(lambda p, t, c, i: M.decode_step(p, t, c, i, cfg)[0])
        f(params, tok, cache, jnp.asarray(5))            # compile + warm
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            out = f(params, tok, cache, jnp.asarray(5))
            jax.block_until_ready(out)
            ts.append(time.perf_counter() - t0)
        return min(ts) * 1e6

    us_f32 = dec(base)
    rows = [{"arch": "quant/decode_f32", "us": us_f32,
             "bytes": pt.tree_bytes(base)}]
    log(f"[perf] quant/decode_f32   {us_f32:9.0f}us  "
        f"({pt.tree_bytes(base)} B weights)")
    ratios = {}
    for mode in ("int8", "int4"):
        qtree = quantize_backbone(base, mode)
        us_q = dec(qtree)
        ratios[mode] = pt.tree_bytes(base) / pt.tree_bytes(qtree)
        rows.append({"arch": f"quant/decode_{mode}", "us": us_q,
                     "bytes": pt.tree_bytes(qtree),
                     "wall_ratio": us_f32 / us_q,
                     "bytes_ratio": ratios[mode]})
        log(f"[perf] quant/decode_{mode}  {us_q:9.0f}us  "
            f"bytes_ratio={ratios[mode]:.2f}x "
            f"wall_ratio={us_f32 / us_q:.2f}x (analytic win is bytes)")
    return rows, ratios["int8"]


def run_obs(log=print, n_clients: int = 4, local_steps: int = 5,
            reps: int = 6, serve_reps: int = 24,
            out_path: str = "experiments/bench/obs_telemetry.jsonl"):
    """Telemetry overhead gate: the instrumented loops (live obs sink,
    JSONL events on) vs the same loops with the no-op sink, on the two
    hot paths the observability layer touches — the masked het federated
    round (run_het_round settings) and the multi-tenant serve loop.
    The enabled path pays host-side clocks, dict updates and JSONL
    writes only (the jitted programs are byte-identical either way), so
    the interleaved min-of-reps ratio must stay under the checked-in
    1.05x bar (baselines/obs_overhead.json).  Side effect: ``out_path``
    is left holding the run's events + a metrics snapshot — the CI
    telemetry artifact that ``telemetry_section`` renders."""
    import os

    from benchmarks import serve_multitenant
    from repro import obs
    from repro.fed.simulate import FedHyper, FedSim
    from repro.serve import AdapterStore, ServeEngine
    from repro.utils import pytree as pt

    # fresh artifact: drop the live file and any rotated segments
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    for p in [out_path] + [f"{out_path}.{i}" for i in range(1, 4)]:
        if os.path.exists(p):
            os.remove(p)

    # batch 16 (vs run_het_round's 32): a shorter round is *harder* on
    # this gate — the per-round host epilogue is fixed cost, so its
    # relative weight grows — and buys enough reps for a stable floor
    ranks = tuple([2, 4, 8] * (n_clients // 3 + 1))[:n_clients]
    hp = FedHyper(method="fedlora_opt", n_clients=n_clients,
                  local_steps=local_steps, batch=16, seq_len=64,
                  client_ranks=ranks)
    rng = np.random.default_rng(0)
    batches = [{"tokens": jnp.asarray(
                    rng.integers(5, FED_CFG.vocab_size,
                                 size=(n_clients, hp.batch, hp.seq_len)),
                    jnp.int32),
                "loss_mask": jnp.ones((n_clients, hp.batch, hp.seq_len),
                                      jnp.float32)}
               for _ in range(local_steps)]
    key = jax.random.PRNGKey(0)
    sim = FedSim(FED_CFG, hp)

    def one_round():
        t0 = time.perf_counter()
        sim.run_round(batches, key)
        jax.block_until_ready(sim.client_adapters)
        return time.perf_counter() - t0

    # 8 tenants through 4 rows: long enough for a stable min-of-reps
    # (a ~10ms loop cannot resolve a 5% bar through box noise) and the
    # queue actually queues, so admission/retire telemetry is on the
    # measured path
    n_tenants, n_new = 8, 48
    cfg, base, shared, tenants, prompts = serve_multitenant._setting(
        n_tenants)
    store = AdapterStore(base, cfg, n_slots=n_tenants, kind="dora_mag",
                         shared=shared)
    for name, tree in tenants.items():
        store.register(name, pt.filter_tree(
            tree, lambda p: p.endswith("dB_mag")))
    engine = ServeEngine(base, cfg, store, max_rows=n_tenants // 2,
                         max_prompt_len=prompts.shape[1],
                         max_len=prompts.shape[1] + n_new + 8,
                         decode_chunk=8)
    reqs = [(f"tenant{t}", prompts[t]) for t in range(n_tenants)]

    def one_serve():
        t0 = time.perf_counter()
        engine.generate(reqs, n_new=n_new)
        return time.perf_counter() - t0

    obs.disable()
    one_round(), one_serve()                    # compile + warm, obs off
    ts = {"round_off": [], "round_on": [], "serve_off": [], "serve_on": []}

    def measure(fn, off_key, on_key, n, attempts):
        # interleaved pairs, min as the estimator — but adaptive: box
        # noise only ever *adds* time, so a ratio stuck above the bar
        # after one batch earns more samples (the floors converge to
        # the true ratio), while quiet boxes exit after one batch.  A
        # genuine leak (sync/transfer/per-step callback on the hot
        # path) shifts the floor itself and keeps failing every batch.
        for _ in range(attempts):
            for _ in range(n):
                obs.disable()
                ts[off_key].append(fn())
                obs.enable(out_path)            # append mode: events keep
                ts[on_key].append(fn())
            if min(ts[on_key]) / min(ts[off_key]) <= 1.03:
                break
        return min(ts[on_key]) / min(ts[off_key])

    r_ratio = measure(one_round, "round_off", "round_on", reps, attempts=3)
    # the serve loop is ~100x cheaper than the round, so buy its noise
    # floor down with many more interleaved reps — min-of-few on a
    # tens-of-ms loop cannot resolve a 5% bar on a shared box
    s_ratio = measure(one_serve, "serve_off", "serve_on", serve_reps,
                      attempts=4)
    # still enabled from the last interleaved pair — its registry holds
    # that pair's metrics, which is what the snapshot epilogue dumps
    obs.emit_snapshot()
    obs.disable()

    us = {k: min(v) * 1e6 for k, v in ts.items()}
    log(f"[perf] obs/het_round disabled={us['round_off']:9.0f}us "
        f"instrumented={us['round_on']:9.0f}us ratio={r_ratio:.3f}x")
    log(f"[perf] obs/serve     disabled={us['serve_off']:9.0f}us "
        f"instrumented={us['serve_on']:9.0f}us ratio={s_ratio:.3f}x "
        f"(bar: 1.05x; events -> {out_path})")
    rows = [{"arch": "obs/het_round_disabled", "us": us["round_off"],
             "ratio": 1.0},
            {"arch": "obs/het_round_instrumented", "us": us["round_on"],
             "ratio": r_ratio},
            {"arch": "obs/serve_disabled", "us": us["serve_off"],
             "ratio": 1.0},
            {"arch": "obs/serve_instrumented", "us": us["serve_on"],
             "ratio": s_ratio}]
    return rows, max(r_ratio, s_ratio)


def main():
    rows = run()
    fed_rows, speedup = run_fed_round()
    het_rows, het_ratio = run_het_round()
    cohort_rows, cohort_ratio = run_cohort()
    dist_rows, dist_ratio = run_dist_round()
    pipe_rows, pipe_ratio = run_pipeline()
    quant_rows, quant_ratio = run_quant()
    print("name,us_per_call,derived")
    for r in rows:
        print(f"perf/{r['arch']}/fwd,{r['fwd_us']:.0f},smoke_cpu")
        print(f"perf/{r['arch']}/decode,{r['dec_us']:.0f},smoke_cpu")
    for r in fed_rows:
        print(f"perf/{r['arch']},{r['us']:.0f},smoke_cpu")
    for r in het_rows + cohort_rows + dist_rows + pipe_rows + quant_rows:
        print(f"perf/{r['arch']},{r['us']:.0f},smoke_cpu")
    # ratios, not timings — kept out of the us_per_call column
    print(f"# fed_round speedup (per_step / scan): {speedup:.2f}x")
    print(f"# het_round overhead (het_masked / uniform): {het_ratio:.2f}x")
    print(f"# cohort_round overhead (sampled_cohort / full_fleet): "
          f"{cohort_ratio:.2f}x")
    print(f"# dist_round overhead (shardmap / engine): {dist_ratio:.2f}x")
    print(f"# pipeline overhead (shardmap / engine): {pipe_ratio:.2f}x")
    print(f"# quant decode byte ratio (f32 / int8, analytic): "
          f"{quant_ratio:.2f}x")
    return (rows + fed_rows + het_rows + cohort_rows + dist_rows
            + pipe_rows + quant_rows)


if __name__ == "__main__":
    from repro.utils.runtime import enable_compile_cache
    enable_compile_cache()
    main()
