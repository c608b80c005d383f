#!/usr/bin/env python3
"""Bring-up smoke: the system's two main paths on a TPU, through their
normal entry points, at DeepSeek-7B widths with seeded random weights.

    python chip_smoke.py             # one chip: train phase, serve phase
    python chip_smoke.py --chips 4   # four chips: the federated pipeline
                                     # with one client per chip vs FedSim

One chip:

  train  ``launch.train.make_fed_pipeline_step`` on ``make_client_mesh(1)``
         with method fedlora_opt (LoRA r=8 on q and v): two
         ``run_pipeline`` iterations (stage-1 round, stage 2 on ΔA_D,
         stage 3 on ΔB_M) over 2048-token sequences.  Fails unless every
         stage loss is finite and every stage changed its masked leaves.
  serve  ``ServeEngine`` over an ``AdapterStore(kind="dora_mag")`` of
         several tenants, once with the bf16 backbone and once with
         ``backbone_quant="int8"``; each answers requests with prompts of
         a few hundred tokens.  The ``bgmv_mag`` and ``quant_matmul``
         calls the engines make resolve to compiled Pallas kernels, and
         their outputs at the smoke's shapes agree with the ``ref.py``
         oracles run in float32 at full matmul precision.

Four chips: the same pipeline on ``make_client_mesh(4)`` (the psum /
all_gather aggregation collectives across chips) against the
``FedSim`` sequence ``run_round → global_stage → personalize`` on the
same inputs, and nothing else.

Only the depth is cut (8 of 30 layers on one chip, 2 on four).  The last
line of standard output is one JSON object; it says ``"ok": true`` only
when every phase passed.  With no TPU, or run outside the checkout, the
script exits non-zero before printing it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

N_LAYERS = {1: 8, 4: 2}       # depth kept of DeepSeek-7B's 30 layers
# tokens per training sequence.  On four chips FedSim runs every client
# on every chip (vmapped, replicated, float32): at 1024 tokens its round
# program needs 17.4 GB of a v5e's 15.75 GB of HBM, at 512 about 13 GB
SEQ = {1: 2048, 4: 512}
ROWS = 2                      # sequences per client per step
N_TENANTS, MAX_ROWS, PROMPT_MAX, N_NEW, CHUNK = 6, 8, 512, 32, 16
# kernel outputs are bf16 and their operands are rounded to bf16 at up to
# five points (x·A_mag, A_dir, h, B_dir, the output): each rounding is
# ≤ 2⁻⁹ relative, so agreement within 2% of the output's scale holds for
# a correct kernel, while a wrong slot, rank mask or group scale moves the
# output by O(100%) of it
KERNEL_RTOL = 2e-2
# the four-chip comparison runs in float32 at full matmul precision: in
# bf16, Adam's sign-like first steps turn rounding noise into differences
# of a quarter of the update (tiny-size CPU rehearsal), which would hide a
# broken collective.  In float32 the two engines differ only in reduction
# order (5.5e-8 of the update on the CPU rehearsal); 1e-3 leaves room for
# that, while a wrong collective (a client missing, wrong weights, the
# mean taken twice) moves the result by O(1) of the update
PIPELINE_RTOL = 1e-3


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond, msg: str) -> None:
    if not cond:
        fail(msg)


# ---------------------------------------------------------------------------
# shared set-up
# ---------------------------------------------------------------------------

def deepseek(chips: int):
    from repro.configs import deepseek_7b
    cfg = dataclasses.replace(deepseek_7b.ARCH, n_layers=N_LAYERS[chips])
    a = deepseek_7b.ARCH
    log(f"config: {a.name} d_model={cfg.d_model} heads={cfg.n_heads} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} dtype={cfg.dtype} "
        f"lora r={cfg.lora_rank} on {','.join(cfg.lora_targets)}; "
        f"depth cut {a.n_layers} -> {cfg.n_layers} layers, random weights "
        f"from seed 0")
    return cfg


def init_base(cfg, sharding=None):
    import jax
    from repro.models import model as M
    from repro.utils import pytree as pt
    base = jax.jit(M.init_params, static_argnums=1, out_shardings=sharding)(
        jax.random.PRNGKey(0), cfg)
    jax.block_until_ready(base)
    n = sum(x.size for x in jax.tree.leaves(base))
    log(f"base: {n / 1e9:.3f} B parameters, "
        f"{pt.tree_bytes(base) / 1e9:.2f} GB")
    return base


def token_batch(rng, cfg, lead):
    import jax.numpy as jnp
    return {"tokens": jnp.asarray(rng.integers(5, cfg.vocab_size, size=lead),
                                  jnp.int32),
            "loss_mask": jnp.ones(lead, jnp.float32)}


def rel_diff(a, b, ref):
    """‖a − b‖ / ‖b − ref‖ over every leaf, in float64."""
    import jax
    import numpy as np
    num = den = 0.0
    for x, y, z in zip(jax.tree.leaves(a), jax.tree.leaves(b),
                       jax.tree.leaves(ref)):
        x, y, z = (np.asarray(v, np.float64) for v in (x, y, z))
        num += float(np.sum((x - y) ** 2))
        den += float(np.sum((y - z) ** 2))
    return (num / max(den, 1e-300)) ** 0.5


def peak_gb(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 1e9:.2f} GB"


# ---------------------------------------------------------------------------
# one chip: train phase
# ---------------------------------------------------------------------------

def instrument(pipe, rec):
    """Wrap the three stage programs so ``run_pipeline`` — the entry point
    under test — still sequences them, while each call is timed to
    completion and its inputs/outputs kept for the mask checks."""
    import jax

    def wrap(stage, fn):
        def call(*args):
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn(*args))
            rec.setdefault(stage, []).append(
                (args, out, time.perf_counter() - t0))
            return out
        return call

    return dataclasses.replace(
        pipe, round_step=wrap("round", pipe.round_step),
        global_step=wrap("global", pipe.global_step),
        personal_step=wrap("personal", pipe.personal_step))


def changed(before, after, mask):
    """(every masked leaf changed, every unmasked leaf unchanged)."""
    import jax
    import numpy as np
    moved, kept = [], []
    for x, y, m in zip(jax.tree.leaves(before), jax.tree.leaves(after),
                       jax.tree.leaves(mask)):
        diff = not np.array_equal(np.asarray(x), np.asarray(y))
        (moved if m else kept).append(diff)
    return moved, kept


def train_phase(cfg, base, mesh, seed=0):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import aggregation as fedagg
    from repro.launch.train import (TrainSettings, make_fed_pipeline_step,
                                    pick_micro_batches)

    C, S = 1, SEQ[1]
    micro = pick_micro_batches(cfg, ROWS, S)
    st = TrainSettings(lr=1e-4, micro_batches=micro, method="fedlora_opt",
                       local_steps=1, global_steps=1, personal_steps=1)
    log(f"train: make_fed_pipeline_step on make_client_mesh({C}), "
        f"{st.method}, {ROWS}x{S} tokens per client step, "
        f"micro_batches={micro}")
    pipe = make_fed_pipeline_step(cfg, mesh, st)
    rec: dict = {}
    run = instrument(pipe, rec)

    ad = pipe.method.make_adapter(base, cfg, jax.random.PRNGKey(1))
    adapters = fedagg.broadcast_to_clients(ad, C)
    client = NamedSharding(mesh, P("data"))
    adapters = jax.device_put(adapters, client)
    # laid out as the round program returns it, so iteration 1 reuses
    # iteration 0's compiled program
    opt_state = jax.device_put(pipe.opt_init(adapters), client)
    rng = np.random.default_rng(seed)
    step = jnp.zeros((), jnp.int32)
    anchor = None
    losses = []
    for it in range(2):
        batch = token_batch(rng, cfg, (C, ROWS, S))
        server = token_batch(rng, cfg, (ROWS, S))
        personal = token_batch(rng, cfg, (C, ROWS, S))
        # dropout keys for the three stages (cfg.lora_dropout = 0.1)
        keys = jax.random.split(jax.random.PRNGKey(10 + it), 3)
        adapters, opt_state, _, anchor, met = run.run_pipeline(
            base, adapters, opt_state, step, batch, server, personal,
            prox_anchor=anchor, rng=keys[0], global_rng=keys[1],
            personal_rng=keys[2])
        step = step + st.local_steps
        ce = {k: float(met[k]["ce"]) for k in ("round", "global",
                                                "personal")}
        losses.append(ce)
        log(f"train: iteration {it}: stage-1 ce={ce['round']:.6f} "
            f"stage-2 ce={ce['global']:.6f} stage-3 ce={ce['personal']:.6f}"
            f"; wall " + " ".join(
                f"{k}={rec[k][it][2]:.3f}s" for k in ("round", "global",
                                                       "personal")))
    check(all(np.isfinite(v) for ce in losses for v in ce.values()),
          f"non-finite stage loss: {losses}")

    # every stage changed its own masked leaves (in at least one of the
    # iterations: stage 1 cannot move A until B_mag leaves zero) and
    # stages 2/3 left every other leaf of the tree they train untouched
    abs_ad = jax.eval_shape(lambda: ad)
    masks = {"round": pipe.method.stage_mask(abs_ad, "local_pretrain"),
             "global": pipe.method.stage_global_mask(abs_ad),
             "personal": pipe.method.stage_local_mask(abs_ad)}
    for stage in ("round", "global", "personal"):
        moved_any = None
        for args, out, _ in rec[stage]:       # trained tree: in 1, out 0
            moved, kept = changed(args[1], out[0], masks[stage])
            moved_any = moved if moved_any is None else [
                a or b for a, b in zip(moved_any, moved)]
            if stage != "round":
                check(not any(kept), f"stage {stage} moved leaves outside "
                      "its mask")
        check(moved_any and all(moved_any),
              f"stage {stage}: {moved_any.count(False)} of "
              f"{len(moved_any)} masked leaves never changed")
        log(f"train: stage {stage}: all {len(moved_any)} masked leaves "
            "changed")
    log(f"train: wall per stage after warm-up: " + " ".join(
        f"{k}={rec[k][-1][2]:.3f}s" for k in ("round", "global",
                                               "personal")))
    log(f"train: peak_bytes_in_use {peak_gb(jax.devices()[0])}")


# ---------------------------------------------------------------------------
# one chip: serve phase
# ---------------------------------------------------------------------------

def tenant_store(cfg, base):
    """Shared decomposed adapters with per-tenant ΔB_M, as in
    examples/serve_personalized.py."""
    import jax
    import jax.numpy as jnp
    from repro.core import peft
    from repro.serve import AdapterStore
    from repro.utils.pytree import filter_tree, tree_map_with_path

    shared = peft.add_lora(base, cfg, jax.random.PRNGKey(1), decomposed=True)
    shared = tree_map_with_path(
        lambda p, x: x + 0.2 if p.endswith("B_mag") else x, shared)
    store = AdapterStore(base, cfg, n_slots=N_TENANTS + 2, kind="dora_mag",
                         shared=shared)
    for t in range(N_TENANTS):
        variant = tree_map_with_path(
            lambda p, x: x + 0.3 * (t + 1) * jnp.sign(jnp.sin(
                jnp.arange(x.size, dtype=jnp.float32) + t)).reshape(x.shape)
            if p.endswith("dB_mag") else x, shared)
        store.register(f"tenant{t}", filter_tree(
            variant, lambda p: p.endswith("dB_mag")))
    return store


def requests(cfg, seed):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [(f"tenant{i % N_TENANTS}",
             np.asarray(rng.integers(5, cfg.vocab_size, size=(int(
                 rng.integers(PROMPT_MAX // 2, PROMPT_MAX + 1)),)), np.int32))
            for i in range(MAX_ROWS + 2)]


def serve_engine(cfg, base, store, quant):
    import jax
    import numpy as np
    from repro.serve import ServeEngine

    qcfg = dataclasses.replace(cfg, backbone_quant=quant)
    eng = ServeEngine(base, qcfg, store, max_rows=MAX_ROWS,
                      max_prompt_len=PROMPT_MAX, max_len=PROMPT_MAX + N_NEW,
                      decode_chunk=CHUNK)
    reqs = requests(cfg, 7)
    name = quant or "bf16"
    for attempt in ("compile+run", "warm"):
        t0 = time.perf_counter()
        outs = eng.generate(reqs, n_new=N_NEW)
        dt = time.perf_counter() - t0
        check(len(outs) == len(reqs)
              and all(o.shape == (N_NEW,) for o in outs)
              and all(((o >= 0) & (o < cfg.vocab_size)).all() for o in outs),
              f"serve {name}: malformed answers")
        log(f"serve[{name}]: {len(reqs)} requests, prompts "
            f"{min(len(r[1]) for r in reqs)}-{max(len(r[1]) for r in reqs)}"
            f" tokens, {N_NEW} new each, {attempt} wall {dt:.3f}s")
    heads = {tuple(o[:4].tolist()) for o in outs}
    log(f"serve[{name}]: first answer {outs[0][:8].tolist()}, "
        f"{len(heads)} distinct 4-token openings over {len(outs)} answers")
    jax.block_until_ready(eng.base)
    log(f"serve[{name}]: peak_bytes_in_use {peak_gb(jax.devices()[0])}")
    return eng, np.asarray([eng.store.slot_of(t) for t, _ in reqs])


def run_compiled(name, fn, *args):
    """Compile ``fn`` for ``args``, require a Pallas kernel in the
    compiled program, and run it."""
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    check("tpu_custom_call" in compiled.as_text(),
          f"{name}: no Pallas custom call in the compiled program")
    return compiled(*args)


def agree(name, got, want, rtol=KERNEL_RTOL):
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    check(got.shape == want.shape and np.isfinite(got).all(),
          f"{name}: shape {got.shape} vs {want.shape} or non-finite")
    scale = float(np.abs(want).max())
    err = float(np.abs(got - want).max())
    log(f"kernel {name}: max|err| {err:.4g} vs max|ref| {scale:.4g} "
        f"(ratio {err / max(scale, 1e-30):.3g}, limit {rtol})")
    check(scale > 0 and err <= rtol * scale, f"{name}: outside tolerance")


def bgmv_mag_check(cfg, store, slots):
    """The engine's bgmv_mag call (impl left to the backend) at the
    smoke's prefill and decode shapes, on layer 0's q_proj pool."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import bgmv_mag, bgmv_mag_ref
    from repro.kernels.dispatch import resolve_impl

    check(resolve_impl(None, "bgmv_mag") == "pallas",
          "bgmv_mag does not resolve to the Pallas kernel")
    q = store.overlay()["blocks"]["sub0"]["attn"]["q_proj"]
    leaf = lambda k: q[k][0]                             # layer 0
    pools = (leaf("bgmv_A_dir"), leaf("bgmv_A_mag"), leaf("bgmv_B_mag"),
             leaf("pool_dB_mag"), leaf("bgmv_B_dir"))
    ranks = leaf("pool_ranks")
    idx = jnp.asarray(slots[:MAX_ROWS], jnp.int32)
    scale = cfg.lora_alpha / cfg.lora_rank
    rng = np.random.default_rng(3)
    for shape in ((MAX_ROWS, PROMPT_MAX, cfg.d_model),
                  (MAX_ROWS, cfg.d_model)):
        x = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
        got = run_compiled("bgmv_mag", lambda x, *a: bgmv_mag(
            x, *a, idx, scale=scale, ranks=ranks), x, *pools)
        x3 = x.reshape(MAX_ROWS, -1, cfg.d_model)        # the oracle's layout
        with jax.default_matmul_precision("highest"):
            want = bgmv_mag_ref(x3.astype(jnp.float32), *pools, idx, scale,
                                ranks=ranks)
        agree(f"bgmv_mag x{shape}", got, want.reshape(got.shape))


def quant_matmul_check(cfg, qbase):
    """The quantized linear's quant_matmul call at the smoke's prefill
    and decode token counts, on layer 0's int8 q_proj and down_proj."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import quant_matmul, quant_matmul_ref
    from repro.kernels.dispatch import resolve_impl

    check(resolve_impl(None, "quant_matmul") == "pallas",
          "quant_matmul does not resolve to the Pallas kernel")
    blk = qbase["blocks"]["sub0"]
    rng = np.random.default_rng(4)
    for proj, leaf in (("q_proj", blk["attn"]["q_proj"]),
                       ("down_proj", blk["mlp"]["down_proj"])):
        q, s = leaf["kernel_q"][0], leaf["kernel_scale"][0]
        for m in (MAX_ROWS * PROMPT_MAX, MAX_ROWS):
            x = jnp.asarray(rng.normal(size=(m, q.shape[0])), jnp.bfloat16)
            got = run_compiled("quant_matmul", quant_matmul, x, q, s)
            with jax.default_matmul_precision("highest"):
                want = quant_matmul_ref(x.astype(jnp.float32), q, s)
            agree(f"quant_matmul int8 {proj} M={m} K={q.shape[0]} "
                  f"N={q.shape[1]}", got, want)


def serve_phase(cfg, base):
    import gc
    store = tenant_store(cfg, base)
    log(f"serve: AdapterStore(kind='dora_mag'), {N_TENANTS} tenants, "
        f"{store.bytes_per_tenant()} B of ΔB_M per tenant")
    eng, slots = serve_engine(cfg, base, store, None)
    bgmv_mag_check(cfg, store, slots)
    del eng
    gc.collect()
    eng, _ = serve_engine(cfg, base, store, "int8")
    quant_matmul_check(cfg, eng.base)
    del eng
    gc.collect()


# ---------------------------------------------------------------------------
# four chips: the pipeline with one client per chip vs FedSim
# ---------------------------------------------------------------------------

def four_chip_phase(cfg, mesh):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.fed.simulate import FedHyper, FedSim
    from repro.launch.train import TrainSettings, make_fed_pipeline_step

    C, S = 4, SEQ[4]
    cfg = dataclasses.replace(cfg, dtype="float32")
    log("4-chip: float32 weights, matmuls at full precision in both engines")
    client = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    # one replicated copy of the weights serves both engines: the
    # simulator's programs then run replicated over the four chips
    base = init_base(cfg, rep)
    hp = FedHyper(method="fedlora_opt", n_clients=C, local_steps=1,
                  batch=ROWS, seq_len=S, lr=1e-4, server_lr=5e-4,
                  global_steps=1, personal_steps=1, lam=1e-3)
    sim = FedSim(cfg, hp, base=base)
    st = TrainSettings(lr=hp.lr, micro_batches=1, clip=hp.clip,
                       method=hp.method, local_steps=1, server_lr=hp.server_lr,
                       global_steps=1, personal_steps=1, lam=hp.lam)
    pipe = make_fed_pipeline_step(cfg, mesh, st)
    log(f"4-chip: make_fed_pipeline_step on make_client_mesh({C}), "
        f"{hp.method}, {ROWS}x{S} tokens per client step, vs FedSim")
    init = jax.tree.map(np.asarray, sim.client_adapters)
    adapters = jax.device_put(sim.client_adapters, client)
    opt_state = jax.device_put(sim.opt_state, client)
    rng = np.random.default_rng(0)
    cb = [token_batch(rng, cfg, (C, ROWS, S))]
    sb = [token_batch(rng, cfg, (ROWS, S))]
    pb = [token_batch(rng, cfg, (C, ROWS, S))]

    # the simulator's dropout key chains (cfg.lora_dropout = 0.1): both
    # engines draw the same masks from the same keys
    keys = [jax.random.PRNGKey(k) for k in (0, 100, 200)]
    highest = jax.default_matmul_precision("highest")
    t0 = time.perf_counter()
    # production first: FedSim's stages donate their input buffers
    with highest:
        adapters, _, agg_p, _, met = pipe.run_pipeline(
            base, adapters, opt_state, np.zeros((), np.int32),
            jax.device_put(cb[0], client), jax.device_put(sb[0], rep),
            jax.device_put(pb[0], client), rng=keys[0], global_rng=keys[1],
            personal_rng=keys[2])
        jax.block_until_ready(adapters)
    log(f"4-chip: pipeline (compile+run) {time.perf_counter() - t0:.3f}s; "
        + " ".join(f"{k} ce={float(met[k]['ce']):.6f}"
                   for k in ("round", "global", "personal")))
    check(all(np.isfinite(float(met[k]["ce"]))
              for k in ("round", "global", "personal")),
          "4-chip: non-finite stage loss")
    t0 = time.perf_counter()
    with highest:
        sim.local_round(cb, keys[0])
        agg_s = sim.aggregate()
        agg_s = sim.global_stage(agg_s, sb, keys[1])
        sim.personalize(pb, keys[2])
        jax.block_until_ready(sim.client_adapters)
    log(f"4-chip: FedSim run_round -> global_stage -> personalize "
        f"(compile+run) {time.perf_counter() - t0:.3f}s")
    init0 = jax.tree.map(lambda x: x[0], init)
    r_ad = rel_diff(adapters, sim.client_adapters, init)
    r_agg = rel_diff(agg_p, agg_s, init0)
    log(f"4-chip: |pipeline - FedSim| / |FedSim update|: client adapters "
        f"{r_ad:.4g}, aggregated model {r_agg:.4g} (limit {PIPELINE_RTOL})")
    check(r_ad <= PIPELINE_RTOL and r_agg <= PIPELINE_RTOL,
          "4-chip: pipeline disagrees with FedSim")


# ---------------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train and serve phases; 4: the four-chip "
                         "federated pipeline vs FedSim only")
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no src/repro beside {Path(__file__).name}: run it from a "
             "checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        fail(f"needs a TPU, JAX found {dev.platform!r} devices")
    if len(devs) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, found "
             f"{len(devs)}")
    from repro.utils.runtime import enable_compile_cache
    cache_dir = enable_compile_cache()
    cache = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; "
        f"jax {jax.__version__}; compile cache {cache_dir}")

    from repro.launch.mesh import make_client_mesh
    t0 = time.perf_counter()
    cfg = deepseek(args.chips)
    mesh = make_client_mesh(args.chips)
    if args.chips == 4:
        four_chip_phase(cfg, mesh)
    else:
        base = init_base(cfg)
        train_phase(cfg, base, mesh)
        serve_phase(cfg, base)
    log(f"compile cache: {cache['hits']} hits, {cache['misses']} misses "
        f"in {cache_dir}")
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
