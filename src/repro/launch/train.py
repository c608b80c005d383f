"""Production federated train step + the paper's three-stage pipeline.

TPU-native mapping of the paper's round (DESIGN.md §4):

  · clients ↔ slices of the ('pod','data') axes — ONE client per data
    shard; each client's decomposed-LoRA adapters live only on its shard;
  · local SGD ↔ per-shard grad/update steps inside a shard_map that is
    MANUAL over ('pod','data') and AUTO over 'model' (XLA still does
    tensor parallelism inside each client);
  · aggregation ↔ the method's *collective form* (core.aggregation
    .CollectiveAgg) issued from inside the manual region — a weighted
    psum for the mean family, a per-row coverage-weighted psum for
    replication averaging, an all_gather of the stacked factors followed
    by QR/truncated-SVD re-factorization for exact aggregation.  The only
    cross-client (and the only cross-pod) traffic, a few MB of adapter
    state;
  · per-client state (the paper's personal ΔB_M, FedALT's individual
    pair) never crosses shards: keep-local leaves are restored from the
    shard's own values after the collective;
  · heterogeneous fleets ride the same program: per-client rank masks
    (peft.client_rank_masks) zero update rows above each client's rank
    and re-mask the rebroadcast inside the manual region;
  · FedProx's proximal anchor is the shard's round-start adapters — a
    per-shard leaf captured by the local-step scan, no extra state.

``make_fed_train_step`` returns ONE federated round (stage 1 + the
collective).  ``make_fed_pipeline_step`` extends that into the paper's
full three-stage pipeline (Eqs. 9–11) as three jitted shard_map
programs sharing one layout:

  stage 1  the round above — per-client local steps, then the method's
           collective; also emits the aggregate as a replicated leaf;
  stage 2  the global optimizer: only ``method.stage_global_mask``
           leaves (ΔA_D for the paper, Eq. 9) train on the server batch
           mixture — the aggregate carries no client axis and its
           optimizer state lives outside the client axis.  When the
           server batch divides evenly over the client axis, each shard
           computes gradients on its own slice of every micro-batch and
           a token-weighted psum recovers the full-batch gradient (dp×
           fewer backbone FLOPs per shard); otherwise every shard runs
           the identical replicated math.  The result is rebroadcast
           with the same keep-local/het-re-mask semantics as stage 1;
  stage 3  per-client personalization: only ``method.stage_local_mask``
           leaves (ΔB_M, Eq. 10) train per shard with the Eq. 11
           ½λ‖·‖²_F regularizer and NO collective — personalization
           never crosses shards.

``FedPipeline.run_pipeline`` sequences the three stages exactly like
the single-process oracle (``FedSim.run_round`` → ``global_stage`` →
``personalize``); the rebroadcast/keep-local/het-re-mask logic is the
shared ``core.aggregation.client_rebroadcast`` so the two paths cannot
diverge.  The parity sweep in tests/test_distributed.py pins the full
pipeline to the simulator for every registry method.

Gradient accumulation: each local step's batch is split into
micro-batches (a lax.scan, so HLO stays one body deep) so scan-boundary
activations of an 88-layer model fit HBM; LoRA grads are accumulated in
f32.
"""
from __future__ import annotations

import dataclasses
import re
import time
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core import aggregation as fedagg
from repro.core import peft
from repro.core.methods import get_method
from repro.launch.mesh import data_axes, dp_size
from repro.models import model as M
from repro.models.config import ArchConfig
from repro.optim import adamw, masked
from repro.optim.optimizers import apply_updates, clip_by_global_norm
from repro.utils import pytree as pt
from repro.utils import sharding as shd

Params = Any


@dataclasses.dataclass(frozen=True)
class TrainSettings:
    lr: float = 1e-4
    micro_batches: int = 1
    clip: float = 1.0
    remat: object = True          # True (full) | "dots" | False
    # stage: which components train (paper pipeline stages)
    stage: str = "local_pretrain"   # | "global" | "local"
    # federated method (core.methods registry) — drives the adapter
    # factory, the per-stage trainable mask, the keep-local leaves, and
    # the collective aggregation form
    method: str = "fedlora_opt"
    # local optimizer steps per round (per train_step call); the batch
    # carries local_steps × per-step-batch rows per client, step-major
    local_steps: int = 1
    # FedProx proximal coefficient (only consulted for prox methods)
    prox_mu: float = 0.0
    # Heterogeneous fleet: one LoRA rank per client (len == dp_size(mesh));
    # None → uniform at cfg.lora_rank.  Mirrors FedHyper.client_ranks.
    client_ranks: Optional[tuple] = None
    # server-side allocation rank for a heterogeneous fleet (0 → fleet max)
    server_rank: int = 0
    # per-client data-size aggregation weights (len == dp_size(mesh));
    # None → uniform.  Mirrors FedHyper.client_weights.
    client_weights: Optional[tuple] = None
    # ---- pipeline stages 2/3 (mirror FedHyper) -----------------------
    server_lr: float = 5e-4       # stage-2 global-optimizer lr
    global_steps: int = 5         # stage-2 steps per global_step call
    personal_steps: int = 20      # stage-3 steps per personal_step call
    lam: float = 1e-3             # Eq. 11 Frobenius regularizer (stage 3)
    # Telemetry: when True the round program additionally all_gathers
    # per-client {ce, grad_norm, drift} as replicated metric leaves
    # (repro.obs consumes them host-side — no callbacks enter the jit)
    # and ``FedPipeline.run_pipeline`` emits fed_round/fed_stage events.
    # False (the default) leaves the compiled programs byte-identical to
    # the pre-telemetry ones.
    telemetry: bool = False

    def __post_init__(self):
        """Normalize the fleet vectors at the dataclass boundary
        (lists/ndarrays → plain tuples; mirrors FedHyper).  Length checks
        need the mesh and stay in ``make_fed_pipeline_step``."""
        if self.client_ranks is not None:
            object.__setattr__(self, "client_ranks",
                               tuple(int(r) for r in self.client_ranks))
        if self.client_weights is not None:
            object.__setattr__(self, "client_weights",
                               tuple(float(w) for w in self.client_weights))


def pick_micro_batches(cfg: ArchConfig, per_client_batch: int,
                       seq_len: int, budget_bytes: float = 1.0e9) -> int:
    """Choose grad-accumulation depth so scan-boundary activations
    (n_superblocks × mb × S × D × 2B) stay under budget."""
    n_sb, tail, pattern = cfg.blocks_layout()
    per_mb = (n_sb + 1) * seq_len * cfg.d_model * 2 * len(pattern)
    mb_max = max(1, int(budget_bytes // max(per_mb, 1)))
    micro = max(1, -(-per_client_batch // mb_max))
    while per_client_batch % micro:
        micro += 1
    return min(micro, per_client_batch)


@dataclasses.dataclass(frozen=True)
class FedPipeline:
    """The three jitted shard_map stage programs plus the sequencing
    driver.  Signatures (C = dp_size(mesh); trees as in
    ``make_fed_train_step``):

      round_step(base, adapters, opt_state, step, batch, anchor=None,
                 rng=None)
          → (adapters, opt_state, aggregated, metrics)
      global_step(base, aggregated, adapters, server_batch)
          → (aggregated, adapters, metrics)
      personal_step(base, adapters, batch) → (adapters, metrics)

    ``aggregated`` is the replicated server model (no client axis) — the
    same tree ``FedSim.aggregate`` returns.  ``server_batch`` is a
    replicated {tokens, loss_mask} dict of ``global_steps · B`` rows,
    step-major; ``batch`` trees carry the leading client axis.
    ``anchor`` is the FedProx proximal reference (defaults to the call's
    input adapters — correct for round-only training; the pipeline
    driver threads the post-round rebroadcast through subsequent rounds
    exactly like ``FedSim._round_ref``).  ``rng`` trees thread the
    adapter dropout keys: stage 1 takes ``rng`` in ``round_step``,
    stages 2/3 take ``rng`` as their last argument, with the simulator's
    exact key chains (see make_fed_pipeline_step)."""
    round_step: Callable
    global_step: Callable
    personal_step: Callable
    opt_init: Callable
    method: Any
    # unjitted stage-1 body — make_fed_train_step wraps it so the
    # round-only engine can drop the aggregate output INSIDE its own jit
    # (XLA then DCEs the replicated materialization the pipeline needs)
    round_step_raw: Callable = None
    # telemetry (set from TrainSettings.telemetry): run_pipeline emits
    # fed_round / fed_stage events using the per-client metric leaves the
    # round program all_gathers; comm_bytes_round is the analytic wire
    # cost of one round's collective (same accounting as FedSim)
    telemetry: bool = False
    comm_bytes_round: int = 0
    comm_class: str = "psum"

    def run_pipeline(self, base, adapters, opt_state, step, batch,
                     server_batch, personal_batch, prox_anchor=None,
                     rng=None, global_rng=None, personal_rng=None):
        """One full paper-pipeline iteration: stage-1 round → stage-2
        global optimizer → stage-3 personalization, with the simulator's
        sequencing (``FedSim.run_round`` → ``global_stage`` →
        ``personalize``).  Returns (adapters, opt_state, aggregated,
        prox_anchor, metrics); pass the returned ``prox_anchor`` (and
        ``step + local_steps``) into the next iteration — for prox
        methods the anchor is the post-round rebroadcast, which stages
        2/3 must not disturb (mirrors ``FedSim._round_ref``)."""
        enabled = self.telemetry and obs.enabled()
        t0 = time.perf_counter() if enabled else 0.0
        adapters, opt_state, agg, met1 = self.round_step(
            base, adapters, opt_state, step, batch, prox_anchor, rng)
        if enabled:
            jax.block_until_ready(adapters)
            t1 = time.perf_counter()
        anchor = adapters if self.method.prox else None
        agg, adapters, met2 = self.global_step(base, agg, adapters,
                                               server_batch, global_rng)
        if enabled:
            jax.block_until_ready(adapters)
            t2 = time.perf_counter()
        adapters, met3 = self.personal_step(base, adapters, personal_batch,
                                            personal_rng)
        if enabled:
            jax.block_until_ready(adapters)
            t3 = time.perf_counter()
            self._emit_round_event(step, met1, met2, met3,
                                   (t1 - t0, t2 - t1, t3 - t2, t3 - t0))
        return adapters, opt_state, agg, anchor, {
            "round": met1, "global": met2, "personal": met3}

    def _emit_round_event(self, step, met1, met2, met3, wall):
        """Host epilogue: feed the round program's replicated per-client
        metric leaves into the global telemetry sink."""
        name = self.method.name
        dt_round, dt_global, dt_personal, total = wall
        ce = np.asarray(met1.get("client_ce", []), np.float64).reshape(-1)
        gn = np.asarray(met1.get("client_grad_norm", []),
                        np.float64).reshape(-1)
        drift = np.asarray(met1.get("client_drift", []),
                           np.float64).reshape(-1)
        spread = float(ce.max() - ce.min()) if ce.size else 0.0
        obs.inc("fed/rounds", method=name, engine="pipeline")
        obs.inc("fed/comm_bytes", self.comm_bytes_round, method=name,
                comm=self.comm_class)
        obs.set_gauge("fed/loss_spread", spread, method=name)
        for span, dt in (("fed/round", dt_round),
                         ("fed/stage2_global", dt_global),
                         ("fed/stage3_personalize", dt_personal)):
            obs.observe("span_seconds", dt, span=span, method=name)
        for c in range(ce.size):
            obs.observe("fed/client_ce", float(ce[c]), method=name, client=c)
        obs.event(
            "fed_round", engine="pipeline", method=name, step=int(step),
            clients=int(ce.size),
            ce=[round(float(v), 6) for v in ce],
            grad_norm=[round(float(v), 6) for v in gn],
            drift=[round(float(v), 6) for v in drift],
            loss_spread=round(spread, 6),
            comm_bytes=int(self.comm_bytes_round),
            comm_class=self.comm_class,
            wall={"round": round(dt_round, 6),
                  "global": round(dt_global, 6),
                  "personal": round(dt_personal, 6),
                  "total": round(total, 6)})
        for stage, met, dt in (("global", met2, dt_global),
                               ("personal", met3, dt_personal)):
            obs.event("fed_stage", engine="pipeline", stage=stage,
                      method=name, ce=round(float(np.asarray(met["ce"])), 6),
                      wall=round(dt, 6))


def make_fed_pipeline_step(cfg: ArchConfig, mesh,
                           settings: TrainSettings) -> FedPipeline:
    """Build the three-stage pipeline engine (see FedPipeline).

    base: global param tree (model-sharded, replicated over data axes).
    adapters: leading client axis C = dp_size(mesh), sharded 1-per-shard
    (for a heterogeneous fleet, allocated at the server rank and already
    rank-masked, as FedSim lays them out).
    batch: {"tokens": (C, local_steps·B_c, S), ...} sharded likewise,
    step-major: local step t consumes rows [t·B_c, (t+1)·B_c).
    step: global local-step counter; one round advances it by
    ``settings.local_steps``, so the caller passes step + local_steps to
    the next round (the optimizer's bias-correction schedule matches the
    simulator's per-step counter; stages 2/3 restart their counters at 0
    each call with freshly initialized optimizer state, exactly like
    ``FedSim.global_stage``/``personalize``).

    Adapter dropout: pass ``rng`` into ``round_step`` and each local
    step derives this client's dropout key as
    ``jax.random.split(fold_in(rng, step), C)[client]`` — the exact key
    chain ``FedSim.local_round`` uses, so ``cfg.lora_dropout > 0``
    trains with the same masks in both engines (bit-exact at
    micro_batches=1; micro-batching reshapes the activations, which
    redraws the Bernoulli masks).  With ``rng=None`` the loss sees no
    key and dropout is off regardless of cfg, the previous contract.

    Stages 2/3 take their own ``rng`` (last argument of ``global_step``
    / ``personal_step``) with the simulator's key chains: stage 2 draws
    ``fold_in(rng, step)`` per server step (no client split —
    ``FedSim.global_stage``); stage 3 draws
    ``split(fold_in(rng, 31 + step), C)[client]`` (``FedSim.personalize``
    — the 31 offset decorrelates stage-3 masks from a stage-1 round fed
    the same key).  A stage-2 rng forces the replicated stage-2 path
    (each shard of the sharded path grads a different row slice, which
    would redraw different Bernoulli masks than the full-batch oracle).
    """
    if cfg.use_fused_dora:
        raise ValueError(
            "use_fused_dora is forward/serving-only (the Pallas kernel "
            "defines no VJP); the train step requires the jnp adapter path")
    daxes = data_axes(mesh)
    dp = dp_size(mesh)
    micro = settings.micro_batches
    is_moe = cfg.n_experts > 0
    method = get_method(settings.method)
    keep_rx = re.compile(method.keep_local) if method.keep_local else None
    # the method's cross-client collective — resolving it here (not at
    # step time) means an aggregator with no shard_map form fails fast,
    # never silently training with different math than the simulator
    collective = fedagg.collective_form(method)
    # leaves the host aggregate zeroes in the server model (fedalt's
    # individual pair): the collective meaned them, the stage-2 server
    # model must not see that mean
    zrx = fedagg.aggregate_zero_rx(method)
    zero_rx = re.compile(zrx) if zrx else None
    prox_mu = settings.prox_mu if method.prox else 0.0
    lam = settings.lam if method.personal_reg is not None else 0.0

    # ---- fleet layout: ranks, coverage masks, aggregation weights ------
    het = settings.client_ranks is not None
    if het:
        if not method.het_ranks:
            raise ValueError(
                f"method {method.name!r} has no rank dimension "
                "(het_ranks=False); client_ranks requires a LoRA-family "
                "method")
        alloc_rank = peft.fleet_alloc_rank(settings.client_ranks, dp,
                                           settings.server_rank)
        ranks = jnp.asarray(settings.client_ranks, jnp.int32)
    else:
        alloc_rank = cfg.lora_rank
        ranks = jnp.full((dp,), alloc_rank, jnp.int32)
    if settings.client_weights is not None:
        peft.validate_client_weights(settings.client_weights, dp)
        weight_c = jnp.asarray(settings.client_weights, jnp.float32)
    else:
        weight_c = jnp.ones((dp,), jnp.float32)

    # abstract adapter tree (drives the per-stage trainable masks, the
    # shard specs, and the per-client coverage masks); heterogeneous
    # fleets allocate at the server rank, exactly as FedSim does
    mk = (partial(method.make_adapter, rank=alloc_rank) if het
          else method.make_adapter)
    abs_ad = jax.eval_shape(
        lambda: mk(abstract_base(cfg), cfg, jax.random.PRNGKey(0)))
    # per-stage optimizers over the per-stage masks — one adamw per
    # stage, exactly the simulator's opt / opt_global / opt_local
    opt = masked(adamw(settings.lr),
                 method.stage_mask(abs_ad, settings.stage))
    opt_g = masked(adamw(settings.server_lr), method.stage_global_mask(abs_ad))
    opt_l = masked(adamw(settings.lr), method.stage_local_mask(abs_ad))
    reg_mask = method.personal_reg(abs_ad) if method.personal_reg else None
    # per-client coverage masks over the rank axis of every leaf; on a
    # uniform fleet these are all-ones (and unused outside the coverage
    # collective), so the uniform program pays nothing
    covers_c = peft.client_rank_masks(abs_ad, ranks)

    ad_spec = shd.client_specs(abs_ad, mesh)
    ost_abs = jax.eval_shape(opt.init, abs_ad)
    ost_spec = shd.client_specs(ost_abs, mesh)
    cov_spec = shd.client_specs(covers_c, mesh)
    w_spec = shd.client_vector_spec(mesh)   # weights / participation /
                                            # staleness / update scales
    # the aggregated server model carries no client axis: replicated in,
    # replicated out (stages 1 → 2 hand it off in this layout)
    agg_spec = shd.replicated_specs(abs_ad)
    mesh_tag = ("manual", mesh.shape["data"]) if is_moe else None

    def batch_spec_of(batch):
        return {k: P(shd.client_axis(mesh)) for k in batch}

    # ---- shared per-shard training scan --------------------------------
    # One loop body for all three stages: T optimizer steps, each
    # micro-batched via lax.scan (one HLO body regardless of depth — an
    # unrolled loop made 88-layer compiles explode), forward-only carry
    # (grads), LoRA grads accumulated in f32.
    def train_scan(base, ad, ost, step0, batch, *, T, stage_opt, cover,
                   stage_lam, stage_prox, anchor, stage, rng=None,
                   rng_fold=0, rng_split=True, grad_axes=None):
        def loss_fn(ad_, mb, rng_):
            params = pt.merge_trees(base, ad_)
            loss, met = M.loss_and_metrics(params, mb, cfg, rng=rng_,
                                           mesh=mesh_tag,
                                           remat=settings.remat)
            if stage_lam:
                # Eq. 11 ½λ‖·‖²_F over the method's personal_reg leaves
                reg = sum(jnp.sum(jnp.square(x)) for m, x in zip(
                    jax.tree.leaves(reg_mask), jax.tree.leaves(ad_)) if m)
                loss = loss + 0.5 * stage_lam * reg
            if stage_prox:
                d = pt.tree_sub(ad_, anchor)
                loss = loss + 0.5 * stage_prox * pt.tree_dot(d, d)
            return loss, met

        B_c = batch["tokens"].shape[0]
        shards = dp if grad_axes is not None else 1
        if B_c % (T * micro * shards):
            raise ValueError(
                f"{stage} batch of {B_c} rows is not divisible by steps "
                f"({T}) x micro_batches ({micro})"
                + (f" x shards ({shards})" if shards > 1 else ""))
        mb_sz = B_c // (T * micro * shards)
        if grad_axes is not None:
            # data-parallel stage: each shard takes its slice of every
            # micro-batch; the token-weighted psum below recovers the
            # full-batch gradient
            cidx = fedagg.client_index(grad_axes)
            sbatch = {k: v.reshape((T, micro, shards, mb_sz)
                                   + v.shape[1:])[:, :, cidx]
                      for k, v in batch.items()}
        else:
            sbatch = {k: v.reshape((T, micro, mb_sz) + v.shape[1:])
                      for k, v in batch.items()}

        def local_step(carry, sb):
            ad_, ost_, step = carry
            # per-step dropout key: the simulator's chains —
            # split(fold_in(rng, fold + step), C)[client] on per-client
            # stages (fold 0 for the round, 31 for personalization), and
            # the unsplit fold_in(rng, step) on the replicated stage-2
            # server model — so both engines draw the same masks for the
            # same step/client
            if rng is None:
                step_rng = None
            else:
                k = jax.random.fold_in(rng, rng_fold + step)
                step_rng = (jax.random.split(k, dp)
                            [fedagg.client_index(daxes)]
                            if rng_split else k)
            g0 = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), ad_)

            def acc_body(carry_g, mb):
                g_acc, n_acc = carry_g
                (_, met), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    ad_, mb, step_rng)
                # grad weight: the CE denominator (n_tok) when sharded,
                # so uneven loss masks still reduce to the full-batch
                # gradient; 1 on the replicated/per-client path
                n = (met["n_tok"] if grad_axes is not None
                     else jnp.ones((), jnp.float32))
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32) * n, g_acc, g)
                return (g_acc, n_acc + n), met

            (g_acc, n_tot), mets = jax.lax.scan(
                acc_body, (g0, jnp.zeros((), jnp.float32)), sb)
            if grad_axes is not None:
                n_tot = jax.lax.psum(n_tot, grad_axes)
                g_acc = jax.tree.map(
                    lambda x: jax.lax.psum(x, grad_axes) / n_tot, g_acc)
            else:
                g_acc = jax.tree.map(lambda x: x / micro, g_acc)
            # pre-clip gradient norm rides the metrics unconditionally
            # (not telemetry-gated) so the compiled program is identical
            # with obs on and off; equals the simulator's per-client
            # grad_norm at micro_batches=1
            gnorm = pt.global_norm(g_acc)
            g_acc = clip_by_global_norm(g_acc, settings.clip)
            upd, ost_ = stage_opt.update(g_acc, ost_, ad_, step)
            if cover is not None:
                # heterogeneous fleet: zero the update rows above this
                # client's rank (adapters are allocated at the server rank)
                upd = jax.tree.map(jnp.multiply, upd, cover)
            ad_ = apply_updates(ad_, upd)
            met = jax.tree.map(lambda x: jnp.sum(x, axis=0) / micro, mets)
            met = dict(met, grad_norm=gnorm)
            return (ad_, ost_, step + 1), met

        (ad, ost, _), mets = jax.lax.scan(local_step, (ad, ost, step0),
                                          sbatch)
        return ad, ost, jax.tree.map(lambda m: m[-1], mets)

    # ---- stage 1: the federated round ----------------------------------
    def round_body(base, adapters, opt_state, step0, batch, anchor, weight,
                   part, stale, scale, covers, rng, *, use_rng, use_faults):
        # inside the manual region: one client per shard
        adapters = jax.tree.map(lambda x: x[0], adapters)   # drop C axis
        opt_state = jax.tree.map(lambda x: x[0], opt_state)
        batch = {k: v[0] for k, v in batch.items()}
        anchor = jax.tree.map(lambda x: x[0], anchor)
        w = weight[0]
        cover = jax.tree.map(lambda x: x[0], covers)
        if use_faults:
            ad0, ost0 = adapters, opt_state     # round-start snapshot
        adapters, opt_state, mets = train_scan(
            base, adapters, opt_state, step0, batch,
            T=settings.local_steps, stage_opt=opt,
            cover=cover if het else None, stage_lam=0.0,
            stage_prox=prox_mu, anchor=anchor, stage="round",
            rng=rng if use_rng else None)
        if use_faults:
            # fault layer — statically gated (``old + 1·(new−old) ≠ new``
            # in f32, so the honest path must never run these), and when
            # active BOTH engines apply the identical expressions to ALL
            # shards (identity values for honest clients) so parity with
            # FedSim.run_cohort_round holds bit for bit:
            #   scale  corrupted-update adversaries inflate this shard's
            #          round update;
            #   part   a 0-participation shard reverts adapters AND
            #          optimizer state to round start (its mid-round work
            #          is lost) and contributes weight 0 below.
            p, s = part[0], scale[0]
            adapters = jax.tree.map(
                lambda new, old: old + s * (new - old), adapters, ad0)
            adapters = jax.tree.map(
                lambda new, old: jnp.where(p > 0, new, old), adapters, ad0)
            opt_state = jax.tree.map(
                lambda new, old: jnp.where(p > 0, new, old), opt_state, ost0)
            w = w * p

        # the method's collective aggregation: the only cross-client (and
        # only cross-pod) traffic.  Keep-local leaves (the paper's
        # personal ΔB_M, FedALT's individual pair) are restored from this
        # shard's own post-round values — personalization never crosses
        # shards.  ``step`` feeds the COMPRESSED codecs' rounding keys:
        # the post-round counter, = FedSim._step at FedSim.aggregate time.
        # ``staleness`` feeds the STALENESS (FedBuff) discount; other
        # kinds ignore it.
        agg = collective(adapters, axes=daxes, weight=w, cover=cover,
                         step=step0 + settings.local_steps,
                         staleness=stale[0])
        if settings.telemetry:
            # per-client aggregate drift ‖client − aggregate‖ over the
            # shared leaves, pre-rebroadcast (the simulator's
            # _client_drift) — a per-shard scalar, all_gathered below
            sq = jnp.zeros((), jnp.float32)
            for (p, x), y, m in zip(
                    jax.tree_util.tree_leaves_with_path(adapters),
                    jax.tree.leaves(agg), jax.tree.leaves(cover)):
                if keep_rx is not None and keep_rx.search(pt.path_str(p)):
                    continue
                d = x - y
                if het:
                    d = d * m
                sq = sq + jnp.sum(jnp.square(d))
            drift = jnp.sqrt(sq)
        if zero_rx is not None:
            agg = pt.tree_map_with_path(
                lambda p, x: jnp.zeros_like(x) if zero_rx.search(p) else x,
                agg)
        out = fedagg.client_rebroadcast(agg, adapters, keep_rx,
                                        cover if het else None)
        met_last = jax.tree.map(lambda m: jax.lax.pmean(m, daxes), mets)
        if settings.telemetry:
            # per-client metric leaves, replicated by the all_gather so
            # they satisfy the replicated out_spec — the host pulls them
            # after the jit returns (no callbacks inside the program)
            met_last = dict(
                met_last,
                client_ce=jax.lax.all_gather(mets["ce"], daxes),
                client_grad_norm=jax.lax.all_gather(mets["grad_norm"],
                                                    daxes),
                client_drift=jax.lax.all_gather(drift, daxes))
        return (jax.tree.map(lambda x: x[None], out),
                jax.tree.map(lambda x: x[None], opt_state), agg, met_last)

    def round_step(base, adapters, opt_state, step, batch, anchor=None,
                   rng=None, weights=None, participation=None,
                   staleness=None, update_scale=None):
        if anchor is None:
            # round-only training: the proximal reference is the call's
            # input adapters (a round ends in rebroadcast, so the next
            # round's input IS the last rebroadcast)
            anchor = adapters
        use_rng = rng is not None
        if not use_rng:
            rng = jnp.zeros((2,), jnp.uint32)   # placeholder, never consumed
        # cohort/fault inputs (mirror FedSim.run_cohort_round): all (C,)
        # vectors riding w_spec.  ``use_faults`` is a static gate — with
        # every argument None the fault transforms never enter the
        # program and the placeholder vectors are dead inputs, so the
        # honest round compiles to the identical math as before.
        use_faults = participation is not None or update_scale is not None
        w_c = weight_c if weights is None else jnp.asarray(
            weights, jnp.float32)
        part_c = (jnp.ones((dp,), jnp.float32) if participation is None
                  else jnp.asarray(participation, jnp.float32))
        stale_c = (jnp.zeros((dp,), jnp.float32) if staleness is None
                   else jnp.asarray(staleness, jnp.float32))
        scale_c = (jnp.ones((dp,), jnp.float32) if update_scale is None
                   else jnp.asarray(update_scale, jnp.float32))
        body = jax.shard_map(
            partial(round_body, use_rng=use_rng, use_faults=use_faults),
            mesh=mesh,
            in_specs=(base_manual_specs(base, cfg), ad_spec, ost_spec, P(),
                      batch_spec_of(batch), ad_spec, w_spec, w_spec,
                      w_spec, w_spec, cov_spec, P()),
            out_specs=(ad_spec, ost_spec, agg_spec, P()),
            axis_names=set(daxes), check_vma=False,
        )
        return body(base, adapters, opt_state, step, batch, anchor,
                    w_c, part_c, stale_c, scale_c, covers_c, rng)

    # ---- stage 2: the global optimizer (replicated server model) -------
    def global_body(base, agg, adapters, sbatch, covers, rng, *, use_rng):
        own = jax.tree.map(lambda x: x[0], adapters)
        cover = jax.tree.map(lambda x: x[0], covers)
        # the server model trains at the full allocated rank with no rank
        # mask and a fresh zero-state optimizer (FedSim.global_stage).
        # agg/sbatch come in replicated; when the server batch divides
        # evenly over the client axis each shard grads its own slice of
        # every micro-batch and the token-weighted psum inside train_scan
        # recovers the full-batch gradient (dp× fewer backbone FLOPs per
        # shard, updates stay replicated); otherwise every shard runs the
        # identical replicated math.  Dropout rng forces the replicated
        # path: sharded rows would redraw different Bernoulli masks than
        # the full-batch oracle (mask shape follows the activations).
        B_s = sbatch["tokens"].shape[0]
        shard2 = (dp > 1 and not use_rng
                  and B_s % (settings.global_steps * micro * dp) == 0)
        ost = opt_g.init(agg)
        agg, _, mets = train_scan(
            base, agg, ost, jnp.zeros((), jnp.int32), sbatch,
            T=settings.global_steps, stage_opt=opt_g, cover=None,
            stage_lam=0.0, stage_prox=0.0, anchor=None, stage="global",
            rng=rng if use_rng else None, rng_split=False,
            grad_axes=daxes if shard2 else None)
        if shard2:
            # per-shard metrics differ (different rows) — mean them so
            # the replicated out_spec holds
            mets = jax.tree.map(lambda m: jax.lax.pmean(m, daxes), mets)
        out = fedagg.client_rebroadcast(agg, own, keep_rx,
                                        cover if het else None)
        return agg, jax.tree.map(lambda x: x[None], out), mets

    def global_step(base, aggregated, adapters, server_batch, rng=None):
        use_rng = rng is not None
        if not use_rng:
            rng = jnp.zeros((2,), jnp.uint32)   # placeholder, never consumed
        body = jax.shard_map(
            partial(global_body, use_rng=use_rng),
            mesh=mesh,
            in_specs=(base_manual_specs(base, cfg), agg_spec, ad_spec, P(),
                      cov_spec, P()),
            out_specs=(agg_spec, ad_spec, P()),
            axis_names=set(daxes), check_vma=False,
        )
        return body(base, aggregated, adapters, server_batch, covers_c, rng)

    # ---- stage 3: per-client personalization (no collective) -----------
    def personal_body(base, adapters, batch, covers, rng, *, use_rng):
        ad = jax.tree.map(lambda x: x[0], adapters)
        batch = {k: v[0] for k, v in batch.items()}
        cover = jax.tree.map(lambda x: x[0], covers)
        ost = opt_l.init(ad)
        ad, _, mets = train_scan(
            base, ad, ost, jnp.zeros((), jnp.int32), batch,
            T=settings.personal_steps, stage_opt=opt_l,
            cover=cover if het else None, stage_lam=lam, stage_prox=0.0,
            anchor=None, stage="personal",
            rng=rng if use_rng else None, rng_fold=31)
        met_last = jax.tree.map(lambda m: jax.lax.pmean(m, daxes), mets)
        return jax.tree.map(lambda x: x[None], ad), met_last

    def personal_step(base, adapters, batch, rng=None):
        use_rng = rng is not None
        if not use_rng:
            rng = jnp.zeros((2,), jnp.uint32)   # placeholder, never consumed
        body = jax.shard_map(
            partial(personal_body, use_rng=use_rng),
            mesh=mesh,
            in_specs=(base_manual_specs(base, cfg), ad_spec,
                      batch_spec_of(batch), cov_spec, P()),
            out_specs=(ad_spec, P()),
            axis_names=set(daxes), check_vma=False,
        )
        return body(base, adapters, batch, covers_c, rng)

    def opt_init(adapters_c):
        return jax.vmap(opt.init)(adapters_c)

    # analytic wire cost of one round's collective — FedSim.aggregate's
    # exact billing, evaluated once at build time on the abstract adapter
    # template (heterogeneous fleets bill each client at its own rank)
    comm_cls = fedagg.comm_class(method)
    topk_ratio = getattr(collective, "topk_ratio", 0.01)
    if het:
        comm_bytes = sum(
            fedagg.comm_bytes_per_round(
                abs_ad, exclude_rx=method.keep_local, rank=int(r),
                comm=comm_cls, n_clients=dp, topk_ratio=topk_ratio)
            for r in settings.client_ranks)
    else:
        comm_bytes = dp * fedagg.comm_bytes_per_round(
            abs_ad, exclude_rx=method.keep_local, comm=comm_cls,
            n_clients=dp, topk_ratio=topk_ratio)

    return FedPipeline(round_step=jax.jit(round_step),
                       global_step=jax.jit(global_step),
                       personal_step=jax.jit(personal_step),
                       opt_init=opt_init, method=method,
                       round_step_raw=round_step,
                       telemetry=settings.telemetry,
                       comm_bytes_round=int(comm_bytes),
                       comm_class=comm_cls)


def make_fed_train_step(cfg: ArchConfig, mesh, settings: TrainSettings):
    """Returns (train_step, opt_init).  train_step signature:

        train_step(base, adapters, opt_state, step, batch, rng=None)
            → (adapters, opt_state, metrics)

    One train_step call is one federated ROUND: ``settings.local_steps``
    optimizer steps per client, then one aggregation — the stage-1
    program of ``make_fed_pipeline_step`` with the aggregate output
    dropped.  Every method in the core.methods registry trains with the
    same math here as in the single-process simulator (fed/simulate.py).
    """
    pipe = make_fed_pipeline_step(cfg, mesh, settings)

    def train_step(base, adapters, opt_state, step, batch, rng=None,
                   weights=None, participation=None, staleness=None,
                   update_scale=None):
        # the aggregate is dropped inside this jit so round-only training
        # never pays for materializing the pipeline's replicated output;
        # the cohort/fault vectors pass straight through to the stage-1
        # body (see round_step)
        adapters, opt_state, _, met = pipe.round_step_raw(
            base, adapters, opt_state, step, batch, rng=rng,
            weights=weights, participation=participation,
            staleness=staleness, update_scale=update_scale)
        return adapters, opt_state, met

    return jax.jit(train_step), pipe.opt_init


def abstract_base(cfg: ArchConfig):
    return jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))


def base_manual_specs(base, cfg: ArchConfig):
    """Manual specs for the base tree over the DATA axes only: MoE expert
    slots are expert-parallel (manual over 'data'); everything else is
    replicated across clients ('model'-axis sharding stays auto)."""
    def fn(path, x):
        if cfg.n_experts and re.search(r"moe/experts/", path):
            # (n_sb, E_slots, D, F) — E_slots manual over 'data'
            lead = [None] * (len(x.shape) - 3)
            return P(*lead, "data", None, None)
        return P(*([None] * len(x.shape)))

    return pt.tree_map_with_path(fn, base)
