"""Multi-device tests (8 host devices via subprocess — XLA locks device
count at first init, so these run in their own interpreter).

Client meshes (make_client_mesh) run the fully manual ``jax.shard_map``
region; meshes with a tensor-parallel 'model' axis run it partial-auto
(manual data axes, auto 'model').

The collective-parity sweeps are the acceptance gate for the
distributed aggregation engine: for every method in the registry, one
production shard_map round must produce the same client adapters as
``FedSim.run_round`` (mixed-rank and weighted fleets included).
"""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.dist

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(snippet: str, timeout=900):
    # the child runs on 8 virtual CPU devices and never on an accelerator:
    # a chip belongs to one process, and the parent may hold it
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu", PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", snippet], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


# ---------------------------------------------------------------------------
# collective-parity sweep: shard_map round == FedSim.run_round
# ---------------------------------------------------------------------------

# Shared harness, exec'd inside the 8-device subprocess.  ``run_case``
# drives ROUNDS production train_step calls against the FedSim oracle on
# identical initial state/batches and compares final client adapters in
# f32 (the two paths fuse differently, so ~ulp drift accumulates; the
# exact method is compared on the product A·B — truncated-SVD *factors*
# are sign-sensitive to that drift, the aggregate itself is not).
PARITY_HARNESS = r"""
import numpy as np
import jax, jax.numpy as jnp
from repro.launch.mesh import make_client_mesh
from repro.launch.train import make_fed_train_step, TrainSettings
from repro.fed.simulate import FedHyper, FedSim
from repro.core.methods import available_methods, get_method
from repro.models.config import ArchConfig
from repro.utils import pytree as pt

C, T, B, S, ROUNDS = 4, 2, 2, 16, 2
cfg = ArchConfig(name="t", family="dense", n_layers=1, d_model=32, n_heads=2,
                 n_kv_heads=1, d_ff=64, vocab_size=64, dtype="float32",
                 lora_rank=4, lora_dropout=0.0)
mesh = make_client_mesh(C)
rng = np.random.default_rng(0)


def reseed(name):
    # every case draws from its own name-keyed data stream: sweep
    # results must not depend on registry order/size (a method added
    # earlier in the alphabet would otherwise shift every later case's
    # batches, and the ~ulp parity tolerances are marginal enough for
    # that to matter)
    import zlib
    global rng
    rng = np.random.default_rng(zlib.crc32(name.encode()))


def make_batches():
    return [{"tokens": jnp.asarray(
                 rng.integers(5, cfg.vocab_size, size=(C, B, S)), jnp.int32),
             "loss_mask": jnp.ones((C, B, S), jnp.float32)}
            for _ in range(T)]


def compare(name, prod, ref):
    prod = dict(zip(pt.tree_paths(prod), map(np.asarray, jax.tree.leaves(prod))))
    ref = dict(zip(pt.tree_paths(ref), map(np.asarray, jax.tree.leaves(ref))))
    assert set(prod) == set(ref), name
    if name == "lora_exact":
        for pref in sorted(p.rsplit("/", 1)[0] for p in prod
                           if p.endswith("lora_A")):
            pa, pb = pref + "/lora_A", pref + "/lora_B"
            np.testing.assert_allclose(
                np.einsum("...ir,...ro->...io", prod.pop(pa), prod.pop(pb)),
                np.einsum("...ir,...ro->...io", ref.pop(pa), ref.pop(pb)),
                rtol=5e-4, atol=5e-5, err_msg=f"{name}:{pref}")
    for p in sorted(prod):
        if name == "lora_fedavg_q8":
            # the engines agree to ~ulp, and a stochastic-rounding draw
            # whose fractional part sits within that drift of its uniform
            # sample can legitimately flip between them — allow isolated
            # diffs up to one SR bin, but still demand near-total strict
            # agreement: a broken rounding-key chain flips ~half the
            # draws on every leaf and fails the 99% gate
            bin_ = max(np.abs(prod[p]).max(), np.abs(ref[p]).max()) / 127.0
            np.testing.assert_allclose(prod[p], ref[p], rtol=2e-4,
                                       atol=2 * bin_ + 2e-5,
                                       err_msg=f"{name}:{p}")
            close = np.isclose(prod[p], ref[p], rtol=2e-4, atol=2e-5)
            assert close.mean() > 0.99, (name, p, float(close.mean()))
        elif name == "adapter":
            # Houlsby adapters amplify rounding: nudging the oracle's own
            # initial adapters by one ulp moves one adapter_down element
            # by 3.0e-5 after two pipeline iterations, the same size as
            # the engine-vs-oracle gap (3.4e-5).  That is reduction order,
            # not engine math; atol is twice the oracle's own sensitivity
            np.testing.assert_allclose(prod[p], ref[p], rtol=2e-4, atol=6e-5,
                                       err_msg=f"{name}:{p}")
        else:
            np.testing.assert_allclose(prod[p], ref[p], rtol=2e-4, atol=2e-5,
                                       err_msg=f"{name}:{p}")


def run_case(name, ranks=None, weights=None, prox_mu=0.0):
    reseed(name)
    hp = FedHyper(method=name, n_clients=C, local_steps=T, batch=B,
                  seq_len=S, lr=1e-2, prox_mu=prox_mu, client_ranks=ranks,
                  client_weights=weights)
    sim = FedSim(cfg, hp)
    st = TrainSettings(lr=hp.lr, micro_batches=1, clip=hp.clip, remat=False,
                       method=name, local_steps=T, prox_mu=prox_mu,
                       client_ranks=ranks, client_weights=weights)
    step_fn, _ = make_fed_train_step(cfg, mesh, st)
    na, no = sim.client_adapters, sim.opt_state
    step0 = jnp.zeros((), jnp.int32)
    for r in range(ROUNDS):
        batches = make_batches()
        big = {k: jnp.concatenate([b[k] for b in batches], axis=1)
               for k in batches[0]}
        # production first: FedSim.local_round donates its buffers, and
        # round 1 shares them with the production call
        na, no, met = step_fn(sim.base, na, no, step0, big)
        sim.run_round(batches, jax.random.PRNGKey(r))
        step0 = step0 + T
        assert np.isfinite(float(met["ce"])), (name, r)
    compare(name, na, sim.client_adapters)
    print("OK", name, "ranks" if ranks else "", "weights" if weights else "")


# ---- full three-stage pipeline: shard_map == FedSim stage by stage ----
TG, TP = 2, 2          # stage-2 / stage-3 steps per pipeline iteration


def make_server_batches(n):
    return [{"tokens": jnp.asarray(
                 rng.integers(5, cfg.vocab_size, size=(B, S)), jnp.int32),
             "loss_mask": jnp.ones((B, S), jnp.float32)}
            for _ in range(n)]


def flat(bs, axis):
    return {k: jnp.concatenate([b[k] for b in bs], axis=axis)
            for k in bs[0]}


def keep_leaves(method, tree):
    import re
    if not method.keep_local:
        return {}
    rx = re.compile(method.keep_local)
    return {p: np.asarray(x) for p, x in
            zip(pt.tree_paths(tree), jax.tree.leaves(tree)) if rx.search(p)}


def run_pipeline_case(name, ranks=None, weights=None, prox_mu=0.0):
    from repro.launch.train import make_fed_pipeline_step
    reseed(name)
    method = get_method(name)
    hp = FedHyper(method=name, n_clients=C, local_steps=T, batch=B,
                  seq_len=S, lr=1e-2, server_lr=5e-3, global_steps=TG,
                  personal_steps=TP, lam=1e-2, prox_mu=prox_mu,
                  client_ranks=ranks, client_weights=weights)
    sim = FedSim(cfg, hp)
    st = TrainSettings(lr=hp.lr, micro_batches=1, clip=hp.clip, remat=False,
                       method=name, local_steps=T, prox_mu=prox_mu,
                       client_ranks=ranks, client_weights=weights,
                       server_lr=hp.server_lr, global_steps=TG,
                       personal_steps=TP, lam=hp.lam)
    pipe = make_fed_pipeline_step(cfg, mesh, st)
    na, no = sim.client_adapters, sim.opt_state
    step0 = jnp.zeros((), jnp.int32)
    anchor = None
    agg_p = None
    for r in range(ROUNDS):
        cb, sb = make_batches(), make_server_batches(TG)
        pb = (make_batches() + make_batches())[:TP]
        na, no, agg_p, met = pipe.round_step(
            sim.base, na, no, step0, flat(cb, 1), anchor)
        anchor = na if method.prox else None
        kept = keep_leaves(method, na)
        agg_p, na, _ = pipe.global_step(sim.base, agg_p, na, flat(sb, 0))
        # keep-local leaves must pass through stage 2 untouched
        for p, want in kept.items():
            node = na
            for k in p.split("/"):
                node = node[k]
            np.testing.assert_array_equal(np.asarray(node), want,
                                          err_msg=f"{name}:stage2-kept:{p}")
        na, _ = pipe.personal_step(sim.base, na, flat(pb, 1))

        sim.local_round(cb, jax.random.PRNGKey(r))
        agg_s = sim.aggregate()
        agg_s = sim.global_stage(agg_s, sb, jax.random.PRNGKey(100 + r))
        sim.personalize(pb, jax.random.PRNGKey(200 + r))
        step0 = step0 + T
        assert np.isfinite(float(met["ce"])), (name, r)
    compare(name, na, sim.client_adapters)
    compare(name, agg_p, agg_s)
    print("PIPE-OK", name, "ranks" if ranks else "",
          "weights" if weights else "")
"""


@pytest.mark.slow
def test_collective_parity_all_methods():
    """Every registry method: production shard_map round == FedSim round
    on a uniform fleet (2 rounds, so optimizer state and the FedProx
    anchor survive the round boundary)."""
    out = _run(PARITY_HARNESS + r"""
names = available_methods()
for name in names:
    m = get_method(name)
    run_case(name, prox_mu=0.05 if m.prox else 0.0)
print("SWEPT", len(names))
""")
    assert "SWEPT 14" in out, out


@pytest.mark.slow
def test_collective_parity_het_and_weighted_fleets():
    """Mixed-rank fleets (rank-aware aggregation family + the paper
    pipeline + FedALT) and data-size-weighted clients run identically on
    the production path."""
    out = _run(PARITY_HARNESS + r"""
run_case("fedlora_opt", ranks=(1, 2, 3, 4))
run_case("lora_zeropad", ranks=(1, 2, 3, 4))
run_case("lora_replication", ranks=(1, 2, 3, 4), weights=(1., 2., 3., 4.))
run_case("lora_exact", ranks=(1, 2, 3, 4), weights=(4., 3., 2., 1.))
run_case("fedalt", ranks=(2, 4, 4, 2))
run_case("lora", weights=(1., 2., 3., 4.))
run_case("lora_fedavg_q8", ranks=(1, 2, 3, 4), weights=(1., 2., 3., 4.))
print("HET-OK")
""")
    assert "HET-OK" in out, out


@pytest.mark.slow
def test_round_parity_with_adapter_dropout():
    """cfg.lora_dropout > 0 on the production path: threading ``rng``
    into the round draws the simulator's exact per-step/per-client
    dropout keys (micro_batches=1), so the round parity gate extends to
    dropout-on training — including over the compressed q8 uplink."""
    out = _run(PARITY_HARNESS + r"""
import dataclasses as _dc
cfg = _dc.replace(cfg, lora_dropout=0.3)


def run_dropout_case(name):
    hp = FedHyper(method=name, n_clients=C, local_steps=T, batch=B,
                  seq_len=S, lr=1e-2)
    sim = FedSim(cfg, hp)
    st = TrainSettings(lr=hp.lr, micro_batches=1, clip=hp.clip, remat=False,
                       method=name, local_steps=T)
    step_fn, _ = make_fed_train_step(cfg, mesh, st)
    na, no = sim.client_adapters, sim.opt_state
    step0 = jnp.zeros((), jnp.int32)
    for r in range(ROUNDS):
        batches = make_batches()
        big = {k: jnp.concatenate([b[k] for b in batches], axis=1)
               for k in batches[0]}
        na, no, met = step_fn(sim.base, na, no, step0, big,
                              rng=jax.random.PRNGKey(r))
        sim.run_round(batches, jax.random.PRNGKey(r))
        step0 = step0 + T
        assert np.isfinite(float(met["ce"])), (name, r)
    compare(name, na, sim.client_adapters)
    print("DROPOUT-OK", name)


run_dropout_case("lora")
run_dropout_case("lora_fedavg_q8")
""")
    assert out.count("DROPOUT-OK") == 2, out


@pytest.mark.slow
def test_pipeline_parity_with_dropout():
    """cfg.lora_dropout > 0 through ALL THREE pipeline stages: stage 1
    takes ``rng`` in round_step, stages 2/3 take their own rng (the
    simulator's ``global_stage`` / ``personalize`` key chains —
    ``fold_in(rng, step)`` unsplit and ``split(fold_in(rng, 31+step),
    C)[client]`` respectively), so the full-pipeline parity gate extends
    to dropout-on training.  A stage-2 rng also forces the replicated
    stage-2 path (sharded rows would redraw different masks)."""
    out = _run(PARITY_HARNESS + r"""
import dataclasses as _dc
cfg = _dc.replace(cfg, lora_dropout=0.3)


def run_pipeline_dropout_case(name):
    from repro.launch.train import make_fed_pipeline_step
    method = get_method(name)
    hp = FedHyper(method=name, n_clients=C, local_steps=T, batch=B,
                  seq_len=S, lr=1e-2, server_lr=5e-3, global_steps=TG,
                  personal_steps=TP, lam=1e-2)
    sim = FedSim(cfg, hp)
    st = TrainSettings(lr=hp.lr, micro_batches=1, clip=hp.clip, remat=False,
                       method=name, local_steps=T, server_lr=hp.server_lr,
                       global_steps=TG, personal_steps=TP, lam=hp.lam)
    pipe = make_fed_pipeline_step(cfg, mesh, st)
    na, no = sim.client_adapters, sim.opt_state
    step0 = jnp.zeros((), jnp.int32)
    anchor = None
    for r in range(ROUNDS):
        cb, sb = make_batches(), make_server_batches(TG)
        pb = (make_batches() + make_batches())[:TP]
        na, no, agg_p, met = pipe.round_step(
            sim.base, na, no, step0, flat(cb, 1), anchor,
            jax.random.PRNGKey(r))
        anchor = na if method.prox else None
        agg_p, na, _ = pipe.global_step(sim.base, agg_p, na, flat(sb, 0),
                                        jax.random.PRNGKey(100 + r))
        na, _ = pipe.personal_step(sim.base, na, flat(pb, 1),
                                   jax.random.PRNGKey(200 + r))

        sim.local_round(cb, jax.random.PRNGKey(r))
        agg_s = sim.aggregate()
        agg_s = sim.global_stage(agg_s, sb, jax.random.PRNGKey(100 + r))
        sim.personalize(pb, jax.random.PRNGKey(200 + r))
        step0 = step0 + T
        assert np.isfinite(float(met["ce"])), (name, r)
    compare(name, na, sim.client_adapters)
    compare(name, agg_p, agg_s)
    print("PIPE-DROPOUT-OK", name)


run_pipeline_dropout_case("lora")
run_pipeline_dropout_case("fedlora_opt")
""", timeout=1800)
    assert out.count("PIPE-DROPOUT-OK") == 2, out


@pytest.mark.slow
def test_pipeline_stage2_sharded_server_batch():
    """When the replicated server batch divides evenly over the client
    axis, stage 2 shards rows across clients and recovers the full-batch
    gradient with a token-weighted psum — the pipeline must still match
    the simulator's replicated stage-2 math (dp× fewer FLOPs is a pure
    layout change)."""
    out = _run(PARITY_HARNESS + r"""
# widen the server batches so TG·B_srv (= 8) divides over C=4 shards
# and the sharded stage-2 path engages (the default B=2 batches leave
# it on the replicated fallback)
def make_server_batches(n):
    return [{"tokens": jnp.asarray(
                 rng.integers(5, cfg.vocab_size, size=(4, S)), jnp.int32),
             "loss_mask": jnp.ones((4, S), jnp.float32)}
            for _ in range(n)]


run_pipeline_case("lora")
run_pipeline_case("fedlora_opt")
print("STAGE2-SHARD-OK")
""", timeout=1800)
    assert "STAGE2-SHARD-OK" in out, out


@pytest.mark.slow
def test_pipeline_parity_all_methods():
    """The full three-stage pipeline (stage-1 round → stage-2 global
    optimizer on replicated server batches → stage-3 per-client
    personalization) matches the FedSim sequence ``run_round →
    global_stage → personalize`` for every registry method over 2 full
    iterations — final client adapters AND the aggregated server model;
    keep-local leaves are verified untouched by stage 2."""
    out = _run(PARITY_HARNESS + r"""
names = available_methods()
for name in names:
    m = get_method(name)
    run_pipeline_case(name, prox_mu=0.05 if m.prox else 0.0)
print("PIPE-SWEPT", len(names))
""", timeout=1800)
    assert "PIPE-SWEPT 14" in out, out


@pytest.mark.slow
def test_pipeline_parity_het_and_weighted_fleets():
    """Mixed-rank and data-size-weighted fleets through the full
    pipeline: stage 2 trains the server model at the full allocated rank
    and the rebroadcast re-masks each client to its own rank; stage 3
    masks every personalization update the same way the simulator
    does."""
    out = _run(PARITY_HARNESS + r"""
run_pipeline_case("fedlora_opt", ranks=(1, 2, 3, 4))
run_pipeline_case("lora_zeropad", ranks=(1, 2, 3, 4))
run_pipeline_case("lora_replication", ranks=(1, 2, 3, 4),
                  weights=(1., 2., 3., 4.))
run_pipeline_case("lora_exact", ranks=(1, 2, 3, 4), weights=(4., 3., 2., 1.))
run_pipeline_case("fedalt", ranks=(2, 4, 4, 2))
run_pipeline_case("lora", weights=(1., 2., 3., 4.))
print("PIPE-HET-OK")
""", timeout=1800)
    assert "PIPE-HET-OK" in out, out


@pytest.mark.slow
def test_collective_parity_faulted_and_async_rounds():
    """Cohort-fault parity: the production round with participation /
    staleness / update_scale vectors matches ``FedSim.run_cohort_round``
    on identical state across three aggregation classes — weighted
    FedAvg with dropouts, trimmed-mean with corrupted-update
    adversaries, and FedBuff staleness-discounted (async/buffered)
    rounds.  Fault vectors change per round, so the static ``use_faults``
    gate and the call-time weight threading both get exercised across a
    retrace boundary."""
    out = _run(PARITY_HARNESS + r"""
def run_fault_case(name, weights=None, fault_rounds=()):
    reseed(name)
    hp = FedHyper(method=name, n_clients=C, local_steps=T, batch=B,
                  seq_len=S, lr=1e-2, client_weights=weights)
    sim = FedSim(cfg, hp)
    st = TrainSettings(lr=hp.lr, micro_batches=1, clip=hp.clip, remat=False,
                       method=name, local_steps=T, client_weights=weights)
    step_fn, _ = make_fed_train_step(cfg, mesh, st)
    na, no = sim.client_adapters, sim.opt_state
    step0 = jnp.zeros((), jnp.int32)
    bytes_before = sim.comm_bytes
    for r, f in enumerate(fault_rounds):
        batches = make_batches()
        big = {k: jnp.concatenate([b[k] for b in batches], axis=1)
               for k in batches[0]}
        def arr(k):
            v = f.get(k)
            return None if v is None else jnp.asarray(v, jnp.float32)
        na, no, met = step_fn(sim.base, na, no, step0, big,
                              participation=arr("participation"),
                              staleness=arr("staleness"),
                              update_scale=arr("update_scale"))
        sim.run_cohort_round(batches, jax.random.PRNGKey(r),
                             participation=f.get("participation"),
                             staleness=f.get("staleness"),
                             update_scale=f.get("update_scale"))
        step0 = step0 + T
        assert np.isfinite(float(met["ce"])), (name, r)
    compare(name, na, sim.client_adapters)
    # billing followed participation: only live clients paid the wire
    live = sum(sum(1 for p in f.get("participation", (1.,) * C) if p > 0)
               for f in fault_rounds)
    assert sim.comm_bytes - bytes_before == live * sim.client_comm_bytes(), \
        (name, sim.comm_bytes - bytes_before, live)
    print("FAULT-OK", name)


run_fault_case("lora", weights=(1., 2., 3., 4.),
               fault_rounds=[{"participation": (1., 0., 1., 1.)},
                             {"participation": (0., 1., 1., 0.)}])
run_fault_case("lora_trimmed",
               fault_rounds=[{"participation": (1., 1., 1., 1.),
                              "update_scale": (1., 25., 1., 1.)},
                             {"participation": (1., 0., 1., 1.),
                              "update_scale": (1., 1., 40., 1.)}])
run_fault_case("lora_fedbuff",
               fault_rounds=[{"participation": (1., 1., 0., 1.),
                              "staleness": (0., 2., 5., 1.)},
                             {"participation": (1., 1., 1., 0.),
                              "staleness": (3., 0., 0., 7.)}])
""")
    assert out.count("FAULT-OK") == 3, out


def test_fed_train_step_rejects_bad_fleets():
    """Fleet-shape validation fires at construction (shared with FedSim
    via peft.fleet_alloc_rank), and aggregators without a collective form
    are rejected before tracing."""
    from repro.core import aggregation as fedagg
    from repro.core.methods import FedMethod
    from repro.core.peft import fleet_alloc_rank
    from repro.launch.mesh import make_client_mesh
    from repro.launch.train import make_fed_train_step, TrainSettings
    from repro.models.config import ArchConfig

    cfg = ArchConfig(name="t", family="dense", n_layers=1, d_model=32,
                     n_heads=2, n_kv_heads=1, d_ff=64, vocab_size=64,
                     dtype="float32", lora_rank=4, lora_dropout=0.0)
    mesh = make_client_mesh(1)
    with pytest.raises(ValueError, match="entries for"):
        make_fed_train_step(cfg, mesh, TrainSettings(
            method="lora", client_ranks=(2, 4)))
    with pytest.raises(ValueError, match="entries for"):
        make_fed_train_step(cfg, mesh, TrainSettings(
            method="lora", client_weights=(1.0, 2.0)))
    with pytest.raises(ValueError, match="het_ranks=False"):
        make_fed_train_step(cfg, mesh, TrainSettings(
            method="prompt", client_ranks=(4,)))
    with pytest.raises(ValueError, match="below the fleet max"):
        fleet_alloc_rank((2, 8), 2, server_rank=4)
    custom = FedMethod(name="custom", make_adapter=lambda *a, **k: {},
                       train_mask=lambda t: t, aggregate=lambda t: t)
    with pytest.raises(ValueError, match="no shard_map collective form"):
        fedagg.collective_form(custom)
    # fedavg_excluding is only WMEAN-expressible when the excluded leaves
    # are exactly the keep-local set (the restore overwrites them); any
    # other exclude_rx would silently average leaves the simulator zeroes
    import functools
    mismatched = FedMethod(
        name="excl", make_adapter=lambda *a, **k: {},
        train_mask=lambda t: t,
        aggregate=functools.partial(fedagg.fedavg_excluding,
                                    exclude_rx=r"foo$"),
        keep_local=r"bar$")
    with pytest.raises(ValueError, match="no shard_map collective form"):
        fedagg.collective_form(mismatched)


# ---------------------------------------------------------------------------
# model-parallel tests (partial-auto shard_map over a 'model' axis)
# ---------------------------------------------------------------------------


def test_fed_train_step_dense_and_moe_debug_mesh():
    out = _run("""
import jax, jax.numpy as jnp
from repro.launch.mesh import make_debug_mesh, dp_size
from repro.launch.train import make_fed_train_step, TrainSettings
from repro.models.config import ArchConfig
from repro.models import model as M
from repro.core import peft, aggregation as agg

mesh = make_debug_mesh(4, 2)
for fam_kw in [dict(family="dense"), dict(family="moe", n_experts=4, top_k=2)]:
    cfg = ArchConfig(name="t", n_layers=2, d_model=64, n_heads=4,
                     n_kv_heads=2, d_ff=128, vocab_size=256, dtype="float32",
                     lora_rank=4, lora_dropout=0.0, **fam_kw)
    C = dp_size(mesh)
    base = M.init_params(jax.random.PRNGKey(0), cfg)
    ad = peft.add_lora(base, cfg, jax.random.PRNGKey(1), decomposed=True)
    adapters = agg.broadcast_to_clients(ad, C)
    with jax.set_mesh(mesh):
        fn, opt_init = make_fed_train_step(cfg, mesh, TrainSettings(micro_batches=2))
        ost = opt_init(adapters)
        batch = {"tokens": jnp.ones((C, 4, 32), jnp.int32),
                 "loss_mask": jnp.ones((C, 4, 32), jnp.float32)}
        na, no, met = jax.jit(fn)(base, adapters, ost, jnp.zeros((), jnp.int32), batch)
        assert jnp.isfinite(met["ce"]), fam_kw
        # aggregation: shared components identical across clients
        leaf = jax.tree.leaves(na)[0]
        import numpy as np
        for c in range(1, C):
            np.testing.assert_allclose(np.asarray(leaf[c]), np.asarray(leaf[0]), rtol=1e-5)
    print("OK", fam_kw)
""")
    assert out.count("OK") == 2


def test_moe_ep_matches_local_math():
    _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_debug_mesh
from repro.models.config import ArchConfig
from repro.models.layers import moe_ffn_ep, moe_ffn_local
cfg = ArchConfig(name="m", family="moe", n_layers=1, d_model=32, n_heads=2,
                 n_kv_heads=1, d_ff=64, vocab_size=64, dtype="float32",
                 n_experts=4, top_k=2, capacity_factor=8.0)
mesh = make_debug_mesh(4, 2)
k = jax.random.split(jax.random.PRNGKey(0), 4)
p = {"router": {"kernel": jax.random.normal(k[0], (32, 4)) * 0.2},
     "experts": {"gate": jax.random.normal(k[1], (4, 32, 64)) * 0.2,
                 "up": jax.random.normal(k[2], (4, 32, 64)) * 0.2,
                 "down": jax.random.normal(k[3], (4, 64, 32)) * 0.2}}
x = jax.random.normal(jax.random.PRNGKey(5), (8, 16, 32))
y_loc, _ = moe_ffn_local(p, x, cfg)
with jax.set_mesh(mesh):
    y_ep, _ = jax.jit(lambda p, x: moe_ffn_ep(p, x, cfg, mesh))(p, x)
np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_loc), rtol=2e-3, atol=2e-4)
# small-batch (decode-style) replicated path
x1 = jax.random.normal(jax.random.PRNGKey(6), (1, 3, 32))
y1_loc, _ = moe_ffn_local(p, x1, cfg)
with jax.set_mesh(mesh):
    y1_ep, _ = jax.jit(lambda p, x: moe_ffn_ep(p, x, cfg, mesh))(p, x1)
np.testing.assert_allclose(np.asarray(y1_ep), np.asarray(y1_loc), rtol=2e-3, atol=2e-4)
print("OK")
""")


def test_dryrun_tiny_mesh_smoke():
    """The dry-run machinery end-to-end on a small mesh with a reduced
    arch — exercises lower+compile+analysis without the 512-dev cost."""
    _run("""
import jax, jax.numpy as jnp
from repro.configs import get_smoke_config, InputShape
from repro.launch import specs as SP
from repro.launch.mesh import make_debug_mesh, dp_size
from repro.launch.serve import make_decode_step
from repro.launch import analysis as AN

cfg = get_smoke_config("gemma3-1b")
mesh = make_debug_mesh(4, 2)
shape = InputShape("mini_decode", 64, 8, "decode")
with jax.set_mesh(mesh):
    abs_base = SP.abstract_params(cfg)
    base_sh = SP.param_specs(cfg, mesh, abs_base)
    args, sh = SP.decode_specs(cfg, shape, mesh)
    fn = make_decode_step(cfg, mesh)
    lw = jax.jit(fn, in_shardings=(base_sh, sh["new_token"], sh["cache"],
                                   sh["cache_index"]), out_shardings=None
                 ).lower(abs_base, args["new_token"], args["cache"],
                         args["cache_index"])
    c = lw.compile()
    mem = c.memory_analysis()
    assert mem.temp_size_in_bytes >= 0
    colls = AN.parse_collectives(c.as_text(), (2,))
    fl = AN.analytic_step_flops(cfg, shape)
    assert fl["flops_global"] > 0
    print("OK", colls.get("total", 0) >= 0)
""")
