"""Named parts of the expert layer and of stage 2's gradient sync.

* Every expert-layer body (``moe_ffn_manual`` inside the stage programs,
  ``moe_ffn_local``, ``moe_ffn_ep``) runs under ``ffn``, and inside it
  under ``moe_router``, ``moe_dispatch``, ``moe_experts`` and
  ``moe_combine``: the compiled HLO carries each in the forward, the
  backward and the rematerialised forward.
* The expert layer moves D-wide rows by gathers only, each under
  ``moe_dispatch`` or ``moe_combine``: no row scatter-add, forward,
  backward or recompute.
* The ``moe_path`` counter records, at trace time, the body taken, how
  it moves rows, the expert slots it holds and their capacity.
* Stage 2's token-weighted gradient psum runs under ``grad_sync`` when
  the server batch is split over the clients, and nowhere else.
"""
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.fed.simulate import FedHyper, FedSim
from repro.models import layers as L
from repro.models.config import ArchConfig

CFG = ArchConfig(name="moe-scope-t", family="moe", n_layers=2, d_model=32,
                 n_heads=4, n_kv_heads=2, d_head=8, d_ff=16, vocab_size=64,
                 n_experts=4, top_k=2, qk_norm=True, dtype="float32",
                 lora_rank=4, lora_dropout=0.0)
PARTS = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")
PROGRAMS = ("round_step", "global_step", "personal_step")
OP_NAME = re.compile(r'op_name="([^"]*)"')
# a gather or scatter: its element type, shape and op name
MOVE = re.compile(r'= (\w+)\[([\d,]*)\]\S* (gather|scatter)\(.*op_name="([^"]*)"')
CAPACITY = 20                     # ceil(2 · 32 tokens · 1.25 / 4)
# the combine's backward reads the experts' output itself, so nothing of
# its forward is recomputed; every other part runs again under the remat
RECOMPUTED = {"moe_router": True, "moe_dispatch": True, "moe_experts": True,
              "moe_combine": False}


@pytest.fixture(autouse=True)
def _null_sink():
    obs.disable()
    yield
    obs.disable()


def _batch(shape, seed):
    rng = np.random.default_rng(seed)
    return {"tokens": jnp.asarray(rng.integers(5, 64, size=shape), jnp.int32),
            "loss_mask": jnp.ones(shape, jnp.float32)}


def _moe_path():
    return obs.active().metrics.snapshot()["counters"]["moe_path"]


@pytest.fixture(scope="module")
def stages():
    """({program: [op_name, ...]}, the moe_path counter, {program:
    [(kind, dtype, shape, op_name) of each gather and scatter]}) of the
    three stage programs of a tiny expert model, lowered once each."""
    from repro.launch.mesh import make_client_mesh
    from repro.launch.train import TrainSettings, make_fed_pipeline_step
    sim = FedSim(CFG, FedHyper(method="fedlora_opt", n_clients=1))
    pipe = make_fed_pipeline_step(CFG, make_client_mesh(1), TrainSettings(
        method="fedlora_opt", local_steps=1, global_steps=1,
        personal_steps=2))
    base, ad = sim.base, sim.client_adapters
    obs.enable()
    try:
        low_r = pipe.round_step.lower(base, ad, pipe.opt_init(ad),
                                      jnp.zeros((), jnp.int32),
                                      _batch((1, 2, 16), 0))
        lows = {"round_step": low_r,
                "global_step": pipe.global_step.lower(
                    base, low_r.out_info[2], ad, _batch((2, 16), 1)),
                "personal_step": pipe.personal_step.lower(
                    base, ad, _batch((1, 4, 16), 2))}
        counter = _moe_path()
    finally:
        obs.disable()
    texts = {k: v.compile().as_text() for k, v in lows.items()}
    names = {k: OP_NAME.findall(t) for k, t in texts.items()}
    moves = {k: [(m.group(3), m.group(1),
                  tuple(int(d) for d in m.group(2).split(",") if d),
                  m.group(4)) for m in MOVE.finditer(t)]
             for k, t in texts.items()}
    return names, counter, moves


def _fwd_bwd_remat(names):
    """The op names of the forward, the backward and the recompute."""
    return ([n for n in names if "jvp(" in n and "transpose(" not in n],
            [n for n in names if "transpose(" in n
             and "rematted_computation" not in n],
            [n for n in names if "rematted_computation" in n.split("/")])


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("part", PARTS)
def test_expert_parts_in_forward_backward_and_remat(stages, program, part):
    names = [n for n in stages[0][program] if part in n.split("/")]
    assert all(re.search(rf"(^|/)ffn/(.*/)?{part}/", n) for n in names), part
    fwd, bwd, remat = _fwd_bwd_remat(names)
    assert fwd and bwd, (part, len(fwd), len(bwd))
    assert bool(remat) == RECOMPUTED[part], (part, len(remat))


@pytest.mark.parametrize("program", PROGRAMS)
def test_expert_rows_move_by_gathers_only(stages, program):
    """Every gather of D-wide float rows inside ``ffn`` is the dispatch's
    or the combine's, forward, backward and recompute; no scatter writes
    D-wide rows anywhere in the program."""
    moves = stages[2][program]
    rows = [n for kind, dt, shape, n in moves if kind == "gather"
            and dt.startswith("f") and len(shape) >= 2
            and shape[-1] == CFG.d_model and "ffn" in n.split("/")]
    parts = [set(n.split("/")) & {"moe_dispatch", "moe_combine"}
             for n in rows]
    assert all(parts), [n for n, p in zip(rows, parts) if not p]
    assert all(_fwd_bwd_remat(rows)), rows
    scattered = [(shape, n) for kind, _, shape, n in moves
                 if kind == "scatter" and shape[-1:] == (CFG.d_model,)]
    assert not scattered, scattered


def test_moe_path_counts_each_stage_program_once(stages):
    assert stages[1] == [{"labels": {"path": "manual", "dispatch": "gather",
                                     "experts": 4, "capacity": CAPACITY},
                          "value": 3.0}]


def test_no_grad_sync_with_one_client(stages):
    assert not any("grad_sync" in n.split("/")
                   for names in stages[0].values() for n in names)


def _expert_params():
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    D, F, E = CFG.d_model, CFG.d_ff, CFG.n_experts
    return {"router": {"kernel": jax.random.normal(k[0], (D, E)) * 0.2},
            "experts": {"gate": jax.random.normal(k[1], (E, D, F)) * 0.2,
                        "up": jax.random.normal(k[2], (E, D, F)) * 0.2,
                        "down": jax.random.normal(k[3], (E, F, D)) * 0.2}}


@pytest.mark.parametrize("path", ["local", "ep"])
def test_local_and_ep_bodies_carry_the_parts_and_count(path):
    p = _expert_params()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, CFG.d_model))
    if path == "local":
        fn = lambda p, x: L.moe_ffn_local(p, x, CFG)
    else:
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        fn = lambda p, x: L.moe_ffn_ep(p, x, CFG, mesh)
    obs.enable()
    low = jax.jit(fn).lower(p, x)
    assert _moe_path() == [{"labels": {"path": path, "dispatch": "gather",
                                       "experts": 4, "capacity": CAPACITY},
                            "value": 1.0}]
    names = OP_NAME.findall(low.compile().as_text())
    for part in PARTS:
        assert any(re.search(rf"ffn/(.*/)?{part}/", n) for n in names), part


def test_grad_sync_names_the_sharded_stage2_psum():
    """Two clients, a server batch of two rows: stage 2 splits the rows
    over the clients and sums the gradients under ``grad_sync``."""
    snippet = r"""
import re
import jax, jax.numpy as jnp, numpy as np
from repro.fed.simulate import FedHyper, FedSim
from repro.launch.mesh import make_client_mesh
from repro.launch.train import TrainSettings, make_fed_pipeline_step
from repro.models.config import ArchConfig
cfg = ArchConfig(name="gs", family="dense", n_layers=1, d_model=16,
                 n_heads=2, n_kv_heads=2, d_ff=32, vocab_size=32,
                 dtype="float32", lora_rank=2, lora_dropout=0.0)
sim = FedSim(cfg, FedHyper(method="fedlora_opt", n_clients=2))
pipe = make_fed_pipeline_step(cfg, make_client_mesh(2), TrainSettings(
    method="fedlora_opt", local_steps=1, global_steps=1, personal_steps=1))
agg = jax.tree.map(lambda x: x[0], sim.client_adapters)
server = {"tokens": jnp.ones((2, 8), jnp.int32),
          "loss_mask": jnp.ones((2, 8), jnp.float32)}
text = pipe.global_step.lower(sim.base, agg, sim.client_adapters,
                              server).compile().as_text()
op = re.compile(r'op_name="([^"]*)"')
red = [op.search(l).group(1) for l in text.splitlines()
       if re.search(r" all-reduce(-start)?\(", l) and op.search(l)]
print("REDUCE", sum("grad_sync" in n.split("/") for n in red), len(red))
"""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src,
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    r = subprocess.run([sys.executable, "-c", snippet], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    synced, reduces = map(int, re.search(r"REDUCE (\d+) (\d+)",
                                         r.stdout).groups())
    assert synced >= 1 and reduces >= synced, r.stdout
