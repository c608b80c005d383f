"""Named scopes inside the stage programs and the pipeline's host spans.

* The compiled HLO of ``round_step``, ``global_step`` and
  ``personal_step`` carries each model scope (``attn``, ``ffn``,
  ``lora``, ``ce``) in its ``op_name`` metadata in all three forms — the
  forward (``jvp(``), the backward (``transpose(``) and the
  rematerialised forward (``rematted_computation``) — plus
  ``optimizer`` in every stage and ``aggregate`` in the round.
* ``obs.span`` always writes a host event into a profiler trace, and
  reads the clock and records ``span_seconds`` only with telemetry on.
* ``FedPipeline.run_pipeline`` opens the three ``fed/*`` stage spans, and
  with telemetry on its ``fed_round`` event's ``wall`` is their seconds.
"""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.fed.simulate import FedHyper, FedSim
from repro.models.config import ArchConfig
from repro.obs import read_events

CFG = ArchConfig(name="scope-t", family="dense", n_layers=2, d_model=32,
                 n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=64,
                 dtype="float32", lora_rank=4, lora_dropout=0.0)
STAGE_SPANS = ("fed/round", "fed/stage2_global", "fed/stage3_personalize")
MODEL_SCOPES = ("attn", "ffn", "lora", "ce")
OP_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.fixture(autouse=True)
def _null_sink():
    obs.disable()
    yield
    obs.disable()


def _batch(shape, seed):
    rng = np.random.default_rng(seed)
    return {"tokens": jnp.asarray(rng.integers(5, 64, size=shape), jnp.int32),
            "loss_mask": jnp.ones(shape, jnp.float32)}


def _pipeline(telemetry=False):
    from repro.launch.mesh import make_client_mesh
    from repro.launch.train import TrainSettings, make_fed_pipeline_step
    st = TrainSettings(method="fedlora_opt", local_steps=1, global_steps=1,
                       personal_steps=2, telemetry=telemetry)
    return make_fed_pipeline_step(CFG, make_client_mesh(1), st)


@pytest.fixture(scope="module")
def state():
    sim = FedSim(CFG, FedHyper(method="fedlora_opt", n_clients=1,
                               local_steps=1))
    return sim.base, sim.client_adapters


def _inputs(pipe, state, seed=0):
    base, ad = state
    return (base, ad, pipe.opt_init(ad), jnp.zeros((), jnp.int32),
            _batch((1, 2, 16), seed), _batch((2, 16), seed + 1),
            _batch((1, 4, 16), seed + 2))


@pytest.fixture(scope="module")
def op_names(state):
    """{program: [op_name, ...]} of the three compiled stage programs."""
    pipe = _pipeline()
    base, ad, ost, step, b1, b2, b3 = _inputs(pipe, state)
    ad1, _, agg, _ = pipe.round_step(base, ad, ost, step, b1)
    texts = {
        "round_step": pipe.round_step.lower(base, ad, ost, step, b1),
        "global_step": pipe.global_step.lower(base, agg, ad1, b2),
        "personal_step": pipe.personal_step.lower(base, ad1, b3)}
    return {k: OP_NAME.findall(v.compile().as_text())
            for k, v in texts.items()}


PROGRAMS = ("round_step", "global_step", "personal_step")


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("scope", MODEL_SCOPES)
def test_model_scope_in_forward_backward_and_remat(op_names, program, scope):
    names = [n for n in op_names[program] if scope in n.split("/")]
    fwd = [n for n in names if "jvp(" in n and "transpose(" not in n]
    bwd = [n for n in names if "transpose(" in n
           and "rematted_computation" not in n]
    remat = [n for n in names if "rematted_computation" in n.split("/")]
    assert fwd and bwd and remat, (scope, len(fwd), len(bwd), len(remat))


@pytest.mark.parametrize("program", PROGRAMS)
def test_optimizer_scope_in_every_stage(op_names, program):
    assert any("optimizer" in n.split("/") for n in op_names[program])


def test_aggregate_scope_only_in_the_round(op_names):
    has = {p: any("aggregate" in n.split("/") for n in op_names[p])
           for p in PROGRAMS}
    assert has == {"round_step": True, "global_step": False,
                   "personal_step": False}


def test_lora_nests_inside_attn(op_names):
    """The q/v adapters' deltas sit inside attention: the innermost
    scope of their ops is ``lora``."""
    assert any(re.search(r"/attn/(.*/)?lora/", n)
               for n in op_names["round_step"])


# ---------------------------------------------------------------------------
# obs.span

def _host_events(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return [ev.name for plane in ProfileData.from_file(path).planes
            for line in plane.lines for ev in line.events]


def test_span_annotates_the_trace_with_telemetry_off(tmp_path, monkeypatch):
    """With telemetry off the span opens its profiler annotation and
    reads no clock (so it records no histogram)."""
    from repro.obs import tracing

    def no_clock():
        raise AssertionError("span read the clock with telemetry off")
    monkeypatch.setattr(tracing.time, "perf_counter", no_clock)
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("fed/round", method="m") as s:
            jnp.ones(4).block_until_ready()
    assert s.seconds == 0.0
    assert "fed/round" in _host_events(str(tmp_path))


def test_span_records_the_histogram_with_telemetry_on():
    obs.enable()
    with obs.span("fed/round", method="m") as s:
        pass
    snap = obs.active().metrics.snapshot()
    (series,) = snap["histograms"]["span_seconds"]
    assert series["labels"] == {"span": "fed/round", "method": "m"}
    assert series["count"] == 1 and s.seconds >= 0.0


def test_span_closes_its_annotation_when_the_body_raises():
    with pytest.raises(ValueError):
        with obs.span("fed/round"):
            raise ValueError("boom")
    obs.enable()
    with pytest.raises(ValueError):
        with obs.span("fed/round"):
            raise ValueError("boom")
    (series,) = obs.active().metrics.snapshot()["histograms"]["span_seconds"]
    assert series["count"] == 1


# ---------------------------------------------------------------------------
# the pipeline's stage spans

def test_pipeline_stage_spans_reach_the_trace_with_telemetry_off(
        tmp_path, state):
    pipe = _pipeline()
    args = _inputs(pipe, state)
    pipe.run_pipeline(*args)                      # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        out = pipe.run_pipeline(*args)
        jax.block_until_ready(out[0])
    names = _host_events(str(tmp_path))
    for span in STAGE_SPANS:
        assert names.count(span) == 1, span


def test_pipeline_round_event_wall_is_the_stage_spans(tmp_path, state):
    pipe = _pipeline(telemetry=True)
    args = _inputs(pipe, state, seed=5)
    obs.enable(str(tmp_path / "fed.jsonl"))
    pipe.run_pipeline(*args)
    snap = obs.emit_snapshot()
    obs.disable()
    (ev,) = read_events(str(tmp_path / "fed.jsonl"), kind="fed_round")
    wall = ev["wall"]
    assert set(wall) == {"round", "global", "personal", "total"}
    assert wall["total"] == pytest.approx(
        wall["round"] + wall["global"] + wall["personal"], abs=2e-6)
    hist = {s["labels"]["span"]: s
            for s in snap["histograms"]["span_seconds"]}
    for span, key in zip(STAGE_SPANS, ("round", "global", "personal")):
        assert hist[span]["count"] == 1
        assert hist[span]["labels"]["method"] == "fedlora_opt"
        assert hist[span]["sum"] == pytest.approx(wall[key], abs=1e-6)
    stages = read_events(str(tmp_path / "fed.jsonl"), kind="fed_stage")
    assert [s["wall"] for s in stages] == [wall["global"], wall["personal"]]
