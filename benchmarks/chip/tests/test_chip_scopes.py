"""Device time by named scope and device idle under the program's spans
(scopes.py): on made-up events, and on a trace recorded here on the CPU
around the pipeline's ``fed/*`` stage spans."""
import importlib.util

import pytest

import devtrace as t
import scopes
from devtrace import Device, Event, Trace


PRE = "jit(round_step)/while/body/closed_call"
NAMES = {"round_step": {
    "fusion.1": f"{PRE}/jvp()/attn/dot_general",
    "fusion.2": f"{PRE}/jvp()/attn/lora/dot_general",
    "fusion.3": f"{PRE}/transpose(jvp())/checkpoint/rematted_computation/"
                "ffn/mul",
    "fusion.4": f"{PRE}/optimizer/sqrt",
    "copy.5": f"{PRE}/add",
    "while.6": f"{PRE}/attn/while",
    "fusion.7": "jit(round_step)/aggregate/psum"},
    "personal_step": {"fusion.1": f"{PRE}/jvp()/ce/dot_general"}}


def _op(inst, start, dur):
    """An operation event as a TPU trace names it: by its HLO text."""
    op = inst.split(".")[0]
    kind = ", kind=kLoop" if op == "fusion" else ""
    return Event(f"%{inst} = f32[2]{{0}} {op}(f32[2]{{0}} %p){kind}",
                 start, dur)


def _trace():
    ops = [_op("fusion.1", 0.0, 1.0), _op("fusion.2", 1.0, 0.5),
           _op("fusion.3", 1.5, 0.25), _op("fusion.4", 1.75, 0.25),
           _op("copy.5", 2.0, 0.5),
           _op("while.6", 0.0, 2.5),               # a container: left out
           _op("fusion.7", 3.0, 0.5),
           _op("fusion.1", 3.5, 0.5),              # personal_step's
           _op("fusion.9", 4.0, 0.25)]             # no such instruction
    mods = [Event("jit_round_step(1315)", 0.0, 3.5),
            Event("jit_personal_step(8973)", 3.5, 1.0)]
    spans = [Event("bench/iteration", 0.0, 5.0)]
    return Trace([Device(mods, ops)], spans)


def _ctx(trace, **kw):
    job = {"local_steps": 1, "global_steps": 1, "personal_steps": 2}
    return dict({"trace": trace, "traffic": {"job": job}, "units": 1,
                 "op_names": NAMES}, **kw)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, t.__file__.replace("devtrace.py", f"metrics/{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("name,scope", [
    ("a/attn/lora/dot", "lora"), ("a/lora/attn/dot", "attn"),
    ("transpose(jvp())/checkpoint/rematted_computation/ffn/mul", "ffn"),
    ("opt_state.mu['attn']['q_proj']", scopes.NO_SCOPE),
    ("a/attention/dot", scopes.NO_SCOPE), ("", scopes.NO_SCOPE)])
def test_innermost_scope_wins(name, scope):
    assert scopes.scope_of(name) == scope


def test_program_names_and_hlo_text():
    assert scopes.program("jit_round_step(13154574830244894782)") == \
        "round_step"
    assert scopes.program("round_step") == "round_step"
    text = ("HloModule jit_round_step, is_scheduled=true\n\n"
            "fused_computation.1 {\n"
            "  %p = f32[2]{0} parameter(0), metadata={op_name=\"p\"}\n}\n"
            "ENTRY %main.3 (p: f32[2]) -> f32[2] {\n"
            "  %copy.2 = f32[2]{0} copy(%p)\n"
            "  ROOT %fusion.1 = f32[2]{0} fusion(%copy.2), kind=kLoop, "
            "calls=%fused_computation.1, metadata={op_type=\"mul\" "
            "op_name=\"jit(round_step)/attn/mul\" source_line=3}\n}\n")
    assert scopes.hlo_op_names(text) == (
        "round_step", {"p": "p", "fusion.1": "jit(round_step)/attn/mul"})


def test_breakdown_joins_by_program_and_leaves_out_containers():
    b = scopes.breakdown(_trace(), NAMES)
    assert b["scopes"] == {"attn": 1.0, "lora": 0.5, "ffn": 0.25,
                           "optimizer": 0.25, scopes.NO_SCOPE: 0.75,
                           "aggregate": 0.5, "ce": 0.5}
    assert b["total"] == pytest.approx(3.75)      # the while op is out
    assert sum(b["scopes"].values()) == pytest.approx(b["total"])
    assert b["remat"] == pytest.approx(0.25)
    assert b["families"] == {"copy f32[2]": 0.5, "fusion:kLoop f32[2]": 0.25}


def test_cpu_trace_names_join_too():
    """A CPU trace names an operation by its instruction alone."""
    ops = [Event("fusion.1", 0.0, 1.0), Event("end: fusion.1", 1.0, 0.0)]
    tr = Trace([Device([Event("round_step", 0.0, 2.0)], ops)], [])
    assert scopes.breakdown(tr, NAMES)["scopes"] == {"attn": 1.0,
                                                     scopes.NO_SCOPE: 0.0}


def test_readers_per_step_and_per_round():
    ctx = _ctx(_trace())                          # 4 steps, 1 round
    assert _reader("attn_ms.train")(ctx) == pytest.approx(250.0)
    assert _reader("lora_ms.train")(ctx) == pytest.approx(125.0)
    assert _reader("ce_ms.train")(ctx) == pytest.approx(125.0)
    assert _reader("aggregate_ms.train")(ctx) == pytest.approx(500.0)
    assert _reader("remat_pct.train")(ctx) == pytest.approx(
        100 * 0.25 / 3.75)
    assert _reader("unscoped_pct.train")(ctx) == pytest.approx(
        100 * 0.75 / 3.75)
    per_step = sum(_reader(f"{s}_ms.train")(ctx) for s in
                   ("attn", "ffn", "lora", "ce", "optimizer"))
    per_step += _reader("aggregate_ms.train")(ctx) / 4
    per_step += 1e3 * 0.75 / 4                    # no scope
    assert per_step == pytest.approx(1e3 * 3.75 / 4)


def test_readers_find_nothing_in_a_program_without_scopes():
    plain = {"round_step": {k: "jit(round_step)/dot_general"
                            for k in NAMES["round_step"]}}
    ctx = _ctx(_trace(), op_names=plain, program_spans=[])
    for name in ("attn_ms.train", "aggregate_ms.train",
                 "unscoped_pct.train", "dispatch_idle_ms.train"):
        assert _reader(name)(ctx) is None, name
    assert _reader("remat_pct.train")(ctx) == 0.0
    ctx = _ctx(_trace(), op_names={}, program_spans=[])   # nothing joins
    assert _reader("remat_pct.train")(ctx) is None
    assert _reader("attn_ms.train")(ctx) is None


def test_idle_counts_only_inside_program_spans():
    ops = [Event("a", 0.0, 1.0), Event("b", 2.0, 1.0), Event("c", 5.0, 1.0)]
    trace = Trace([Device([], ops)], [Event("bench/iteration", 0.0, 6.0)])
    spans = [Event("fed/round", 0.5, 2.0),         # holds the 1.0-2.0 gap
             Event("fed/stage2_global", 3.5, 0.5)]  # half of 3.0-5.0
    assert scopes.idle_under(trace, spans) == pytest.approx(1.5)
    ctx = _ctx(trace, units=3, program_spans=spans + [
        Event("serve/prefill", 3.0, 2.0)])         # not a fed/ span
    assert _reader("dispatch_idle_ms.train")(ctx) == pytest.approx(500.0)


@pytest.fixture(scope="module")
def small_cell():
    """A tiny pipeline laid out as `fedpipe.Cell` lays it out: stage
    programs behind its bench/* span wrappers, state and one iteration's
    rows."""
    import dataclasses
    import types

    import jax.numpy as jnp
    import numpy as np

    import fedpipe
    from repro.fed.simulate import FedHyper, FedSim
    from repro.launch.mesh import make_client_mesh
    from repro.launch.train import TrainSettings, make_fed_pipeline_step
    from repro.models.config import ArchConfig

    cfg = ArchConfig(name="t", family="dense", n_layers=1, d_model=16,
                     n_heads=2, n_kv_heads=2, d_ff=32, vocab_size=32,
                     dtype="float32", lora_rank=2, lora_dropout=0.0)
    sim = FedSim(cfg, FedHyper(method="fedlora_opt", n_clients=1))
    pipe = make_fed_pipeline_step(cfg, make_client_mesh(1), TrainSettings(
        local_steps=1, global_steps=1, personal_steps=1))
    pipe = dataclasses.replace(pipe, **{
        k: fedpipe._spanned(f"bench/{k}", getattr(pipe, k))
        for k in ("round_step", "global_step", "personal_step")})
    rng = np.random.default_rng(0)

    def batch(*shape):
        return {"tokens": jnp.asarray(rng.integers(2, 32, shape), jnp.int32),
                "loss_mask": jnp.ones(shape, jnp.float32)}
    data = [{"batch": batch(1, 1, 8), "server": batch(1, 8),
             "personal": batch(1, 1, 8)}]
    return types.SimpleNamespace(
        pipe=pipe, base=sim.base, adapters=sim.client_adapters,
        opt_state=pipe.opt_init(sim.client_adapters),
        step=jnp.zeros((), jnp.int32), data=data)


def _args(cell):
    b = cell.data[0]
    return (cell.base, cell.adapters, cell.opt_state, cell.step,
            b["batch"], b["server"], b["personal"])


def test_stage_programs_lowered_from_the_cell_name_every_scope(small_cell):
    names = scopes.stage_programs(small_cell)
    assert set(names) == {"round_step", "global_step", "personal_step"}
    for prog, want in (("round_step", scopes.SCOPES),
                       ("personal_step", scopes.SCOPES[:-1])):
        found = {scopes.scope_of(n) for n in names[prog].values()}
        assert set(want) <= found, (prog, found)
    assert any(scopes.REMAT in n for n in names["global_step"].values())
    assert scopes.op_names({"cell": object()}) == {}      # no pipeline


def test_op_names_of_an_executable_another_version_compiled(small_cell):
    """JAX's compile caches leave metadata out of their keys, so the
    executable that ran may carry the op names of a version of the
    program without the scopes, and other instruction numbers.  The names
    then come from a fresh compile, matched line by line."""
    import re
    low = scopes._jitted(small_cell.pipe.personal_step).lower(
        small_cell.base, small_cell.adapters, small_cell.data[0]["personal"],
        None)
    text = low.compile().as_text()
    prog, own = scopes.hlo_op_names(text)
    # the same program as another version compiled it: scope-free op
    # names, and one instruction numbered differently
    inst = next(k for k, v in own.items() if scopes.scope_of(v) == "attn")
    other = re.sub(r'op_name="[^"]*"', 'op_name="jit(personal_step)/add"',
                   text)
    other = re.sub(rf"%{re.escape(inst)}(?![\w.\-])", "%renamed.99999",
                   other)
    prog2, names = scopes.ran_op_names(low, other)
    assert prog2 == prog == "personal_step"
    assert scopes.scope_of(names["renamed.99999"]) == "attn"
    assert inst not in names
    assert {k: v for k, v in names.items() if k != "renamed.99999"} == {
        k: v for k, v in own.items() if k != inst}
    # a text that is another program keeps its own names
    changed = other.replace(" multiply(", " add(", 1)
    assert scopes.ran_op_names(low, changed)[1] == \
        scopes.hlo_op_names(changed)[1]


def test_recorded_cpu_trace_has_the_pipelines_program_spans(tmp_path,
                                                            small_cell):
    """The pipeline's stage spans land in a CPU profiler trace, on the
    clock of the benchmark's own bench/* spans."""
    import jax
    args = _args(small_cell)
    small_cell.pipe.run_pipeline(*args)
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench/iteration"):
                jax.block_until_ready(small_cell.pipe.run_pipeline(*args)[0])
    tr = t.load(str(tmp_path), cpu=True)
    spans = scopes.load_program_spans(str(tmp_path))
    names = [s.name for s in spans]
    for n in ("fed/round", "fed/stage2_global", "fed/stage3_personalize"):
        assert names.count(n) == 2, n
    lo, hi = tr.window()
    assert all(lo <= s.start and s.start + s.dur <= hi for s in spans)
    assert 0 <= scopes.idle_under(tr, spans) < hi - lo
