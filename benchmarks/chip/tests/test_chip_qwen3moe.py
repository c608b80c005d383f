"""The Qwen3-30B-A3B configuration against the program's registered
architecture, and the expert layer's readers (subscopes.py,
expert_counts.py, metrics/moe_*.py, metrics/grad_sync_ms.train.py) on
made-up events and hand-counted shapes."""
import dataclasses
import importlib.util
import json

import jax
import pytest

import counts
import devtrace
import dims
import expert_counts
import subscopes
import weights
from devtrace import Device, Event, Trace

NAME = "qwen3-moe-30b-a3b"
CELL = "qwen3moe-fedpipe"
# what the configuration may change from the registered architecture
MAY_DIFFER = {"n_layers", "vocab_size", "rope_theta", "lora_dropout"}
PUBLISHED = {"num_hidden_layers": (48, 8), "vocab_size": (151936, 18992)}


def _file():
    return json.loads((dims.HERE / "configs" / f"{NAME}.json").read_text())


def test_widths_match_the_registered_architecture():
    from repro.configs import get_config
    d = dims.load(NAME)
    arch = get_config(d.program)
    assert (d.d_model, d.heads, d.kv_heads, d.head_dim, d.d_ff, d.experts,
            d.top_k, d.qk_norm, d.capacity_factor) == (
        arch.d_model, arch.n_heads, arch.n_kv_heads, arch.head_dim,
        arch.d_ff, arch.n_experts, arch.top_k, arch.qk_norm,
        arch.capacity_factor)
    assert (d.d_model, d.heads, d.kv_heads, d.head_dim, d.d_ff, d.experts,
            d.top_k) == (2048, 32, 4, 128, 768, 128, 8)
    run = dims.program_config(d)
    changed = {f.name for f in dataclasses.fields(arch)
               if getattr(arch, f.name) != getattr(run, f.name)}
    assert changed <= MAY_DIFFER, changed
    assert (run.rope_theta, run.norm_eps) == (1e6, 1e-6)


def test_file_states_its_two_cuts():
    c = _file()
    assert c["source"] == "https://huggingface.co/Qwen/Qwen3-30B-A3B"
    assert c["architectures"] == ["Qwen3MoeForCausalLM"]
    assert set(c["reduced"]) == set(PUBLISHED)
    for key, (published, run) in PUBLISHED.items():
        assert (c["reduced"][key]["published"],
                c["reduced"][key]["run"], c[key]) == (published, run, run)
    assert c["vocab_size"] * 8 == c["reduced"]["vocab_size"]["published"]
    assert c["mlp_only_layers"] == [] and c["norm_topk_prob"]
    assert not c["tie_word_embeddings"]
    assert {"routing", "load_balance", "weights", "adapter_state",
            "lora_dropout"} <= set(c["assumed"])
    assert c["deployment"]
    bench = json.loads((dims.HERE.parents[1] / "BENCHMARK.json").read_text())
    (entry,) = [e for e in bench["configs"] if e["name"] == NAME]
    assert entry["reduced"] == list(PUBLISHED)
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "fedpipe", 1)


def test_weight_trees_follow_the_program_layout():
    from repro.core.methods import get_method
    from repro.launch.train import abstract_base
    d = dataclasses.replace(dims.load(NAME), layers=2)
    cfg = dims.program_config(d)
    want = abstract_base(cfg)
    got = jax.eval_shape(lambda k: weights.make_base(k, d),
                         jax.random.PRNGKey(0))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    assert got["blocks"]["sub0"]["moe"]["experts"]["gate"].shape == (
        2, 128, 2048, 768)
    ad_want = jax.eval_shape(lambda: get_method("fedlora_opt").make_adapter(
        want, cfg, jax.random.PRNGKey(0)))
    ad = jax.eval_shape(lambda k: weights.make_adapters(k, d, 1),
                        jax.random.PRNGKey(0))
    assert jax.tree.structure(ad) == jax.tree.structure(ad_want)
    for a, b in zip(jax.tree.leaves(ad), jax.tree.leaves(ad_want)):
        assert a.shape == (1,) + b.shape and a.dtype == b.dtype


# ---------------------------------------------------------------------------
# expert_counts: the expert matmuls over the routed slots

def test_expert_counts_at_the_cells_shapes():
    d = dims.load(NAME)
    n = 2 * 2048 * 8                          # rows · S · top-8
    assert expert_counts.slots(d, 2, 2048) == n == 32768
    # gate, up, down: 2 · n · 2048 · 768 each
    assert expert_counts.pass_flops(d, n) == 3 * 2 * n * 2048 * 768
    # bf16: the three (128, 2048, 768) weights, then (n, 2048) in and
    # (n, 768) out of gate and up, (n, 768) in and (n, 2048) out of down
    w = 3 * 128 * 2048 * 768
    acts = 2 * (n * 2048 + n * 768) + n * 768 + n * 2048
    assert expert_counts.pass_bytes(d, n) == 2 * (w + acts)
    ops, nbytes = expert_counts.train_step(d, 2, 2048)
    # forward, activation backward, recompute, in each of 8 layers
    assert ops == 24 * 3 * 2 * n * 2048 * 768 == pytest.approx(7.4217e12,
                                                               rel=1e-4)
    assert nbytes == 24 * 2 * (w + acts) == pytest.approx(4.2279e10,
                                                          rel=1e-4)
    t, bound = counts.roofline_seconds(ops, nbytes,
                                       counts.peaks("TPU v5 lite"))
    assert bound == "bandwidth" and t == pytest.approx(nbytes / 819e9)


# ---------------------------------------------------------------------------
# the readers, on made-up events

PRE = "jit(round_step)/while/body/closed_call"
GPRE = "jit(global_step)/while/body/closed_call"
NAMES = {
    "round_step": {
        "fusion.1": f"{PRE}/jvp()/ffn/moe_router/dot_general",
        "fusion.2": f"{PRE}/jvp()/ffn/moe_dispatch/scatter-add",
        "fusion.3": f"{PRE}/transpose(jvp())/ffn/moe_experts/dot_general",
        "fusion.4": f"{PRE}/transpose(jvp())/checkpoint/"
                    "rematted_computation/ffn/moe_experts/mul",
        "fusion.5": f"{PRE}/jvp()/ffn/moe_combine/gather",
        "fusion.6": f"{PRE}/jvp()/attn/dot_general",
        "while.7": f"{PRE}/ffn/moe_experts/while"},
    "global_step": {
        "all-reduce.1": f"{GPRE}/grad_sync/psum",
        "fusion.2": f"{GPRE}/optimizer/sqrt"}}


def _op(inst, start, dur):
    op = inst.split(".")[0]
    return Event(f"%{inst} = f32[2]{{0}} {op}(f32[2]{{0}} %p)", start, dur)


def _trace():
    ops = [_op("fusion.1", 0.0, 0.125), _op("fusion.2", 0.125, 0.25),
           _op("fusion.3", 0.5, 0.5), _op("fusion.4", 1.0, 0.25),
           _op("fusion.5", 1.25, 0.125), _op("fusion.6", 1.5, 0.5),
           _op("while.7", 0.0, 2.0),              # a loop: left out
           _op("all-reduce.1", 2.5, 0.25), _op("fusion.2", 2.75, 0.25)]
    mods = [Event("jit_round_step(1)", 0.0, 2.0),
            Event("jit_global_step(2)", 2.5, 0.5)]
    return Trace([Device(mods, ops)], [Event("bench/iteration", 0.0, 3.0)])


def _ctx(names=NAMES, **kw):
    d = dims.Dims(name="t", program=NAME, layers=1, d_model=4, heads=1,
                  kv_heads=1, head_dim=4, d_ff=2, vocab=8, experts=2,
                  top_k=1)
    job = {"local_steps": 1, "global_steps": 1, "personal_steps": 2,
           "rows": 1, "seq_len": 4}
    return dict({"trace": _trace(), "traffic": {"job": job}, "units": 1,
                 "op_names": names, "dims": d,
                 "peak": {"bf16_flops": 1e4, "hbm_bytes_per_s": 1e4}}, **kw)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, devtrace.__file__.replace("devtrace.py",
                                        f"metrics/{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("name,part", [
    ("a/ffn/moe_experts/dot", "moe_experts"),
    ("a/ffn/moe_dispatch/moe_combine/x", "moe_combine"),
    ("a/grad_sync/psum", "grad_sync"), ("a/ffn/mul", None),
    ("a/moe_expertsx/dot", None), ("", None)])
def test_innermost_part_wins(name, part):
    assert subscopes.part_of(name) == part


def test_part_seconds_join_by_program_and_leave_out_loops():
    s = subscopes.seconds(_trace(), NAMES)
    assert s == {"moe_router": 0.125, "moe_dispatch": 0.25,
                 "moe_experts": 0.75, "moe_combine": 0.125,
                 "grad_sync": 0.25}
    assert subscopes.seconds(_trace(), {}) is None


def test_readers_return_the_hand_summed_times():
    ctx = _ctx()                                  # 4 steps, 1 stage-2 step
    assert _reader("moe_experts_ms.train")(ctx) == pytest.approx(
        1e3 * 0.75 / 4)
    assert _reader("moe_dispatch_ms.train")(ctx) == pytest.approx(
        1e3 * (0.125 + 0.25 + 0.125) / 4)
    assert _reader("grad_sync_ms.train")(ctx) == pytest.approx(250.0)
    # slots 4 (1 row of 4 tokens, top-1): a pass is 3·2·4·4·2 = 192 FLOPs
    # and 2·(3·2·4·2 + 3·4·(4 + 2)) = 240 bytes; three passes, one layer,
    # four steps: max(2304 / 1e4, 2880 / 1e4) s over 0.75 s
    assert _reader("moe_experts_roofline_pct.train")(ctx) == pytest.approx(
        100 * 0.288 / 0.75)
    # the ffn scope holds the four parts: its time is their sum
    assert _reader("ffn_ms.train")(ctx) == pytest.approx(
        _reader("moe_experts_ms.train")(ctx)
        + _reader("moe_dispatch_ms.train")(ctx))


def test_readers_find_nothing_in_a_program_without_the_parts():
    plain = {p: {i: n.replace("moe_", "").replace("grad_sync", "add")
                 for i, n in names.items()} for p, names in NAMES.items()}
    for names in (plain, {}):
        ctx = _ctx(names)
        for name in ("moe_experts_ms.train", "moe_dispatch_ms.train",
                     "moe_experts_roofline_pct.train",
                     "grad_sync_ms.train"):
            assert _reader(name)(ctx) is None, (name, names)
    dense = _ctx(dims=dims.load("deepseek-7b"))
    assert _reader("moe_experts_roofline_pct.train")(dense) is None
