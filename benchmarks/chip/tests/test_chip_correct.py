"""``correct`` decided end to end at a small size on the CPU.

A whole run (set-up, window, peak memory, the reference check, the result
line) is driven through ``run.main`` with the look for a chip skipped, a
small configuration of the cell's own kind in place of the real one and
limits read at that size (tests/data/small_limits.json).  The sound
program has to come out correct; with the timed path broken underneath it
has to come out not correct, once per fault the cell can have; and the
control (the reference computed in fp8, put in the program's place) has
to fail at least one number.
"""
import copy
import json

import jax
import numpy as np
import pytest

import checks
import dims
import reference
import run

TINY = {
    "dense": dict(name="tiny-dense", program="deepseek-7b", layers=2,
                  d_model=128, heads=4, kv_heads=4, head_dim=32, d_ff=256,
                  vocab=512),
    "moe": dict(name="tiny-moe", program="qwen3-moe-30b-a3b", layers=2,
                d_model=128, heads=4, kv_heads=2, head_dim=32, d_ff=64,
                vocab=512, experts=8, top_k=2, capacity_factor=1.25,
                qk_norm=True, rope_theta=1e6),
}
# cells of each kind: (size, traffic); they need not be in BENCHMARK.json
CELLS = {"ds7b-fedpipe": ("dense", "fedpipe"),
         "qwen3moe-fedpipe": ("moe", "fedpipe")}
SMALL = json.loads((dims.HERE / "tests" / "data" / "small_limits.json")
                   .read_text())


def small_limits(workload):
    return SMALL[CELLS[workload][0]]


def cell(workload):
    """(BENCHMARK.json with this cell as its only one, the cell, its
    traffic at the small size)."""
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    spec = {"name": workload, "config": CELLS[workload][0],
            "traffic": CELLS[workload][1], "chips": 1}
    bench["workloads"] = [spec]
    traffic = json.loads((dims.HERE / "traffic" / f"{spec['traffic']}.json")
                         .read_text())
    return bench, spec, small_traffic(traffic)


def small_traffic(traffic):
    t = copy.deepcopy(traffic)
    t["job"]["seq_len"] = 128
    t["data_iterations"] = 3
    return t


@pytest.fixture
def small_run(monkeypatch, capsys):
    """Run a cell through run.main at the small size; returns the result
    line as a dict."""
    def go(workload, seed=11):
        monkeypatch.setattr(run, "load_limits", small_limits)
        monkeypatch.setattr(run, "load_cell", cell)
        monkeypatch.setattr(run, "need_chips",
                            lambda n: jax.devices()[:n])
        monkeypatch.setattr(run, "compile_cache", lambda: "off")
        monkeypatch.setattr(dims, "load",
                            lambda name: dims.Dims(**TINY[name]))
        capsys.readouterr()
        assert run.main(["--workload", workload, "--seed", str(seed),
                         "--seconds", "0.01", "--trace", "0"]) == 0
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    return go


@pytest.mark.parametrize("workload", list(CELLS))
def test_sound_program_is_correct(small_run, workload):
    res = small_run(workload)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["device"]["count"] == 1 and res["attempted"] >= 1


def _unchanged(monkeypatch):
    import repro.launch.train as T
    monkeypatch.setattr(T, "apply_updates", lambda p, u: p)


def _half_batch(monkeypatch):
    import repro.models.model as M
    real = M.loss_and_metrics

    def half(params, batch, cfg, **kw):
        n = batch["tokens"].shape[0] // 2
        return real(params, {k: v[:n] for k, v in batch.items()}, cfg, **kw)

    monkeypatch.setattr(M, "loss_and_metrics", half)


@pytest.mark.parametrize("workload,fault", [
    ("ds7b-fedpipe", _unchanged), ("ds7b-fedpipe", _half_batch),
    ("qwen3moe-fedpipe", _unchanged), ("qwen3moe-fedpipe", _half_batch)],
    ids=lambda x: getattr(x, "__name__", x))
def test_broken_timed_path_is_not_correct(small_run, monkeypatch, workload,
                                          fault):
    fault(monkeypatch)
    res = small_run(workload)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("workload", ["ds7b-fedpipe", "qwen3moe-fedpipe"])
def test_control_fails_a_number(workload):
    """The reference in fp8 in the program's place, against the float32
    reference, at the small size: one number out of its limit."""
    _, _, t = cell(workload)
    d = dims.Dims(**TINY[CELLS[workload][0]])
    key = jax.random.PRNGKey(3)
    import weights
    base = weights.make_base(key, d)
    theta0 = weights.make_adapters(jax.random.fold_in(key, 1), d, 1)
    job, S = t["job"], t["job"]["seq_len"]
    toks = lambda k, n: {"tokens": jax.random.randint(
        jax.random.fold_in(key, k), n + (S,), 5, d.vocab),
        "loss_mask": np.ones(n + (S,), np.float32)}
    args = (base, theta0, toks(2, (1, 2)), toks(3, (2,)), toks(4, (1, 8)),
            d, job)
    ref = jax.device_get(reference.pipeline(*args))
    ctl = jax.device_get(reference.pipeline(*args, prec="fp8"))
    ok, rows = checks.verdict(checks.train_numbers(theta0, ctl, ref),
                              small_limits(workload))
    assert not ok, rows


def test_verdict_compares_the_numbers_that_have_limits():
    numbers = {"ce1": (1e-5, ""), "grad1": (0.5, "leaf")}
    assert checks.verdict(numbers, {"ce1": 1e-4, "grad1": None})[0]
    assert not checks.verdict(numbers, {"ce1": 1e-4, "grad1": 0.1})[0]
    assert not checks.verdict(numbers, {"ce1": None, "grad1": None})[0]
    assert not checks.verdict({"ce1": (float("nan"), "")}, {"ce1": 1.0})[0]
