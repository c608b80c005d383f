"""BENCHMARK.json against the format the benchmark's contract sets, and
every file a cell needs found by its name."""
import json
import re

import pytest

import dims

ROOT = dims.HERE.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH = re.compile(r"(hidden_size|intermediate|latent|state|proj|head_dim"
                   r"|_dim$|_rank$|expansion|per_tok)", re.I)


def _text(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert all(_text(w) for w in BENCH["command"])
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p


def test_names_units_and_texts():
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[kind]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for w in BENCH["workloads"]:
        assert _text(w["why"]) and w["chips"] in (1, 4)
    for c in BENCH["configs"]:
        assert _text(c["why"]) and _text(c["source"])


def test_configs_files_and_reductions():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        f = json.loads((ROOT / c["file"]).read_text())
        assert f["source"] == c["source"] and f["name"] == c["name"]
        assert set(c["reduced"]) == set(f["reduced"])
        assert not [k for k in c["reduced"] if WIDTH.search(k)]
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])


def test_every_cell_finds_its_files_and_metrics():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    pairs = set()
    for w in BENCH["workloads"]:
        pairs.add((w["config"], w["traffic"]))
        assert (dims.HERE / "traffic" / f"{w['traffic']}.json").is_file()
        assert (dims.HERE / "limits" / f"{w['name']}.json").is_file()
        mine = [m for m in BENCH["end_to_end"]
                if w["name"] in m.get("workloads", [w["name"]])]
        assert len(mine) >= 2
        layer = [m for m in BENCH["per_layer"]
                 if w["name"] in m["workloads"]]
        assert layer
        for m in layer:
            assert m["moves"] in {x["name"] for x in mine}
    assert len(pairs) == len(BENCH["workloads"])
    for m in BENCH["per_layer"]:
        assert (dims.HERE / "metrics" / f"{m['name']}.py").is_file()
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _text(m["layer"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_limits_are_set(w):
    """Every compared number has a finite limit above 0; a number left
    out (null: no control or fault separates it) is named with its
    readings in the file's ``about``."""
    import math
    f = json.loads((dims.HERE / "limits" / f"{w['name']}.json").read_text())
    lim = f["limits"]
    set_ = [v for v in lim.values() if v is not None]
    assert set_ and all(math.isfinite(v) and v > 0 for v in set_), lim
    assert all(k in f["about"] for k, v in lim.items() if v is None)


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(dims.HERE / "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "TPU" in p.stderr
