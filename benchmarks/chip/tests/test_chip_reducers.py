"""Trace reductions: interval union, idle share, per-program device time,
idle gaps named by host spans, roofline share — on made-up events and on
a small trace recorded on the CPU (tests/data/cpu_trace.xplane.pb: three
bench/iteration spans, each around a round_step and a personal_step)."""
from pathlib import Path

import pytest

import counts
import devtrace as t
from devtrace import Device, Event, Trace

DATA = Path(__file__).resolve().parent / "data" / "cpu_trace.xplane.pb"


def test_union_merges_overlaps_and_keeps_gaps():
    assert t.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]
    assert t.clip([(0, 2.5), (3, 4)], 1, 3.5) == [(1, 2.5), (3, 3.5)]


def test_busy_and_gaps_within_a_window():
    evs = [Event("a", 1.0, 1.0), Event("b", 1.5, 1.0), Event("c", 4.0, 0.5)]
    assert t.busy_seconds(evs, 0.0, 5.0) == pytest.approx(2.0)
    assert t.gaps(evs, 0.0, 5.0) == [(0.0, 1.0), (2.5, 4.0), (4.5, 5.0)]


def _trace():
    ops = [Event("fusion.1", 0.0, 1.0), Event("fusion.7", 1.0, 1.0),
           Event("all-reduce.3", 3.0, 0.5), Event("_bgmv_mag_kernel", 4.0,
                                                  0.25)]
    mods = [Event("jit_round_step(1)", 0.0, 2.0),
            Event("jit_personal_step(2)", 3.0, 1.25)]
    spans = [Event("bench/iteration", 0.0, 5.0),
             Event("bench/stage3", 2.2, 2.8)]
    return Trace([Device(mods, ops), Device(mods, ops[:2])], spans)


def test_idle_share_averages_the_chips():
    busy, win = t.idle_share(_trace())
    assert win == pytest.approx(5.0)
    assert busy == pytest.approx((2.75 + 2.0) / 2)


def test_module_time():
    tr = _trace()
    assert t.module_seconds(tr, "round_step") == (pytest.approx(2.0), 1)
    assert t.module_seconds(tr, "personal") == (pytest.approx(1.25), 1)


def test_top_ops_add_up_instances_and_gaps_take_the_innermost_span():
    tr = _trace()
    assert t.top_ops(tr)[0] == ["fusion", pytest.approx(2.0)]
    g = t.idle_gaps(tr)
    assert g[0] == ["bench/stage3", pytest.approx(1.0)]       # 2.0 .. 3.0
    assert ["bench/iteration", pytest.approx(0.0)] not in g


def test_roofline_share_names_its_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.roofline_seconds(1000, 10, peak) == (10.0, "compute")
    assert counts.roofline_seconds(10, 1000, peak) == (100.0, "bandwidth")


def test_recorded_cpu_trace():
    tr = t.load(str(DATA), cpu=True)
    assert [s.name for s in tr.spans].count("bench/iteration") == 3
    secs, calls = t.module_seconds(tr, r"^round_step$")
    assert calls >= 3 and secs > 0
    busy, win = t.idle_share(tr)
    assert 0 < busy < win
    assert t.top_ops(tr)[0][0] == "dot_general"
    gaps = t.idle_gaps(tr)
    assert gaps and all(name.startswith("bench/") or name == "no span"
                        for name, _ in gaps)
    # the sleep at the end of each iteration is the longest idle stretch
    assert gaps[0][0] == "bench/iteration" and gaps[0][1] > 1e-3

