"""Device time by the program's named scopes, and device idle under the
program's own host spans.

The model and the stage programs name their parts with
``repro.obs.tracing.named_scope``: ``attn``, ``ffn``, ``lora``, ``ce``,
``optimizer`` and ``aggregate``.  JAX carries a scope into each HLO
instruction's ``op_name`` metadata (``…/attn/dot_general``; the backward
under ``transpose(jvp(…))/…/attn/…``; recomputed work under
``…/checkpoint/rematted_computation/attn/…``).  A TPU trace names each
operation event by its HLO instruction and carries no metadata, so an
event is joined to its ``op_name`` through the compiled HLO text of the
program it ran in: the program whose execution encloses it on the same
chip.  The traced run lowers the stage programs from the cell's own
state for that (``stage_programs``).  An operation counts toward the innermost of the six
scopes in its ``op_name``, or toward "no scope".  Loop operations, which
enclose others, are left out (as ``devtrace.top_ops`` does), and times
are averaged over the chips.

The program's host spans (``obs.span``: ``fed/round``,
``fed/stage2_global``, ``fed/stage3_personalize``; ``serve/…``) are read
from the same trace file, on the same clock as the device's operations.

A program that names no scope, or opens no such span, gives nothing to
read: the readers then return None.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import sys
import traceback
from collections import defaultdict
from pathlib import Path

import devtrace

SCOPES = ("attn", "ffn", "lora", "ce", "optimizer", "aggregate")
NO_SCOPE = "no scope"
REMAT = "rematted_computation"
PROGRAM_SPAN = re.compile(r"^(fed|serve)/")
TRACE_DIR = Path(__file__).resolve().parents[2] / ".cache" / "bench_trace"
_INST = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
# a TPU trace names an operation by its HLO text ("%fusion.4 = bf16[…]
# fusion(…)"), a CPU trace by the instruction alone ("fusion.4")
_EVENT_INST = re.compile(r"^%?([\w.\-]*)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule ([^ ,]+)")
_METADATA = re.compile(r", metadata=\{[^}]*\}")
_NAME = re.compile(r"%[\w.\-]+")


def scope_of(name: str) -> str:
    """The innermost of ``SCOPES`` among the ``/``-parts of an op name."""
    for part in reversed(name.split("/")):
        if part in SCOPES:
            return part
    return NO_SCOPE


def program(name: str) -> str:
    """A program's name without its ``jit_`` prefix and ``(fingerprint)``:
    the trace's ``jit_round_step(1315…)`` and the HLO text's
    ``HloModule jit_round_step`` both give ``round_step``."""
    name = re.sub(r"\(.*\)$", "", name)
    return name[4:] if name.startswith("jit_") else name


def hlo_op_names(text: str) -> tuple[str, dict]:
    """(program, {instruction: op_name}) of a compiled HLO module's text."""
    out = {}
    for line in text.splitlines():
        m = _INST.match(line)
        o = m and _OP_NAME.search(line)
        if o:
            out[m.group(1)] = o.group(1)
    return program(_MODULE.match(text).group(1)), out


# ---------------------------------------------------------------------------
# the stage programs' op names

def _jitted(fn):
    """The jitted stage program behind the benchmark's span wrapper."""
    if hasattr(fn, "lower"):
        return fn
    return next(c.cell_contents for c in fn.__closure__
                if hasattr(c.cell_contents, "lower"))


def stage_programs(cell) -> dict:
    """{program: {instruction: op_name}} of the three stage programs,
    lowered from the cell's own state as ``FedPipeline.run_pipeline``
    calls them; JAX's caches hand back the executables that ran.  Empty
    for a cell with no pipeline."""
    import jax
    pipe = getattr(cell, "pipe", None)
    if pipe is None:
        return {}
    b = cell.data[0]
    rs, gs, ps = (_jitted(f) for f in (pipe.round_step, pipe.global_step,
                                       pipe.personal_step))
    low = rs.lower(cell.base, cell.adapters, cell.opt_state, cell.step,
                   b["batch"], None, None)
    agg = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        low.out_info[2], low.compile().output_shardings[2])
    lows = [low,
            gs.lower(cell.base, agg, cell.adapters, b["server"], None),
            ps.lower(cell.base, cell.adapters, b["personal"], None)]
    return dict(ran_op_names(lo, lo.compile().as_text()) for lo in lows)


def ran_op_names(lowered, ran: str) -> tuple[str, dict]:
    """(program, {instruction: op_name}) of the executable that ran, whose
    compiled HLO text is ``ran``.  JAX's compile caches key a program
    without its metadata, so an executable compiled by another version of
    the program that differs only in its scopes carries that version's op
    names.  When ``ran`` names none of the scopes, ``lowered`` (this
    version) is compiled afresh and its op names are matched to ``ran``'s
    instructions line by line; if the two are not one program apart from
    metadata and numbering, ``ran``'s own names stand."""
    prog, names = hlo_op_names(ran)
    if any(scope_of(n) != NO_SCOPE for n in names.values()):
        return prog, names
    matched = _matched(ran, _compile_fresh(lowered).as_text())
    return prog, names if matched is None else matched


def _compile_fresh(lowered):
    """Compile ``lowered`` past JAX's persistent cache and the lowering's
    own compiled executable (which any compiler option skips)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return lowered.compile({"xla_dump_disable_metadata": False})
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _matched(ran: str, fresh: str) -> dict | None:
    """{instruction of ``ran``: op_name of the same line of ``fresh``}, when
    the two instruction lists are equal without their metadata and with
    every %name numbered by first appearance; else None."""
    a, b = ([ln for ln in t.splitlines() if _INST.match(ln)]
            for t in (ran, fresh))
    if _canonical(a) != _canonical(b):
        return None
    out = {}
    for la, lb in zip(a, b):
        o = _OP_NAME.search(lb)
        if o:
            out[_INST.match(la).group(1)] = o.group(1)
    return out


def _canonical(lines) -> list:
    ids: dict = {}
    return [_NAME.sub(lambda m: ids.setdefault(m.group(0), f"%{len(ids)}"),
                      _METADATA.sub("", ln)) for ln in lines]


def op_names(ctx) -> dict:
    """``ctx["op_names"]`` if given, else the stage programs' op names
    (none, with the reason on stderr, if they cannot be lowered: a reader
    finds nothing then, and the run goes on)."""
    if "op_names" not in ctx:
        try:
            ctx["op_names"] = stage_programs(ctx["cell"])
        except Exception:           # noqa: BLE001 - a reader must not fail
            print("scopes: no op names:", file=sys.stderr)
            traceback.print_exc()
            ctx["op_names"] = {}
    return ctx["op_names"]


# ---------------------------------------------------------------------------
# operation time by scope

def _kept(ev) -> bool:
    op = devtrace.op_family(ev.name).split(" ")[0].split(":")[0]
    return op not in devtrace.CONTAINERS


def _enclosing(modules):
    """op start → the program executing then on that chip ("" if none)."""
    mods = sorted(modules, key=lambda m: m.start)
    starts = [m.start for m in mods]

    def at(t):
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t <= mods[i].start + mods[i].dur:
            return program(mods[i].name)
        return ""
    return at


def breakdown(trace, names: dict) -> dict | None:
    """{"scopes": {scope: seconds}, "remat": seconds, "total": seconds,
    "families": {op family: seconds} of "no scope"}: operation time in the
    traced window, averaged over the chips.  ``names``: {program:
    {instruction: op_name}}.  None when no operation joins to an op
    name."""
    named = False
    scopes: dict = defaultdict(float)
    fams: dict = defaultdict(float)
    remat = 0.0
    for d in trace.devices:
        at = _enclosing(d.modules)
        for ev in devtrace.in_window(d.ops, trace):
            if not _kept(ev):
                continue
            inst = _EVENT_INST.match(ev.name).group(1)
            name = names.get(at(ev.start), {}).get(inst, "")
            named = named or bool(name)
            s = scope_of(name)
            scopes[s] += ev.dur
            if s == NO_SCOPE:
                fams[devtrace.op_family(ev.name)] += ev.dur
            if REMAT in name.split("/"):
                remat += ev.dur
    if not named:
        return None
    n = len(trace.devices)
    return {"scopes": {k: v / n for k, v in scopes.items()},
            "remat": remat / n,
            "total": sum(scopes.values()) / n,
            "families": {k: v / n for k, v in sorted(
                fams.items(), key=lambda kv: -kv[1])}}


def of(ctx) -> dict | None:
    """The traced run's breakdown, kept in ``ctx`` for the next reader."""
    if "breakdown" not in ctx:
        ctx["breakdown"] = breakdown(ctx["trace"], op_names(ctx))
    return ctx["breakdown"]


def steps(ctx) -> int:
    """Optimizer steps a chip takes in the traced units."""
    job = ctx["traffic"]["job"]
    return ctx["units"] * (job["local_steps"] + job["global_steps"]
                           + job["personal_steps"])


def scope_ms_per(ctx, scope: str, per: int):
    """Milliseconds of ``scope``'s operations per ``per`` (steps or
    rounds); None when the program names none of the scopes."""
    b = of(ctx)
    if b is None or not any(b["scopes"].get(s) for s in SCOPES):
        return None
    return 1e3 * b["scopes"].get(scope, 0.0) / per


# ---------------------------------------------------------------------------
# program spans

def load_program_spans(path: str) -> list:
    """The host events named ``fed/…`` or ``serve/…`` in the newest
    ``*.xplane.pb`` under ``path`` (or the file itself), as ``Event``s on
    the trace's clock."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not files:
            return []
        path = files[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if devtrace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if PROGRAM_SPAN.match(ev.name):
                    out.append(devtrace.Event(ev.name, ev.start_ns * 1e-9,
                                              ev.duration_ns * 1e-9))
    return out


def program_spans(ctx) -> list:
    """The traced run's program spans: ``ctx["program_spans"]`` if given,
    else read from the trace the run just wrote under ``TRACE_DIR``."""
    if "program_spans" not in ctx:
        ctx["program_spans"] = (load_program_spans(str(TRACE_DIR))
                                if TRACE_DIR.is_dir() else [])
    return ctx["program_spans"]


def idle_under(trace, spans) -> float:
    """Seconds of the window in which a chip runs no operation while one
    of ``spans`` is open, averaged over the chips."""
    lo, hi = trace.window()
    opened = devtrace.clip(devtrace.union(
        (s.start, s.start + s.dur) for s in spans), lo, hi)
    total = 0.0
    for d in trace.devices:
        for gs, ge in devtrace.gaps(d.ops, lo, hi):
            total += sum(e - s for s, e in devtrace.clip(opened, gs, ge))
    return total / len(trace.devices)
