"""Device time of a traced window by phase of the FUnc-SNE step.

The program traces each phase of ``funcsne_step`` under one
``jax.named_scope``: ``funcsne.hd_refine``, ``funcsne.sigma_refresh``,
``funcsne.ld_refine``, ``funcsne.forces_update``.  The scope is HLO
metadata: every instruction the phase emits carries it in
``metadata={op_name=".../funcsne.<phase>/..."}``, in the branches of the
step's ``lax.cond`` too.  An instruction belongs to the innermost such
component of its ``op_name``, or to no phase (the scan, the gate, the
conditionals themselves, the chunk's metrics).

On a TPU an op event's name is its instruction's text without the
metadata (``%fusion.12 = f32[262144,2]{...} fusion(...), kind=...``), and
no stat of the event carries the ``op_name`` either.  So the reader
compiles the window's step program again from the cell's configuration
(the compile cache answers it in well under a second) and gives each
event the phase of the instruction with its name, result shape and
opcode: shape and opcode keep an op of another program that shares a
name (the frames cell's recall probe, the batch window's init) out of
the step's phases.

A phase's time is the union of its events' intervals inside the window,
so control-flow ops that enclose other ops of the phase are not counted
twice.  A gated phase runs once per step whose gate opened it: its runs
are the maximal stretches of its events, in start order, between events
of the other phases.
"""
from __future__ import annotations

import functools
import json
import re

from bench import common
from bench import trace as trace_lib

PHASES = ("hd_refine", "sigma_refresh", "ld_refine", "forces_update")
SCOPE = re.compile(r"(?:^|/)funcsne\.(" + "|".join(PHASES) + r")(?=/|$)")
OP_NAME = re.compile(r'op_name="([^"]*)"')
LAYOUT = re.compile(r"\{[^{}]*\}")


def phase_of(text: str):
    """Innermost ``funcsne.<phase>`` of one instruction's ``op_name``
    metadata, or None."""
    m = OP_NAME.search(text)
    found = SCOPE.findall(m.group(1)) if m else []
    return found[-1] if found else None


def signature(text: str):
    """(name, result shape without layouts, opcode) of one instruction's
    text, or None when it is not an instruction."""
    m = trace_lib.INSTRUCTION.match(text)
    return (m.group(1), LAYOUT.sub("", m.group(2)), m.group(3)) if m \
        else None


def phase_map(hlo_text: str) -> dict:
    """{signature: phase or None} for every instruction of an HLO module's
    text (``compiled.as_text()``)."""
    out = {}
    for line in hlo_text.splitlines():
        sig = signature(line)
        if sig:
            out[sig] = phase_of(line)
    return out


@functools.lru_cache(maxsize=4)
def window_program(config_json: str, traffic: str, chips: int = 1) -> dict:
    """:func:`phase_map` of the step program the window of a cell with
    this configuration, traffic mix and number of chips runs, as
    ``bench/generator.py`` builds it.  A mesh program's text is one
    device's part of it, as each device's trace events name it."""
    import jax
    import jax.numpy as jnp
    from repro.core import funcsne

    from bench import generator
    c = json.loads(config_json)
    tr = common.load_json(common.BENCH / "traffic" / f"{traffic}.json")
    cfg = funcsne.FuncSNEConfig(n_points=c["n"], dim_hd=c["dim_hd"],
                                dim_ld=c["dim_ld"], **c["funcsne"])
    X = jax.ShapeDtypeStruct((c["n"], c["dim_hd"]), jnp.float32)
    st = jax.eval_shape(lambda k, X: funcsne.init_state(
        k, X, cfg, validate=False), jax.random.PRNGKey(0), X)
    hp = funcsne.HParams(*[jax.ShapeDtypeStruct((), jnp.float32)]
                         * len(funcsne.HParams._fields))
    if tr["kind"] == "chunked":
        prog = funcsne.make_chunked_step(
            cfg, int(tr["iters_per_dispatch"]),
            schedule=funcsne.default_schedule, n_iter=int(tr["n_iter"]))
    elif tr["kind"] == "mesh_chunked":
        prog, _, x_sharding, st_sharding = generator.mesh_program(
            jax, funcsne, cfg, tr, jax.devices(), chips)
        X = jax.ShapeDtypeStruct(X.shape, X.dtype, sharding=x_sharding)
        st = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=st_sharding), st)
    else:
        prog = funcsne.make_step(cfg)
    return phase_map(prog.lower(st, X, hp).compile().as_text())


def events(trace, program: dict, holes=()) -> list:
    """(phase, start, end) of device 0's op events in the window, clipped
    to it and sorted by start, leaving out events that lie wholly inside
    one of ``holes``; each event takes the phase ``program`` (a
    :func:`phase_map`) gives its signature."""
    lo, hi = trace.window()
    out = []
    for name, s, e in trace.ops.get(0, []):
        if e <= lo or s >= hi or any(a <= s and e <= b for a, b in holes):
            continue
        out.append((program.get(signature(name)), max(s, lo), min(e, hi)))
    return sorted(out, key=lambda ev: (ev[1], -ev[2]))


def run_events(run, traffic: str, holes=()) -> list:
    """:func:`events` of a traced run of the cell whose traffic mix is
    ``traffic`` (nothing to map, and nothing compiled, when the window
    holds no op event)."""
    lo, hi = run.trace.window()
    if not any(e > lo and s < hi for _, s, e in run.trace.ops.get(0, [])):
        return []
    program = window_program(json.dumps(run.config, sort_keys=True),
                             traffic, run.chips)
    return events(run.trace, program, holes=holes)


def seconds(evs, phase: str) -> float:
    """Device seconds in ``phase``: the union of its events' intervals."""
    return trace_lib.length(trace_lib.union(
        (s, e) for p, s, e in evs if p == phase)) * 1e-9


def runs(evs, phase: str) -> int:
    """Separate executions of ``phase``: stretches of its events, in start
    order, with no event of another phase between them."""
    n, last = 0, None
    for p, _, _ in evs:
        if p is None:
            continue
        if p == phase and last != phase:
            n += 1
        last = p
    return n


def per_run_ms(evs, phase: str):
    """Milliseconds of device time per execution of ``phase``, or None
    when the window holds none."""
    n = runs(evs, phase)
    return 1e3 * seconds(evs, phase) / n if n else None


def per_iteration_ms(evs, phase: str, iterations: int):
    """Milliseconds of device time in ``phase`` per iteration, or None
    when the window holds no iteration or no event of the phase."""
    if not iterations or all(p != phase for p, _, _ in evs):
        return None
    return 1e3 * seconds(evs, phase) / iterations


def per_holding_span_ms(evs, spans, phase: str):
    """Milliseconds of device time in ``phase`` per span of ``spans`` in
    which one of its events starts, or None when there is none."""
    starts = [s for p, s, _ in evs if p == phase]
    held = sum(any(a <= s < b for s in starts) for a, b in spans)
    return 1e3 * seconds(evs, phase) / held if held else None
