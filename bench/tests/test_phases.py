"""Attribution of device time to the step's phases (``bench/phases.py``)
and its six readers, on a fixed HLO snippet and a synthesised trace; and
the program's gate counter against the reference's redraw of the gate."""
import json

import pytest

from bench import common, phases
from bench import trace as trace_lib

MS = 1_000_000  # ns
BODY = "jit(chunk)/while/body/closed_call/"
HLO = f"""HloModule jit_chunk, is_scheduled=true

%fused_computation.12 (param_0: f32[8,2]) -> f32[8,2] {{
  %param_0 = f32[8,2]{{1,0}} parameter(0)
  ROOT %add.3 = f32[8,2]{{1,0}} add(f32[8,2]{{1,0}} %param_0, f32[8,2]{{1,0}} %param_0), metadata={{op_name="{BODY}funcsne.forces_update/add" source_file="funcsne.py" source_line=760}}
}}

ENTRY %main.9 (p.1: f32[8,2]) -> f32[8,2] {{
  %p.1 = f32[8,2]{{1,0}} parameter(0)
  %fusion.12 = f32[8,2]{{1,0}} fusion(f32[8,2]{{1,0}} %p.1), kind=kLoop, calls=%fused_computation.12, metadata={{op_name="{BODY}funcsne.forces_update/add" source_file="funcsne.py" source_line=760}}
  %knn_merge_cand.3 = f32[8,2]{{1,0}} custom-call(f32[8,2]{{1,0}} %fusion.12), custom_call_target="tpu_custom_call", metadata={{op_name="{BODY}cond/branch_1_fun/funcsne.hd_refine/knn_merge_cand" source_file="ops.py" source_line=88}}
  %sort.1 = f32[8,2]{{1,0}} sort(f32[8,2]{{1,0}} %knn_merge_cand.3), dimensions={{1}}, to_apply=%cmp, metadata={{op_name="{BODY}funcsne.ld_refine/jit(sort)/sort" source_file="knn.py" source_line=40}}
  %copy.5 = f32[8,2]{{1,0}} copy(f32[8,2]{{1,0}} %sort.1), metadata={{op_name="{BODY}funcsne.hd_refine_extra/add"}}
  ROOT %tuple.9 = (f32[8,2]{{1,0}}) tuple(f32[8,2]{{1,0}} %copy.5)
}}
"""


def test_phase_map_of_an_hlo_snippet():
    pmap = {sig[0]: (sig, phase)
            for sig, phase in phases.phase_map(HLO).items()}
    assert pmap["fusion.12"] == (("fusion.12", "f32[8,2]", "fusion"),
                                 "forces_update")
    assert pmap["add.3"][1] == "forces_update"          # inside the fusion
    assert pmap["knn_merge_cand.3"][1] == "hd_refine"   # a cond branch
    assert pmap["sort.1"][1] == "ld_refine"             # jit(sort) in a phase
    assert pmap["copy.5"][1] is None                    # no such scope
    assert pmap["p.1"][1] is None and pmap["tuple.9"][1] is None
    assert phases.phase_of('metadata={op_name="a/funcsne.hd_refine/'
                           'jit(f)/funcsne.sigma_refresh/x"}') \
        == "sigma_refresh"                              # the innermost one
    assert phases.signature(
        "%knn_merge_cand.3 = (s32[8,32]{1,0}, f32[8,32]{1,0:T(8,128)}) "
        'custom-call(f32[8]{0} %a), custom_call_target="tpu_custom_call"') \
        == ("knn_merge_cand.3", "(s32[8,32], f32[8,32])", "custom-call")


def _event(name, shape="f32[8]"):
    """An op event's name as a TPU trace gives it: the instruction's text
    with layouts, without metadata."""
    return f"%{name} = {shape}{{0:T(128)}} fusion(f32[8]{{0}} %a), kind=kLoop"


# (instruction, phase, start ms, end ms): window [0, 100) ms, init span
# [0, 10) with one op; three steps: HD (fired) [10, 20), sigma [20, 25)
# (a loop [20, 25) over a body op [21, 24)), LD [25, 35), forces
# [35, 50); LD [50, 60), forces [60, 75); HD [75, 80), LD [80, 85),
# forces [85, 95).  An unscoped scan op encloses each step.
STEPS = [("fusion.1", None, 1, 9),
         ("while.1", None, 10, 50), ("while.1", None, 50, 75),
         ("while.1", None, 75, 95),
         ("knn_merge_cand.3", "hd_refine", 10, 18),
         ("fusion.4", "hd_refine", 18, 20),
         ("while.2", "sigma_refresh", 20, 25),
         ("fusion.5", "sigma_refresh", 21, 24),
         ("knn_merge_cand.4", "ld_refine", 25, 35),
         ("ne_forces_gather_pallas.2", "forces_update", 35, 50),
         ("knn_merge_cand.4", "ld_refine", 50, 60),
         ("ne_forces_gather_pallas.2", "forces_update", 60, 75),
         ("knn_merge_cand.3", "hd_refine", 75, 80),
         ("knn_merge_cand.4", "ld_refine", 80, 85),
         ("ne_forces_gather_pallas.2", "forces_update", 85, 95)]


def _program(with_scopes=True):
    """phase_map of a module holding the steps' instructions."""
    lines = {}
    for name, phase, _, _ in STEPS:
        scope = f"funcsne.{phase}/" if phase and with_scopes else ""
        lines[name] = (_event(name).replace("{0:T(128)}", "{0}")
                       + f', metadata={{op_name="{BODY}{scope}mul"}}')
    return phases.phase_map("\n".join(lines.values()))


def _trace():
    ops = {0: [(_event(n), s * MS, e * MS) for n, _, s, e in STEPS]}
    spans = [("bench.window", 0, 100 * MS), ("bench.init", 0, 10 * MS),
             ("bench.frame", 10 * MS, 50 * MS),
             ("bench.frame", 50 * MS, 75 * MS),
             ("bench.frame", 75 * MS, 100 * MS)]
    return trace_lib.Trace(ops, spans)


def test_phase_seconds_runs_and_holding_spans():
    tr = _trace()
    evs = phases.events(tr, _program(), holes=tr.spans_named("init"))
    assert min(s for _, s, _ in evs) == 10 * MS     # init op left out
    assert [phases.seconds(evs, p) for p in phases.PHASES] == \
        pytest.approx([0.015, 0.005, 0.025, 0.040])
    assert [phases.runs(evs, p) for p in phases.PHASES] == [2, 1, 3, 3]
    assert phases.per_run_ms(evs, "hd_refine") == pytest.approx(7.5)
    assert phases.per_iteration_ms(evs, "ld_refine", 3) == \
        pytest.approx(25 / 3)
    frames = tr.spans_named("frame")
    assert phases.per_holding_span_ms(evs, frames, "hd_refine") == \
        pytest.approx(7.5)
    assert phases.per_holding_span_ms(evs, frames, "sigma_refresh") == \
        pytest.approx(5.0)
    # the window clips: an event across its end counts up to the end
    tr.ops[0].append((_event("ne_forces_gather_pallas.2"), 98 * MS,
                      130 * MS))
    assert phases.seconds(phases.events(tr, _program()),
                          "forces_update") == pytest.approx(0.042)


def test_an_op_of_another_program_takes_no_phase():
    """An event that shares an instruction name with the step program but
    not its result shape (or opcode) is another program's op."""
    tr = trace_lib.Trace({0: [(_event("fusion.4"), 0, 10),
                              (_event("fusion.4", "pred[4,2]"), 10, 12),
                              (_event("fusion.4").replace(" fusion(",
                                                          " sort("), 12, 14)]},
                         [("bench.window", 0, 100)])
    assert [p for p, _, _ in phases.events(tr, _program())] == \
        ["hd_refine", None, None]


READERS = {"hd_refine_ms_per_fire.batch": 7.5,
           "sigma_refresh_ms_per_fire.batch": 5.0,
           "ld_refine_ms_per_iter.batch": 25 / 3,
           "forces_update_ms_per_iter.batch": 40 / 3,
           "hd_refine_ms_per_fire.frames": 7.5,
           "sigma_refresh_ms_per_fire.frames": 5.0}


class _Run:
    config = {"n": 8}
    chips = 1

    def __init__(self, trace, iterations=3):
        self.trace, self.iterations = trace, iterations


def _reader(name):
    return common.load_module(common.BENCH / "metrics" / f"{name}.py")


@pytest.fixture
def program(monkeypatch):
    """The window program the readers compile, replaced by the steps'."""
    asked, table = [], {"program": _program()}

    def window_program(config, traffic, chips):
        asked.append(traffic)
        return table["program"]
    monkeypatch.setattr(phases, "window_program", window_program)
    return asked, table


@pytest.mark.parametrize("name", sorted(READERS))
def test_phase_readers(name, program):
    assert _reader(name).read(_Run(_trace())) == \
        pytest.approx(READERS[name])
    assert program[0] == [name.rsplit(".", 1)[1]]


@pytest.mark.parametrize("name", sorted(READERS))
def test_phase_readers_without_their_phase_or_count(name, program):
    read = _reader(name).read
    phase = name.split("_ms_")[0]
    tr = _trace()
    tr.ops[0] = [ev for ev in tr.ops[0]
                 if _program().get(phases.signature(ev[0])) != phase]
    assert read(_Run(tr)) is None
    if "per_iter" in name:
        assert read(_Run(_trace(), iterations=0)) is None
    # a program without the scopes (the parent of the scopes) maps nothing
    program[1]["program"] = _program(with_scopes=False)
    assert read(_Run(_trace())) is None
    # a window with no op event (a CPU run) compiles nothing
    program[0].clear()
    assert read(_Run(trace_lib.Trace({}, _trace().spans))) is None
    assert program[0] == []


def test_window_program_of_each_cell_holds_every_phase():
    """The program the readers compile is the cell's step program (CPU,
    tiny n): every phase owns instructions in it."""
    for workload, traffic in (("atlas-262k.batch", "batch"),
                              ("mnist-70k.frames", "frames")):
        config = common.find_cell(workload)["config"]
        config["n"] = 512
        table = phases.window_program(json.dumps(config, sort_keys=True),
                                      traffic)
        assert set(table.values()) - {None} == set(phases.PHASES), workload


def test_program_counts_the_gate_the_reference_redraws():
    """The batch mix at a tiny size, step by step: the program's
    ``ChunkMetrics.hd_fires`` summed over the steps equals the count of
    steps whose gate the reference redraws as fired."""
    import jax
    from repro.core import funcsne

    from bench import generator
    spec = common.find_cell("atlas-262k.batch")
    spec["config"]["n"] = 1024
    cell = generator.make(jax, funcsne, spec, 2 ** 31 + 7, None)
    cell.setup()
    st, hp = cell.init(), cell.hp_device(cell.hp)
    hd = sigma = 0
    emas = []
    for _ in range(12):
        st, _, m = cell.prog(st, cell.X, hp)
        hd += int(m.hd_fires)
        sigma += int(m.sigma_fires)
        emas.append(float(m.ema_new_frac))
    fires = cell.gate_fires(st, emas)
    assert hd == sum(fires) and 0 < hd
    every = cell.cfg.sigma_refresh_every
    assert sigma == len(range(0, 12, every))
