"""The readings built on the program's own phases (``bench/harness/
phases.py``, the readers that use it, ``bench/phases.py``): attribution of
idle time to the innermost phase, the staging subtraction, ``None`` where
a run holds no phases, and the existing reduction left as it was."""
import copy
import json
from pathlib import Path

import jax
import pytest

from bench.harness import core, phases, runner
from bench.harness.readings import Readings
from bench.harness.trace import TraceData

SMALL = Path(__file__).parent / "data" / "small.xplane.pb"
NEW = ("span.orchestrate_ms", "span.cohort_build_ms", "span.merge_ms",
       "span.evaluate_ms", "staging_ms", "h2d_mb", "host_idle_ms")
MS = 1e-3


def _readers():
    mods = core.metric_modules()
    return {n: mods[n] for n in NEW}


def _program():
    """Two region rounds of 100 ms and a merge of 20 ms, in seconds."""
    ev = []
    for k, t in enumerate((0.0, 0.1)):
        ev += [("repro.region.step", t, t + 0.1),
               ("repro.region.orchestrate", t, t + 0.01),
               ("repro.cohort.build", t + 0.01, t + 0.02),
               ("repro.cohort.dispatch", t + 0.02, t + 0.025),
               ("repro.cohort.wait", t + 0.025, t + 0.085),
               ("repro.region.evaluate", t + 0.085, t + 0.095)]
    ev.append(("repro.engine.merge", 0.2, 0.22))
    return ev


def _trace(program):
    # the device: the local update inside each wait (30 ms of its 60),
    # the aggregate and evaluation inside each evaluate, a merge op
    ops, modules = [], []
    for t in (0.0, 0.1):
        modules += [("jit_cohort_local_update(1)", t + 0.05, t + 0.08),
                    ("jit__fedavg_multi_impl(2)", t + 0.086, t + 0.09),
                    ("jit_evaluate(3)", t + 0.09, t + 0.094)]
    modules.append(("jit_merge(4)", 0.21, 0.215))
    ops = [(n.split("(")[0] + "/fusion", s, e) for n, s, e in modules]
    base = TraceData({0: ops}, {0: modules},
                     [("bench.window", 0.0, 0.25)], (0.0, 0.25))
    return phases.ProgramTrace(base, program)


def _readings(program, stretch=None, **counts):
    return phases.PhaseReadings(
        kind="train", chips=1, config=core.config("vgg11-cifar10"),
        peaks={}, trace=_trace(program),
        counts=dict(dict(region_rounds=2, rounds=1, merges=1), **counts),
        phases={} if stretch is None else stretch)


def test_innermost_names_each_segment_by_the_deepest_open_phase():
    segs = phases.innermost(_program())
    assert segs[0] == ("region.orchestrate", 0.0, 0.01)
    # the step's own time between evaluate and its end
    assert ("region.step", 0.095, 0.1) in segs
    assert segs[-1] == ("engine.merge", 0.2, 0.22)
    assert sum(e - s for _, s, e in segs) == pytest.approx(0.22)
    assert all(a[2] <= b[1] for a, b in zip(segs, segs[1:]))


def test_idle_by_phase_splits_every_idle_second():
    td = _trace(_program())
    idle = phases.idle_by_phase(td, td.program)
    assert sum(idle.values()) == pytest.approx(td.window_s - td.busy_s(0))
    assert idle["region.orchestrate"] == pytest.approx(0.02)
    assert idle["cohort.build"] == pytest.approx(0.02)
    # 60 ms of wait, 30 of them with the local update running: 30 ms
    # idle per round
    assert idle["cohort.wait"] == pytest.approx(0.06)
    assert idle["region.evaluate"] == pytest.approx(2 * 0.002)
    assert idle["engine.merge"] == pytest.approx(0.015)
    assert idle["region.step"] == pytest.approx(2 * 0.005)
    assert idle[phases.NONE] == pytest.approx(0.03)   # after the merge


def test_host_idle_counts_host_phases_only():
    r = _readings(_program())
    host = 0.02 + 0.02 + 0.004 + 0.015 + 0.01
    assert _readers()["host_idle_ms"].read(r) == pytest.approx(
        1e3 * host / 2)


def test_staging_is_the_wait_less_the_device_time_inside_it():
    stretch = {"cohort.wait": {"count": 8, "wall_s": 8 * 70 * MS,
                               "self_s": 8 * 70 * MS}}
    r = _readings(_program(), stretch)
    # the aggregate runs under evaluate, outside the wait: not subtracted
    assert _readers()["staging_ms"].read(r) == pytest.approx(70 - 30)


def test_span_readers_read_the_unprofiled_stretch():
    stretch = phases.phase_summary({
        "phase.region.orchestrate.wall_s": {"count": 8, "sum": 0.4},
        "phase.region.orchestrate.self_s": {"count": 8, "sum": 0.4},
        "phase.cohort.build.wall_s": {"count": 8, "sum": 0.48},
        "phase.region.evaluate.wall_s": {"count": 8, "sum": 0.32},
        "phase.engine.merge.wall_s": {"count": 2, "sum": 0.14},
        "phase.never.wall_s": {"count": 0}})
    assert "never" not in stretch
    assert stretch["region.orchestrate"] == {"count": 8, "wall_s": 0.4,
                                             "self_s": 0.4}
    r = _readings(_program(), stretch, h2d_bytes=2 * 189_850_240)
    got = {n: m.read(r) for n, m in _readers().items()}
    assert got["span.orchestrate_ms"] == pytest.approx(50.0)
    assert got["span.cohort_build_ms"] == pytest.approx(60.0)
    assert got["span.evaluate_ms"] == pytest.approx(40.0)
    assert got["span.merge_ms"] == pytest.approx(70.0)
    assert got["h2d_mb"] == pytest.approx(189.85024)
    assert got["staging_ms"] is None      # no wait in the stretch


@pytest.mark.parametrize("how", ["no_trace", "no_phases", "serve"])
def test_every_new_reader_returns_none_without_phases(how):
    cfg = core.config("vgg11-cifar10")
    if how == "no_trace":
        r = Readings(kind="train", chips=1, config=cfg, peaks={},
                     trace=None, counts={"region_rounds": 8})
    elif how == "no_phases":
        # what bench/run.py hands its readers: a trace without the
        # program's phases, no stretch, no byte counter
        r = Readings(kind="train", chips=1, config=cfg, peaks={},
                     trace=TraceData.load(str(SMALL)),
                     counts={"region_rounds": 8, "rounds": 2})
    else:
        r = _readings(_program(), {"cohort.wait": {"count": 1,
                                                   "wall_s": 1.0}},
                      h2d_bytes=10)
        r.kind = "serve"
    for name, mod in _readers().items():
        assert mod.read(r) is None, name


def test_layout_bytes_match_the_counters_rule():
    cfg = core.config("vgg11-cifar10")
    one = [[64, 5, 32], [4, 5, 256]]
    three = [[64, 5, 32], [8, 5, 64], [4, 5, 256]]
    assert phases.layout_h2d_bytes(cfg, [one]) == 188_866_560 + 4 * 68
    assert phases.layout_h2d_bytes(cfg, [one, three]) == (
        188_866_560 + 4 * 68 + 220_344_320 + 4 * 76)


def test_the_existing_reduction_reads_the_recorded_trace_as_before():
    """``TraceData`` and the accepted readers give the numbers they gave
    on the recorded chip trace before the program had phases, with and
    without the phases' wrapper."""
    base = TraceData.load(str(SMALL))
    wrapped = phases.ProgramTrace(base, phases.load_program(str(SMALL)))
    assert wrapped.program == []
    mods = core.metric_modules()
    for td in (base, wrapped):
        assert td.host == [("bench.window", 0.045788137, 0.099437883),
                           ("bench.host_sleep", 0.047554687000000005,
                            0.098523893)]
        assert td.window_s == pytest.approx(0.053649746)
        assert td.busy_s() == pytest.approx(2.4838e-05)
        gaps = td.idle_gaps(top=2)
        assert [g[0] for g in gaps] == ["bench.host_sleep"] * 2
        assert [g[1] for g in gaps] == pytest.approx([0.051699411,
                                                      0.001925493])
        assert td.top_ops(top=1) == [["jit_bench_matmul/fusion",
                                      pytest.approx(1.3296e-05)]]
        r = Readings(kind="train", chips=1,
                     config=core.config("vgg11-cifar10"),
                     peaks={"bf16_flops_per_s": 197e12,
                            "hbm_bytes_per_s": 819e9}, trace=td,
                     counts={"real_elements": 1000, "layout_elements": 4613})
        assert mods["device_idle.train"].read(r) == pytest.approx(
            100 * (1 - 2.4838e-05 / 0.053649746))
        assert mods["padding_ratio"].read(r) == pytest.approx(4.613)
        assert mods["local_update_roofline"].read(r) is None


def _tiny():
    cell = copy.deepcopy(core.workload("mnist-cnn.train.multi_region"))
    cell["population"].update(n_devices=2, n_air=1, h_local=2,
                              train_fraction=0.002, eval_size=64,
                              cohort_batch_align=8)
    cell["scenario"]["regions"] = cell["scenario"]["regions"][:2]
    cell["scenario"]["horizon"] = 6 * 3600.0
    cell["warm_layouts"] = []
    cell["checked_rounds"] = 1
    cell["trace_rounds"] = 2
    return cell


def test_the_tool_reads_every_phase_reading_on_the_cpu(capsys):
    """``bench/phases.py`` end to end at a tiny size: the profiled window's
    phases, the unprofiled stretch through the live switch, the tracer's
    cost.  The CPU trace has no TPU plane, so every second is idle."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_phases_tool", core.BENCH / "phases.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    cell = _tiny()
    config = core.config(cell["config"])
    ctx = runner.Context(
        cell=cell, config=config, seed=2**33 + 7, seconds=0.01, trace=True,
        chips=1, devices=jax.devices()[:1],
        device={"platform": "cpu", "kind": "cpu", "count": 1},
        peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
        clock=core.Clock(), compiles=core.CompileClock())
    out = tool.measure(ctx, cost_pairs=1)
    json.dumps(out)
    got = out["metrics"]
    assert set(NEW) - {"staging_ms"} <= set(got)
    assert got["staging_ms"]["value"] > 0      # nothing ran on a TPU
    assert out["h2d_bytes"] == out["layout_h2d_bytes"] > 0
    assert out["program_events"] == 2 * 2 * 6 + 1
    assert out["stretch"]["region_rounds"] == 4
    assert out["stretch"]["phases"]["engine.merge"]["count"] == 1
    assert 0.95 <= out["stretch"]["coverage"] <= 1.0
    assert out["idle_under_phase"] > 0.5
    assert len(out["tracer_cost"]["off"]) == len(out["tracer_cost"]["on"]) == 1
    assert out["checked_region_rounds"] == 2
    cost = out["phase_cost"]
    assert min(cost["disabled_us"], cost["enabled_us"],
               cost["profiled_us"]) > 0
    assert cost["phases_per_round"] == 2 * 6 + 0.5   # a merge every 2
    err = capsys.readouterr().err
    assert "# idle by phase" in err and "# phase cost" in err
    assert "# pair 0 tracer off: round_s" in err
