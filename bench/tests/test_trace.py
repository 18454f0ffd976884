"""The reduction from a profiler trace to busy time, program time and idle
gaps, on made-up intervals and on a small trace recorded on a TPU v5e chip
(``make_trace.py``)."""
from pathlib import Path

import pytest

from bench.harness.trace import TraceData, merge_intervals

SMALL = Path(__file__).parent / "data" / "small.xplane.pb"


def test_merge_clips_and_joins_overlaps():
    ev = [("a", 0.0, 2.0), ("b", 1.0, 3.0), ("c", 5.0, 6.0),
          ("d", 9.0, 12.0), ("e", -3.0, -1.0)]
    assert merge_intervals(ev, 0.5, 10.0) == [(0.5, 3.0), (5.0, 6.0),
                                               (9.0, 10.0)]


def _made_up():
    ops = {0: [("fusion.1", 1.0, 2.0), ("conv.2", 1.5, 3.0),
               ("all-reduce.3", 6.0, 7.0)],
           1: [("fusion.1", 1.0, 5.0)]}
    modules = {0: [("jit_cohort_local_update(7)", 1.0, 3.0),
                   ("jit__fedavg_multi_impl(2)", 6.0, 7.0)],
               1: [("jit_cohort_local_update(7)", 1.0, 5.0)]}
    host = [("bench.window", 0.0, 10.0), ("bench.round", 0.5, 9.5),
            ("bench.orchestrate", 3.2, 5.8)]
    return TraceData(ops, modules, host, (0.0, 10.0))


def test_busy_is_the_union_of_op_intervals_averaged_over_chips():
    td = _made_up()
    assert td.busy_s(0) == pytest.approx(3.0)
    assert td.busy_s(1) == pytest.approx(4.0)
    assert td.busy_s() == pytest.approx(3.5)
    assert td.window_s == pytest.approx(10.0)


def test_program_and_op_time_by_pattern():
    td = _made_up()
    assert td.module_s([r"cohort_local_update"], chip=0) == pytest.approx(2)
    assert td.module_s([r"cohort_local_update"]) == pytest.approx(3.0)
    assert td.module_s([r"_fedavg_multi_impl"], chip=0) == pytest.approx(1)
    assert td.op_s([r"all-reduce"], chip=0) == pytest.approx(1.0)
    assert td.module_s([r"no_such_program"]) == 0.0


def test_idle_gaps_are_named_by_the_innermost_host_annotation():
    td = _made_up()
    gaps = td.idle_gaps(chip=0)
    assert gaps[0] == ["bench.orchestrate", pytest.approx(3.0)]
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    assert sum(g[1] for g in gaps) == pytest.approx(10.0 - td.busy_s(0))
    names = {g[0] for g in gaps}
    assert names <= {"bench.orchestrate", "bench.round", "bench.window"}


def test_top_ops_sum_device_time_per_op():
    td = _made_up()
    top = dict(td.top_ops(chip=1))
    assert top == {"fusion.1": pytest.approx(4.0)}


def test_small_chip_trace():
    """Two dispatches of ``jit_bench_matmul``, 50 ms of host sleep, one
    more dispatch, all inside ``bench.window``.  The chip's clock runs
    about 2 ms behind the host's in this trace, so the window's edges cut
    the first dispatch; over a run's window of seconds that is noise."""
    td = TraceData.load(str(SMALL))
    assert td.chips == [0]
    assert 0 < td.busy_s() < td.window_s
    matmul = td.module_s([r"bench_matmul"])
    # a program's span holds its ops and a few nanoseconds more
    assert 0 < matmul <= 1.01 * td.busy_s()
    gap_name, gap_s = td.idle_gaps()[0]
    assert gap_name == "bench.host_sleep"
    assert 0.05 <= gap_s < 0.5
    assert [e - s for n, s, e in td.host if n == "bench.host_sleep"][0] >= 0.05
    assert all(s >= 0 for _, s in td.top_ops())
