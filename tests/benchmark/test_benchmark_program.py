"""The program-span reductions (benchmark/program_trace.py) against
numbers worked out by hand from benchmark/testdata/program_small.json,
and ``trace.reduce`` still giving trace_small.json its old numbers.

W is the files' wall-clock base; each span is given as (W + start,
duration) in ns.
- The stage's anchor is W and the program's window starts at 1000 on
  the profile, so wall W + x lies at 1000 + x on the trace: the offset
  is 1000 - W.
- Window [1000, 11000); device ops [500, 1500), [3000, 5000) and
  [10500, 11500) leave two idle gaps, [1500, 3000) (1500 ns) and
  [5000, 10500) (5500 ns).
- Gap 1: stage.drain (W+600, 900 -> [1600, 2500)) covers 900 ns, more
  than half, at depth 0; its child applier.fence covers 100 ns. So
  1500 ns go to stage.drain.
- Gap 2: stage.checkpoint ([5000, 11000)) covers all 5500 ns at depth
  0; its child checkpoint.save ([6500, 10500)) covers 4000 ns, more
  than half, at depth 1; the save's child write_arrays covers 2500 ns,
  less than half. So 5500 ns go to checkpoint.save, and no idle second
  is unannotated.
- stage.checkpoint: 6000 ns; its children applier.flush 500,
  applier.fence 1000, checkpoint.save 4000 and stage.publish 400 make
  5900, so self 100 ns; checkpoint.save self 4000 - 2500 = 1500 ns;
  applier.flush self 500 - 2 x 100 (its two applier.dispatch) = 300 ns.
- applier.fence twice (100 and 1000 ns): count 2, total 1100 ns, mean
  550, p50 100, p99 1000 (nearest rank).
- The core's last frontend.backchannel (W+29000) was still open at the
  stop (W+30000), so it is cut there and listed under "open": whole-span
  statistics count the other two (3000 and 1000 ns: p99 0.003 ms).
- The window on the wall clock is [W, W + 10000). A last
  applier.dispatch (W+10500) lies after it: restricted to the window,
  the stage keeps the checkpoint's two dispatches of three; the core
  keeps its first backchannel (3000 ns) and log.flush (800 ns) and
  drops the gc sweep, the later passes and the second stall.
- The stage's samples of stage.deli_to_applied.ms are 300, 100, 400 and
  200: mean 250, p50 200, p99 400 (nearest rank); the core's
  frontend.loop.lag_ms 0.5, 40, 0.5, 2 and 0.5 in the window (p50 0.5,
  p99 40) and 500 after it (p99 500 over all six).
- The core's stalls: W+10500 for 4000 ns, all of it inside
  frontend.gc_sweep (W+10000, 5000); W+2500 for 2000 ns, of which
  frontend.backchannel (W+2000, 3000) covers 2000 at depth 0 and its
  child log.flush (W+4000, 800) 500. So core_stalls lists gc_sweep
  0.004 ms, then backchannel 0.002 ms.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import program_trace as pt  # noqa: E402
from benchmark import trace  # noqa: E402


def _data(name):
    with open(os.path.join(ROOT, "benchmark", "testdata", name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def program():
    return _data("program_small.json")


def test_reduce_keeps_its_old_numbers():
    assert trace.reduce(_data("trace_small.json")["events"]) == {
        "busy_s": pytest.approx(3500e-9), "window_s": pytest.approx(1e-5),
        "devices": 1,
        "spans": {"bench.checkpoint": [2, pytest.approx(3000e-9)],
                  "bench.drain": [1, pytest.approx(3000e-9)]},
        "breakdown": {
            "device_ops": [["fusion.2", pytest.approx(2000e-9)],
                           ["fusion.1", pytest.approx(1000e-9)],
                           ["copy.3", pytest.approx(1000e-9)]],
            "idle_gaps": [["bench.drain", pytest.approx(5000e-9)],
                          ["bench.checkpoint", pytest.approx(1500e-9)]]}}


def test_wall_to_trace_offset(program, tmp_path):
    w = program["stage"]["anchor"]["wall_ns"]
    assert program["window_ns"] == 1000
    assert pt.wall_to_trace_ns(program["stage"], 1000) == 1000 - w
    # a profile without the program's window (an older program), or no
    # span file
    assert pt.wall_to_trace_ns(program["stage"], None) is None
    assert pt.wall_to_trace_ns(None, 1000) is None
    assert pt.window_start_ns(str(tmp_path)) is None


def test_reduce_program(program):
    r = pt.reduce_program(program["stage"])
    assert r["stage.checkpoint"]["count"] == 1
    assert r["stage.checkpoint"]["total_ms"] == pytest.approx(0.006)
    assert r["stage.checkpoint"]["self_ms"] == pytest.approx(0.0001)
    assert r["checkpoint.save"]["self_ms"] == pytest.approx(0.0015)
    assert r["applier.flush"]["self_ms"] == pytest.approx(0.0003)
    assert r["applier.fence"] == {
        "count": 2, "total_ms": pytest.approx(0.0011),
        "self_ms": pytest.approx(0.0011), "mean_ms": pytest.approx(0.00055),
        "p50_ms": pytest.approx(0.0001), "p99_ms": pytest.approx(0.001)}
    core = pt.reduce_program(program["core"])
    assert core["frontend.backchannel"]["count"] == 2
    assert core["frontend.backchannel"]["p99_ms"] == pytest.approx(0.003)
    assert pt.reduce_program({"spans": []}) is None
    assert pt.reduce_program(None) is None


def test_idle_by_span(program):
    win = program["window_ns"]
    got = pt.idle_by_span(program["events"], program["stage"], win)
    assert got == {"checkpoint.save": pytest.approx(5500e-9),
                   "stage.drain": pytest.approx(1500e-9)}
    assert list(got) == ["checkpoint.save", "stage.drain"]
    # no program window on the profile, or no file
    assert pt.idle_by_span(program["events"], program["stage"], None) \
        is None
    assert pt.idle_by_span(program["events"], None, win) is None


def test_idle_gap_with_no_span_is_unannotated(program):
    stage = dict(program["stage"], spans=program["stage"]["spans"][:1])
    got = pt.idle_by_span(program["events"], stage, program["window_ns"])
    assert got == {pt.UNNAMED: pytest.approx(7000e-9)}


def test_core_stalls(program):
    assert pt.core_stalls(program["core"]) == [
        ["frontend.gc_sweep", pytest.approx(0.004)],
        ["frontend.backchannel", pytest.approx(0.002)]]
    assert pt.core_stalls(program["core"], n=1) == [
        ["frontend.gc_sweep", pytest.approx(0.004)]]
    assert pt.core_stalls(program["stage"]) == []
    assert pt.core_stalls(None) is None


def test_window_on_wall(program):
    w = program["stage"]["anchor"]["wall_ns"]
    ev, st = program["events"], program["stage"]
    assert pt.window_on_wall(ev, st, program["window_ns"]) == (w, w + 10000)
    assert pt.window_on_wall(ev, st, None) is None
    assert pt.window_on_wall({"host": []}, st, program["window_ns"]) is None


def test_program_summary(program):
    win = pt.window_on_wall(program["events"], program["stage"],
                            program["window_ns"])
    st = pt.program_summary(program["stage"], win)
    assert st["samples"] == {"stage.deli_to_applied.ms": {
        "count": 4, "mean": 250.0, "p50": 200.0, "p99": 400.0}}
    assert st["counters"] == {"stage.checkpoint.bytes": 25000,
                              "stage.publish.records": 8}
    assert st["spans"]["applier.dispatch"]["count"] == 2
    assert st["span_counts"]["applier.dispatch"] == 3
    assert st["spans"]["stage.checkpoint"] == \
        pt.reduce_program(program["stage"])["stage.checkpoint"]
    assert st["window_s"] == pytest.approx(1e-5) and st["overflow"] == 0
    core = pt.program_summary(program["core"], win)
    assert core["samples"]["frontend.loop.lag_ms"] == {
        "count": 5, "mean": pytest.approx(8.7), "p50": 0.5, "p99": 40.0}
    assert {n: r["count"] for n, r in core["spans"].items()} == {
        "frontend.ingress_batch": 1, "frontend.backchannel": 1,
        "log.flush": 1, "frontend.loop_stall": 1}
    assert core["spans"]["log.flush"]["p99_ms"] == pytest.approx(0.0008)
    whole = pt.program_summary(program["core"])
    assert whole["samples"]["frontend.loop.lag_ms"]["p99"] == 500.0
    assert whole["spans"]["frontend.backchannel"]["count"] == 2
    assert pt.program_summary(None) is None
