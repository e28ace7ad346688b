"""The ``stage.tail_ms`` reader: the stage's poll and drain seconds in
the window over its count of polls, worked out by hand."""

import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _read():
    path = os.path.join(ROOT, "benchmark", "metrics", "stage.tail_ms.py")
    spec = importlib.util.spec_from_file_location("m_stage_tail_ms", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("spans, want", [
    # 4 polls of 0.010 s in all, 3 drains of 0.030 s: 40 ms / 4 turns
    ({"bench.poll": [4, 0.010], "bench.drain": [3, 0.030],
      "bench.checkpoint": [3, 0.5]}, 10.0),
    # turns that found nothing drain nothing
    ({"bench.poll": [2, 0.004]}, 2.0),
])
def test_mean_host_tail_a_turn(spans, want):
    assert _read()({"trace": {"spans": spans}}) == pytest.approx(want)


@pytest.mark.parametrize("layer", [
    {"trace": {"spans": {"bench.drain": [3, 0.03]}}},
    {"trace": {"spans": {}}},
    {"trace": None},
    {},
])
def test_no_poll_reads_nothing(layer):
    assert _read()(layer) is None
