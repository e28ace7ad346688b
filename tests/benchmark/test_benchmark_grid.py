"""The grid cell's harness at tiny sizes on the CPU.

- the rehearsal of ``grid-100x1k-text1k.steady`` (4 grids of 16 x 16
  beside 8 text docs, H 64) is ``correct``, every check 0, the grids'
  checks among them;
- a stage whose STAGE line shows no grid store (a program without the
  grids) makes served_grid.py exit 1 at that line, before any doc is
  loaded, with no result line;
- the readers of ``kernel.matrix_cells_*`` and the bytes they count;
- the stage wrapper's window counters carry the grid counters.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import roofline_grid  # noqa: E402
from benchmark.run import reader  # noqa: E402

CELL = "grid-100x1k-text1k.steady"


def run_cell(root=ROOT, seed=2300000001, seconds=4):
    env = dict(os.environ, PERFBENCH_REHEARSAL="1", JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT)
    env.pop("PERFBENCH_FAULT", None)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(seed), "--seconds", str(seconds), "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=240)


def test_rehearsal_is_correct():
    p = run_cell(seed=2300000003, seconds=8)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, out["checks"]
    assert set(out["checks"]) == {
        "text_mismatch_docs", "acked_not_in_log", "order_faults",
        "applied_gap_ops", "host_escalations", "window_compiles",
        "grid_cells", "grids_off_device"}
    assert all(c["value"] == 0 for c in out["checks"].values())
    assert set(out["metrics"]) == {"staleness_p99_ms", "acked_ops_per_s",
                                   "setup_s"}
    grid = json.loads(next(ln[5:] for ln in p.stdout.splitlines()
                           if ln.startswith("grid ")))
    assert grid["grid_dispatches"] > 0 and grid["grid_cells_set"] > 0
    assert grid["grid_escalations"] == 0 and grid["cells_min"] > 0
    assert 32 <= grid["grid_handles_peak"] <= grid["grid_handles"]


def test_a_stage_without_the_grid_store_gives_no_result(tmp_path):
    """The grids set to 0 under the stage's wrapper, in a copy:
    served_grid.py exits 1 at the STAGE line and prints no result."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    wrap = tmp_path / "benchmark" / "stage_wrap_grid.py"
    head = "from __future__ import annotations\n"
    wrap.write_text(wrap.read_text().replace(head, head + (
        'import os\nos.environ["FLUID_TPU_APPLIER_GRIDS"] = "0"\n'), 1))
    p = run_cell(root=str(tmp_path))
    assert p.returncode == 1
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "no grid store" in p.stderr
    assert not any(line.startswith("load ") for line in
                   p.stdout.splitlines())


def test_the_readers():
    shape = [100, 256, 32]
    per = roofline_grid.matrix_cells_bytes(*shape)
    # 2 vectors x 100 grids x (7 planes x 256 + count) int32, op rows
    assert per == 2 * 100 * (7 * 256 + 1) * 4 + 100 * 32 * 6 * 4
    layer = {"device_kind": "TPU v5 lite",
             "grid_kernel": {"n": 200, "seconds": 0.04, "cells": 5000,
                             "shape": shape}}
    assert reader("kernel.matrix_cells_ms").read(layer) == \
        pytest.approx(0.2)
    share = reader("kernel.matrix_cells_roofline_share").read(layer)
    assert share == pytest.approx(
        100 * (200 * per + 9 * 5000) / (0.04 * 819e9))
    for empty in ({}, {"grid_kernel": None},
                  {"grid_kernel": {"n": 0, "seconds": 0.0, "cells": 0,
                                   "shape": shape}}):
        assert reader("kernel.matrix_cells_ms").read(empty) is None
        assert reader("kernel.matrix_cells_roofline_share").read(
            empty) is None


def test_the_wrapper_counts_the_grids():
    from benchmark import stage_wrap_grid
    from fluidframework_tpu.obs.metrics import tier_counters, tier_snapshot

    class Applier:
        ops_applied, dispatches, stage_seconds = 5, 2, 0.1
        host_escalations, grid_dispatches, grid_cells_set = 0, 3, 7

    stage = tier_counters("stage")  # what an ApplierStage counts on
    stage.inc("stage.checkpoint.grid_bytes", 12)
    c = stage_wrap_grid.counters(Applier())
    assert c["grid_dispatches"] == 3 and c["grid_cells_set"] == 7
    assert c["grid_escalations"] == 0 and c["ops_applied"] == 5
    assert c["stage.checkpoint.grid_bytes"] == \
        tier_snapshot("stage")["stage.checkpoint.grid_bytes"] >= 12
