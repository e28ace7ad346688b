"""The grid cell's applier stage: ``stage_wrap.py`` as it is, with two
readings more for the traced window.

- the applier's grid counters (dispatches, cells set, dead writes, the
  handles' peak, escalations) and the stage's checkpoint bytes (all,
  and the grid cells' share, ``stage.checkpoint.grid_bytes``) beside
  the applier's others, at both ends of the window (``WINDOW``'s
  ``start`` and ``end``);
- the device trace's module events of the grids' kernel
  (``jit_matrix_cells`` on each TPU plane's "XLA Modules" line), under
  ``modules`` in the events file: the kernel is an XLA program, whose
  ops carry no stable name on the "XLA Ops" line that ``trace.py``
  keeps.
"""

from __future__ import annotations

import glob
import os

from benchmark import stage_wrap, trace
from fluidframework_tpu.obs.metrics import tier_snapshot

#: the grids' kernel's module in the device trace
KERNEL = "matrix_cells"
_MODULES_LINE = "XLA Modules"

_counters = stage_wrap.counters
_load_events = trace.load_events
#: the stage's checkpoint counters read at the window's ends
STAGE_COUNTERS = ("stage.checkpoint.bytes", "stage.checkpoint.grid_bytes")


def counters(applier) -> dict:
    # the process's "stage" tier: the ApplierStage's counters
    stage = tier_snapshot("stage")
    return {**_counters(applier),
            **{name: getattr(applier, name, 0) for name in (
                "grid_dispatches", "grid_cells_set", "grid_dead_writes",
                "grid_handles_peak", "grid_escalations")},
            **{name: stage.get(name, 0) for name in STAGE_COUNTERS}}


def load_events(trace_dir: str) -> dict:
    events = _load_events(trace_dir)
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    modules = []
    if paths:
        from jax.profiler import ProfileData

        for plane in ProfileData.from_file(paths[-1]).planes:
            if not trace._TPU_PLANE.match(plane.name):
                continue
            for ln in plane.lines:
                if ln.name == _MODULES_LINE:
                    modules += [[e.name, int(e.start_ns), int(e.duration_ns)]
                                for e in ln.events if KERNEL in e.name]
    events["modules"] = modules
    return events


if __name__ == "__main__":
    stage_wrap.counters = counters
    trace.load_events = load_events
    stage_wrap.main()
