"""Time the grids' share of one base as the grid store fills.

A base of the device applier carries every grid cell ever written, read
back from the device store in pages (``TpuDocumentApplier.
_grid_store_values``: one dispatch per ``GRID_PAGE`` cells of the
fullest grid, each a pass over the store's int8 marks). This builds an
applier with the grid cell's store, marks a square block of cells
written in every grid, and times that read at each fill:

  - ``--fills`` cells a grid, each rounded to a square block (the grid
    cell writes ~180 a grid in a run; a 1k x 1k sheet filled is
    1,000,000);
  - ``--repeats`` reads of each, after one read that compiles.

It prints one JSON line a fill: cells, page dispatches, the read's ms
(min and median), and the bytes a base carries for them. Usage:

    python tools/profile_grid_base.py [--grids G] [--handles H]
        [--fills N,N,...] [--repeats R]
"""

from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--grids", type=int, default=100)
    ap.add_argument("--handles", type=int, default=1536)
    ap.add_argument("--fills", default="180,10000,1000000")
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args()

    from fluidframework_tpu.utils.chip import require_tpu

    require_tpu()
    import jax
    import jax.numpy as jnp

    from fluidframework_tpu.service.tpu_applier import (GRID_PAGE,
                                                        TpuDocumentApplier)

    G, H = args.grids, args.handles
    a = TpuDocumentApplier(max_docs=8, max_slots=64, ops_per_dispatch=4,
                           grids=G, grid_handles=H)
    a.warm_grids()
    a.grid_cells = jax.random.randint(jax.random.PRNGKey(0), (G, H, H),
                                      -(1 << 31), (1 << 31) - 1, jnp.int32)
    for fill in (int(f) for f in args.fills.split(",")):
        side = min(H, math.isqrt(fill))
        a.grid_written = jnp.zeros((G, H, H), jnp.int8).at[
            :, :side, :side].set(1)
        jax.block_until_ready(a.grid_written)
        idx, val = a._grid_store_values()  # compiled by warm_grids
        times = []
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            idx, val = a._grid_store_values()
            times.append(time.perf_counter() - t0)
        cells = side * side
        assert len(idx) == G * cells, (len(idx), G * cells)
        print(json.dumps({
            "grids": G, "handles": H, "cells_a_grid": cells,
            "cells": G * cells, "dispatches": max(1, -(-cells // GRID_PAGE)),
            "read_ms_min": 1e3 * min(times),
            "read_ms_median": 1e3 * statistics.median(times),
            "base_grid_bytes": idx.nbytes + val.nbytes,
            "device": jax.devices()[0].device_kind}), flush=True)


if __name__ == "__main__":
    main()
