"""The grid cell: ``served.py``'s topology and window, with SharedMatrix
grids beside the text cell's 1,000 docs.

What it adds to ``served.py`` (whose process helpers, log tail and
schedule it imports, as ``served_long.py`` does):

- an early ``require`` that the stage's STAGE line shows the grid store
  at the config's size (``grids`` x ``grid_handles``): a program without
  it exits 1 here, before any doc is loaded;
- the stage under ``benchmark/stage_wrap_grid.py``, which adds the grid
  counters and the grids' kernel events to the traced window;
- the load phase (``benchmark/loadgen_grid.py``): each grid attached and
  given ``grid_rows`` rows and ``grid_cols`` columns through the served
  path, before the warm-up, all of it set-up;
- per-client rates: the grids' clients share ``grid_share`` of the rate
  and the text docs' clients the rest, each set on its own schedule of
  ``served.schedule``'s shape (``served_long.long_schedule``);
- its checks: ``served.py``'s six over the text docs (the text held to
  ``refmerge_runs.py``) and over the grids' ops, then ``grid_cells``
  (grids whose device cells, read by ``answers_grid.py``, differ from
  ``refmatrix.py``'s) and ``grids_off_device`` (grids on the host);
- for the traced run, the grids' kernel events in the window (count and
  device seconds), the cells set in it and the kernel's shapes, for
  ``kernel.matrix_cells_*``.
"""

from __future__ import annotations

import json
import os
import resource
import shutil
import signal
import sys
import tempfile
import time

from benchmark import common
from benchmark.common import note, percentile, require
from benchmark.drivers.served import (TENANT, _Applied, _last_seqs, _line,
                                      _Procs, _reduced, _thirds)
from benchmark.drivers.served_long import long_schedule


def run(name: str, entry: dict, cell: dict, config: dict, args) -> dict:
    tr = cell["traffic"]
    if args.rate is not None:
        tr = {**tr, "rate_ops_per_s": args.rate}
    docs = [f"d{i}" for i in range(config["docs"])]
    grids = [f"g{i}" for i in range(config["grids"])]
    n_clients = (len(docs) * config["clients_per_doc"]
                 + len(grids) * config["clients_per_grid"])
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    need = n_clients + 4096
    if soft < need:
        require(hard == resource.RLIM_INFINITY or hard >= need,
                f"RLIMIT_NOFILE hard limit {hard} < {need} sockets")
        resource.setrlimit(resource.RLIMIT_NOFILE, (need, hard))
    tmp = tempfile.mkdtemp(prefix="perfbench-grid-")
    procs = _Procs(tmp)
    env = common.child_env({
        "FLUID_TPU_APPLIER_MAX_DOCS": config["applier_max_docs"],
        "FLUID_TPU_APPLIER_MAX_SLOTS": config["max_slots"],
        "FLUID_TPU_APPLIER_OPS_PER_DISPATCH": config["ops_per_dispatch"],
        "FLUID_TPU_APPLIER_GRIDS": config["grids"],
        "FLUID_TPU_APPLIER_GRID_HANDLES": config["grid_handles"]})
    try:
        return _run(name, entry, config, args, tr, procs, tmp, docs, grids,
                    env)
    except common.BenchFailure as e:
        raise common.BenchFailure(f"{e}\n{procs.tails()}") from None
    finally:
        procs.stop_all()
        shutil.rmtree(tmp, ignore_errors=True)


def _clients(config, docs, grids) -> list:
    """[(gid, doc, kind)]: the text docs' clients, then the grids'."""
    per_doc, per_grid = config["clients_per_doc"], config["clients_per_grid"]
    out = [(g, docs[g // per_doc], "text")
           for g in range(len(docs) * per_doc)]
    base = len(out)
    out += [(base + g, grids[g // per_grid], "grid")
            for g in range(len(grids) * per_grid)]
    return out


def _run(name, entry, config, args, tr, procs, tmp, docs, grids, env):
    log_dir = os.path.join(tmp, "log")
    state_dir = os.path.join(tmp, "state")
    trace_dir = os.path.join(tmp, "trace") if args.trace else None
    stage_args = ["benchmark.stage_wrap_grid", "--log-dir", log_dir,
                  "--state-dir", state_dir]
    if trace_dir:
        stage_args += ["--trace-dir", trace_dir]
    stage = procs.spawn("stage", stage_args, env)
    device = json.loads(_line(stage, "DEVICE", 240))
    require(device["platform"] == common.expected_platform(),
            f"the applier stage runs on {device['platform']}, not "
            f"{common.expected_platform()}")
    require(device["count"] >= entry["chips"],
            f"{device['count']} chips, the cell asks for {entry['chips']}")
    _line(stage, "READY", 300)
    info = json.loads(_line(stage, "STAGE", 60))
    require(info["max_docs"] == config["applier_max_docs"],
            f"stage geometry {info}")
    require(info.get("grids") == config["grids"]
            and info.get("grid_handles") == config["grid_handles"],
            f"the stage shows no grid store of {config['grids']} x "
            f"{config['grid_handles']}: {info}")
    note("stage " + json.dumps(info))
    core = procs.spawn("core", [
        "fluidframework_tpu.service.front_end", "--port", "0",
        "--log-dir", log_dir, "--consume-backchannel", state_dir], env)
    port = int(_line(core, "LISTENING", 120).rsplit(":", 1)[1])

    clients = _clients(config, docs, grids)
    doc_of = {gid: doc for gid, doc, _k in clients}
    n_text = len(docs) * config["clients_per_doc"]
    sched = long_schedule(args.seed, {**tr, "long_share": tr["grid_share"]},
                          n_text, len(clients) - n_text, args.seconds)
    n_workers = tr["workers"]
    all_docs = docs + grids
    worker_of = {d: i % n_workers for i, d in enumerate(all_docs)}
    load = {"rows": config["grid_rows"], "cols": config["grid_cols"],
            "wait_s": tr["load_wait_s"]}
    first = {}
    for gid, doc, kind in clients:
        if kind == "grid":
            first.setdefault(doc, gid)
    workers = []
    for w in range(n_workers):
        mine = [c for c in clients if worker_of[c[1]] == w]
        gids = {c[0] for c in mine}
        plan = {"tenant": TENANT, "seed": args.seed, "seconds": args.seconds,
                "ack_wait_s": tr["ack_wait_s"],
                "connect_concurrency": tr.get("connect_concurrency", 64),
                "traffic": tr, "clients": mine, "load": load,
                "loaders": [g for d, g in first.items()
                            if worker_of[d] == w],
                "schedule": [s for s in sched if s[1] in gids]}
        path = os.path.join(tmp, f"plan{w}.json")
        with open(path, "w") as f:
            json.dump(plan, f)
        workers.append(procs.spawn(f"load{w}", [
            "benchmark.loadgen_grid", "--port", str(port), "--plan", path,
            "--out", os.path.join(tmp, f"out{w}.json")], env, stdin=True))
    for w in workers:
        _line(w, "READY", 600)
    t_load = time.monotonic()
    for w in workers:
        w.stdin.write("LOAD\n")
        w.stdin.flush()
    for w in workers:
        bad = int(_line(w, "LOADED", tr["load_wait_s"] + 600))
        require(bad == 0, f"{bad} grids not loaded")
    load_s = time.monotonic() - t_load
    for w in workers:
        w.stdin.write("WARM\n")
        w.stdin.flush()
    for w in workers:
        bad = int(_line(w, "WARMED", tr["ack_wait_s"] + 30))
        require(bad == 0, f"{bad} warm-up ops not acked")

    from fluidframework_tpu.service.durable_log import DurableLog

    log = DurableLog(log_dir, readonly=True)
    applied = _Applied(state_dir)
    warm_tail = _last_seqs(log, all_docs)
    deadline = time.monotonic() + 300
    while any(applied.now.get(d, 0) < warm_tail[d] for d in all_docs):
        require(time.monotonic() < deadline, "warm-up never applied")
        require(stage.poll() is None, f"stage exited rc {stage.returncode}")
        applied.poll()
        time.sleep(0.02)

    t0 = time.monotonic() + 0.5
    setup_s = t0 - args.t_start
    for w in workers:
        w.stdin.write(f"GO {t0!r}\n")
        w.stdin.flush()
    while time.monotonic() < t0:
        time.sleep(0.001)
    stage.send_signal(signal.SIGUSR1)
    t_end = t0 + args.seconds
    while time.monotonic() < t_end:
        applied.poll()
        time.sleep(0.01)
    stage.send_signal(signal.SIGUSR2)
    records, errors = [], []
    for i, w in enumerate(workers):
        while _line(w, "DONE", 0.01, block=False) is None:
            applied.poll()
            require(time.monotonic() < t_end + tr["ack_wait_s"] + 60,
                    f"load worker {i} never finished")
        with open(os.path.join(tmp, f"out{i}.json")) as f:
            out = json.load(f)
        records.extend(out["records"])
        errors.extend(out["errors"])
    require(not errors, f"load worker errors: {errors[:3]}")

    acked = [r for r in records if "ack" in r]
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        applied.poll()
        if all(applied.now.get(doc_of[r["gid"]], 0) >= r["seq"]
               for r in acked):
            break
        time.sleep(0.02)
    stage.send_signal(signal.SIGINT)
    stopped = json.loads(_line(stage, "STOPPED", 180))
    window = json.loads(_line(stage, "WINDOW", 300))
    require(stage.wait(timeout=120) == 0, f"stage rc {stage.returncode}")
    core.terminate()
    require("jax" not in sys.modules, "the harness imported JAX")

    # ------------------------------------------------------- end to end
    attempted = len(records)
    failed = [r for r in records if "ack" not in r]
    cap = t_end + tr["ack_wait_s"]
    ack_ms = [((r["ack"] if "ack" in r else cap) - r["due"]) * 1e3
              for r in records]
    stale_ms = []
    for r in records:
        t = (applied.first_at(doc_of[r["gid"]], r["seq"])
             if "ack" in r else None)
        stale_ms.append(((t if t is not None else cap) - r["due"]) * 1e3)
    grid_set = set(grids)
    stale_grid = [s for r, s in zip(records, stale_ms)
                  if doc_of[r["gid"]] in grid_set]
    acked_ops = sum(r["size"] for r in acked if r["ack"] <= t_end)
    late_ms = [(r["sent"] - r["due"]) * 1e3 for r in records]
    e2e = {"setup_s": setup_s,
           "staleness_p99_ms": percentile(stale_ms, 99),
           "acked_ops_per_s": acked_ops / args.seconds}
    note("load " + json.dumps({
        "boxcars": attempted, "ops": sum(r["size"] for r in records),
        "failed": len(failed), "nacked": sum(bool(r.get("nacked"))
                                             for r in records),
        "ack_p50_ms": percentile(ack_ms, 50),
        "ack_p99_ms": percentile(ack_ms, 99),
        "staleness_p50_ms": percentile(stale_ms, 50),
        "staleness_grid_p99_ms": (percentile(stale_grid, 99)
                                  if stale_grid else None),
        "late_p50_ms": percentile(late_ms, 50),
        "late_p99_ms": percentile(late_ms, 99),
        "late_max_ms": max(late_ms),
        "ack_p50_ms_by_third": _thirds(records, t0, args.seconds),
        "rate_ops_per_s": tr["rate_ops_per_s"], "load_s": load_s}))
    note("stage_window " + json.dumps(window) + " stopped "
         + json.dumps(stopped))

    # ------------------------------------------------------ correctness
    from benchmark import answers, answers_grid
    from benchmark.grid_editor import grid_digest
    from benchmark.refmatrix import reference_grid
    from benchmark.refmerge_runs import reference_text

    ckpt = os.path.join(state_dir, "applier")
    texts, host_docs = answers.device_texts(ckpt)
    cells, host_grids = answers_grid.device_grids(ckpt)
    mismatch = order_bad = missing = log_ops = grid_bad = 0
    by_doc: dict = {}
    for r in acked:
        by_doc.setdefault(doc_of[r["gid"]], []).append(r)
    n_cells = []
    for d in all_docs:
        is_grid = d in grid_set
        msgs = (answers_grid.read_grid_log(log, TENANT, d) if is_grid
                else answers.read_doc_log(log, TENANT, d))
        log_ops += sum(op is not None for *_x, op in msgs)
        order_bad += answers.order_faults(msgs)
        index = answers.acked_index(msgs)
        for r in by_doc.get(d, ()):
            got = [index.get((r["client_id"], c))
                   for c in range(r["cseq0"], r["cseq"] + 1)]
            digest = (grid_digest if is_grid else answers.op_digest)
            missing += (any(g is None for g in got)
                        or got[-1][0] != r["seq"]
                        or digest([g[1] for g in got]) != r["digest"])
        ops = ((seq, ref, cid, op) for seq, _c, ref, cid, op in msgs
               if op is not None)
        if is_grid:
            want = reference_grid(ops)
            n_cells.append(len(want))
            if (TENANT, d) not in host_grids \
                    and cells.get((TENANT, d)) != want:
                grid_bad += 1
        elif texts.get((TENANT, d)) != reference_text(ops):
            mismatch += 1
    note("grid " + json.dumps({
        "grid_dispatches": stopped.get("grid_dispatches"),
        "grid_cells_set": stopped.get("grid_cells_set"),
        "grid_dead_writes": stopped.get("grid_dead_writes"),
        "grid_handles_peak": stopped.get("grid_handles_peak"),
        "grid_handles": config["grid_handles"],
        "grid_escalations": stopped.get("grid_escalations"),
        "cells_min": min(n_cells), "cells_max": max(n_cells)}))
    checks = {
        "text_mismatch_docs": {"value": mismatch, "limit": 0},
        "acked_not_in_log": {"value": missing, "limit": 0},
        "order_faults": {"value": order_bad, "limit": 0},
        "applied_gap_ops": {"value": abs(log_ops - stopped["ops_applied"]),
                            "limit": 0},
        "host_escalations": {"value": max(stopped["host_escalations"],
                                          host_docs), "limit": 0},
        "window_compiles": {"value": window.get("compiles", 0),
                            "limit": 0},
        "grid_cells": {"value": grid_bad, "limit": 0},
        "grids_off_device": {"value": len(host_grids) + sum(
            (TENANT, g) not in cells and (TENANT, g) not in host_grids
            for g in grids), "limit": 0},
    }
    device["memory_peak_bytes"] = stopped["peak_bytes_in_use"]
    layer = {"cell": name, "device_kind": device["kind"],
             "hops": {"submit_to_deli_ms": [
                 (r["deli_wall"] - r["sent_wall"]) * 1e3 for r in acked
                 if r.get("deli_wall") is not None]},
             "trace": _reduced(window),
             "grid_kernel": _grid_kernel(window, config)}
    return {"attempted": attempted, "failed": len(failed), "e2e": e2e,
            "checks": checks, "device": device, "layer": layer}


def _grid_kernel(window: dict, config: dict):
    """The grids' kernel in the traced window: its module events' count
    and device seconds, the cells set and the shapes; None untraced."""
    from benchmark.trace import WINDOW

    if not window.get("events"):
        return None
    with open(window["events"]) as f:
        events = json.load(f)
    wins = [(s, d) for n, s, d in events["host"] if n == WINDOW]
    if not wins:
        return None
    lo, dur = max(wins, key=lambda w: w[1])
    n, ns = 0, 0
    for _name, s, d in events.get("modules", ()):
        if lo <= s < lo + dur:
            n += 1
            ns += d
    start, end = window["start"], window["end"]
    moved = {k: end.get(k, 0) - start.get(k, 0) for k in end}
    cells = moved["grid_cells_set"]
    turns = sum(1 for nm, s, _d in events["host"]
                if nm == "bench.checkpoint" and lo <= s < lo + dur)
    note("grid_kernel " + json.dumps({
        "dispatches": n, "device_s": ns / 1e9, "cells_set": cells,
        "escalations": moved["grid_escalations"], "checkpoints": turns,
        "checkpoint_bytes": moved.get("stage.checkpoint.bytes"),
        "grid_bytes": moved.get("stage.checkpoint.grid_bytes")}))
    return {"n": n, "seconds": ns / 1e9, "cells": cells,
            "shape": [config["grids"], config["max_slots"],
                      config["ops_per_dispatch"]]}
