"""The long-document cell: ``served.py``'s topology and window, with 16
book-length docs beside the 1,000 short ones.

What it adds to ``served.py`` (whose process helpers, log tail and
schedule it imports):

- an early ``require`` that the stage's STAGE line shows the long tier
  at the config's size (``long_docs`` x ``long_slots``): a program
  without the tier exits 1 here, before any doc is loaded;
- the load phase (``benchmark/loadgen_long.py``): each long doc built to
  ``long_doc_chars`` through the served path, as appends of
  ``load_append_chars`` in boxcars of ``load_boxcar``, before the
  warm-up, all of it set-up;
- per-client rates: the long docs' clients share ``long_share`` of the
  rate and the short docs' clients the rest, each set on its own
  schedule of ``served.schedule``'s shape;
- its checks: ``served.py``'s six, each doc's text from both tiers
  (``answers_long.py``) held to ``refmerge_runs.py``, and
  ``long_docs_off_tier``, the long docs whose text did not come from
  the long tier;
- for the traced run, the long tier's kernel events in the window
  (count and device seconds, by ``LONG_KERNEL``) and its shapes, for
  ``kernel.long_apply_*``.
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
                                      _Procs, _reduced, _thirds, schedule)

#: the long tier's kernel in the device trace (``LONG_KERNEL_NAME`` in
#: ops/pallas_apply.py; the harness does not import the program)
LONG_KERNEL = "long_apply"


def long_schedule(seed: int, traffic: dict, n_short: int, n_long: int,
                  seconds: float) -> list:
    """[(idx, client, size, due)] of both sets of clients: the short
    docs' clients 0..n_short-1 at (1 - long_share) of the rate, the long
    docs' n_short.. at long_share, merged by due time."""
    rate = traffic["rate_ops_per_s"]
    share = traffic["long_share"]
    short = schedule(seed, {**traffic, "rate_ops_per_s": rate * (1 - share)},
                     n_short, seconds)
    long = schedule(f"{seed}/long", {**traffic,
                                     "rate_ops_per_s": rate * share},
                    n_long, seconds)
    merged = sorted([(due, c, size) for _i, c, size, due in short]
                    + [(due, n_short + c, size) for _i, c, size, due in long])
    return [(i, c, size, due) for i, (due, c, size) in enumerate(merged)]


def run(name: str, entry: dict, cell: dict, config: dict, args) -> dict:
    tr = cell["traffic"]
    if args.rate is not None:
        tr = {**tr, "rate_ops_per_s": args.rate}
    per_doc = config["clients_per_doc"]
    docs = [f"d{i}" for i in range(config["docs"])]
    long_docs = [f"L{i}" for i in range(config["long_doc_count"])]
    n_clients = (len(docs) + len(long_docs)) * per_doc
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    need = n_clients + 4096
    if soft < need:
        require(hard == resource.RLIM_INFINITY or hard >= need,
                f"RLIMIT_NOFILE hard limit {hard} < {need} sockets")
        resource.setrlimit(resource.RLIMIT_NOFILE, (need, hard))
    tmp = tempfile.mkdtemp(prefix="perfbench-long-")
    procs = _Procs(tmp)
    env = common.child_env({
        "FLUID_TPU_APPLIER_MAX_DOCS": config["applier_max_docs"],
        "FLUID_TPU_APPLIER_MAX_SLOTS": config["max_slots"],
        "FLUID_TPU_APPLIER_OPS_PER_DISPATCH": config["ops_per_dispatch"],
        "FLUID_TPU_APPLIER_LONG_DOCS": config["long_docs"],
        "FLUID_TPU_APPLIER_LONG_SLOTS": config["long_slots"]})
    try:
        return _run(name, entry, config, args, tr, procs, tmp, docs,
                    long_docs, env)
    except common.BenchFailure as e:
        raise common.BenchFailure(f"{e}\n{procs.tails()}") from None
    finally:
        procs.stop_all()
        shutil.rmtree(tmp, ignore_errors=True)


def _run(name, entry, config, args, tr, procs, tmp, docs, long_docs, env):
    per_doc = config["clients_per_doc"]
    log_dir = os.path.join(tmp, "log")
    state_dir = os.path.join(tmp, "state")
    trace_dir = os.path.join(tmp, "trace") if args.trace else None
    stage_args = ["benchmark.stage_wrap", "--log-dir", log_dir,
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
    require(info.get("long_docs") == config["long_docs"]
            and info.get("long_slots") == config["long_slots"],
            f"the stage shows no long tier of {config['long_docs']} x "
            f"{config['long_slots']}: {info}")
    note("stage " + json.dumps(info))
    core = procs.spawn("core", [
        "fluidframework_tpu.service.front_end", "--port", "0",
        "--log-dir", log_dir, "--consume-backchannel", state_dir], env)
    port = int(_line(core, "LISTENING", 120).rsplit(":", 1)[1])

    all_docs = docs + long_docs
    n_short = len(docs) * per_doc
    sched = long_schedule(args.seed, tr, n_short, len(long_docs) * per_doc,
                          args.seconds)
    n_workers = tr["workers"]
    chars = config["load_append_chars"]
    load = {"appends": -(-config["long_doc_chars"] // chars),
            "chars": chars, "boxcar": config["load_boxcar"],
            "window": tr["load_window"], "wait_s": tr["load_wait_s"]}
    workers = []
    for w in range(n_workers):
        mine_docs = list(range(w, len(all_docs), n_workers))
        mine = {d * per_doc + j for d in mine_docs for j in range(per_doc)}
        plan = {"tenant": TENANT, "seed": args.seed, "seconds": args.seconds,
                "ack_wait_s": tr["ack_wait_s"],
                "connect_concurrency": tr.get("connect_concurrency", 64),
                "clients": [[g, all_docs[g // per_doc]]
                            for g in sorted(mine)],
                "loaders": [d * per_doc for d in mine_docs
                            if d >= len(docs)],
                "load": load,
                "schedule": [s for s in sched if s[1] in mine]}
        path = os.path.join(tmp, f"plan{w}.json")
        with open(path, "w") as f:
            json.dump(plan, f)
        workers.append(procs.spawn(f"load{w}", [
            "benchmark.loadgen_long", "--port", str(port), "--plan", path,
            "--out", os.path.join(tmp, f"out{w}.json")], env, stdin=True))
    for w in workers:
        _line(w, "READY", 600)
    t_load = time.monotonic()
    for w in workers:
        w.stdin.write("LOAD\n")
        w.stdin.flush()
    for w in workers:
        bad = int(_line(w, "LOADED", tr["load_wait_s"] + 600))
        require(bad == 0, f"{bad} load boxcars not acked")
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
    docs_of = {r["gid"]: all_docs[r["gid"] // per_doc] for r in records}
    while time.monotonic() < deadline:
        applied.poll()
        if all(applied.now.get(docs_of[r["gid"]], 0) >= r["seq"]
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
        t = (applied.first_at(docs_of[r["gid"]], r["seq"])
             if "ack" in r else None)
        stale_ms.append(((t if t is not None else cap) - r["due"]) * 1e3)
    long_set = set(long_docs)
    stale_long = [s for r, s in zip(records, stale_ms)
                  if docs_of[r["gid"]] in long_set]
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
        "staleness_long_p99_ms": (percentile(stale_long, 99)
                                  if stale_long else None),
        "late_p50_ms": percentile(late_ms, 50),
        "late_p99_ms": percentile(late_ms, 99),
        "late_max_ms": max(late_ms),
        "ack_p50_ms_by_third": _thirds(records, t0, args.seconds),
        "rate_ops_per_s": tr["rate_ops_per_s"], "load_s": load_s}))
    note("stage_window " + json.dumps(window) + " stopped "
         + json.dumps(stopped))

    # ------------------------------------------------------ correctness
    from benchmark import answers, answers_long
    from benchmark.refmerge_runs import reference_text

    texts, host_docs, on_long = answers_long.device_texts(
        os.path.join(state_dir, "applier"))
    mismatch = order_bad = missing = log_ops = 0
    by_doc: dict = {}
    for r in acked:
        by_doc.setdefault(docs_of[r["gid"]], []).append(r)
    lengths = {}
    for d in all_docs:
        msgs = answers.read_doc_log(log, TENANT, d)
        log_ops += sum(op is not None for *_x, op in msgs)
        order_bad += answers.order_faults(msgs)
        index = answers.acked_index(msgs)
        missing += sum(not answers.boxcar_in_log(
            index, r["client_id"], r["cseq0"], r["cseq"], r["seq"],
            r["digest"]) for r in by_doc.get(d, ()))
        want = reference_text((seq, ref, cid, op)
                              for seq, _c, ref, cid, op in msgs
                              if op is not None)
        if d in long_set:
            lengths[d] = len(want)
        if texts.get((TENANT, d)) != want:
            mismatch += 1
    note("long " + json.dumps({
        "promotions": stopped.get("promotions"),
        "long_dispatches": stopped.get("long_dispatches"),
        "long_slots_peak": stopped.get("long_slots_peak"),
        "long_slots": config["long_slots"],
        "chars_min": min(lengths.values()),
        "chars_max": max(lengths.values())}))
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
        "long_docs_off_tier": {"value": sum((TENANT, d) not in on_long
                                            for d in long_docs),
                               "limit": 0},
    }
    device["memory_peak_bytes"] = stopped["peak_bytes_in_use"]
    layer = {"cell": name, "device_kind": device["kind"],
             "hops": {"submit_to_deli_ms": [
                 (r["deli_wall"] - r["sent_wall"]) * 1e3 for r in acked
                 if r.get("deli_wall") is not None]},
             "trace": _reduced(window),
             "long_kernel": _long_kernel(window, config)}
    return {"attempted": attempted, "failed": len(failed), "e2e": e2e,
            "checks": checks, "device": device, "layer": layer}


def _long_kernel(window: dict, config: dict):
    """The long kernel's dispatches in the traced window: their count,
    device seconds and the (rows, slots, ops) they ran at; None
    untraced."""
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
    for dev in events["device"].values():
        for op, s, d in dev["ops"]:
            if LONG_KERNEL in op and lo <= s < lo + dur:
                n += 1
                ns += d
    note(f"long_kernel {n} dispatches, {ns / 1e9} device s")
    return {"n": n, "seconds": ns / 1e9,
            "shape": [config["long_docs"], config["long_slots"],
                      config["ops_per_dispatch"]]}
