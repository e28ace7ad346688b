"""Bring-up smoke: the served path with the device applier, on the chip.

    python chip_smoke.py             # one chip: phases "served", "inproc"
    python chip_smoke.py --chips 4   # four chips: phase "mesh" only

Phase ``served`` is the production shape over real sockets: a
``front_end`` core over the durable log, an applier ``stage_runner``
process tailing that log (the one process that holds the chip), and
``load_async`` workers driving 1,024 docs x 2 clients in 32-op boxcars.
Every op must be acked, the stage's "applied" records must reach each
doc's last sequence number, and the stage must run the Pallas lane on a TPU with no host
escalation. After the stage has exited, each doc's text in its
checkpoint must equal the scalar merge-tree oracle replayed from the
durable log. This process stays off JAX until then.

Phase ``inproc`` runs 8,192 docs in one process: ``run_inproc`` over
``DurableLog`` with an 8,192 x 256 applier; every op acked and applied,
no escalation, every doc's device text equal to the oracle.

Phase ``mesh`` runs ``TpuDocumentApplier(mesh=4)`` at 8,192 docs under
``run_inproc`` and a one-chip dense applier on the same logged op stream;
their texts must agree, every wave must ride the mesh lane, and the
state must sit on four devices.

Each phase prints one ``phase <name> {json}`` line (smoke figures, not
benchmark numbers). The last line is ``{"ok": true, "device": {...}}``;
any failure exits non-zero before it.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
TENANT = "bench"  # load_async's and run_inproc's default tenant

# rehearsal without the chip (tests only): tiny sizes, and the CPU is
# accepted where a TPU is otherwise required
_TINY = os.environ.get("CHIP_SMOKE_TINY") == "1"

SERVED_DOCS = 8 if _TINY else 1024
SERVED_WORKERS = 2 if _TINY else 4
INPROC_DOCS = 64 if _TINY else 8192
MESH_DOCS = 64 if _TINY else 8192
CLIENTS = 2
BOXCAR = 32
ROUNDS = 2            # 64 ops per client in 32-op boxcars
INPROC_OPS = 24       # ops per client
MAX_SLOTS = 256
WAIT_S = 600.0


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def report(phase: str, fields: dict) -> None:
    print(f"phase {phase} " + json.dumps(fields, default=str), flush=True)


def expected_platform() -> str:
    return "cpu" if _TINY else "tpu"


def expected_lane(platform: str) -> str:
    return "pallas" if platform == "tpu" else "xla"


def oracle_text(log, doc: str) -> str:
    """The scalar merge-tree oracle replayed from the durable log."""
    from fluidframework_tpu.mergetree.client import MergeTreeClient
    from fluidframework_tpu.service.durable_log import log_channel_stream
    from fluidframework_tpu.service.synthetic import CHANNEL_ID, DS_ID

    replica = MergeTreeClient(f"oracle/{doc}")
    for m in log_channel_stream(log, TENANT, doc, DS_ID, CHANNEL_ID):
        replica.apply_msg(m, local=False)
    return replica.get_text()


def compare_texts(applier, log, docs) -> int:
    bad = [d for d in docs if applier.get_text(TENANT, d)
           != oracle_text(log, d)]
    check(not bad, f"{len(bad)} docs differ from the oracle, e.g. {bad[:3]}")
    return len(docs)


# ------------------------------------------------------------- served

def _spawn(args: list, env: dict, errpath: str) -> subprocess.Popen:
    err = open(errpath, "w")
    try:
        return subprocess.Popen(
            [sys.executable, "-m", *args], cwd=REPO, env=env,
            stdout=subprocess.PIPE, stderr=err, text=True)
    finally:
        err.close()


def _line(proc: subprocess.Popen, prefix: str, what: str) -> str:
    line = proc.stdout.readline().strip()
    check(line.startswith(prefix), f"{what}: expected {prefix!r}, got "
          f"{line!r} (rc {proc.poll()})")
    return line[len(prefix):].strip()


def _last_seqs(log, docs) -> dict:
    from fluidframework_tpu.service.durable_log import record_messages

    out = {}
    for d in docs:
        topic = f"deltas/{TENANT}/{d}"
        n = log.refresh_topic(topic)
        out[d] = (record_messages(log.read(topic, n - 1))[-1]
                  .sequence_number if n else 0)
    return out


def phase_served(tmp: str) -> dict:
    from fluidframework_tpu.service.durable_log import DurableLog
    from fluidframework_tpu.service.stage_runner import BACKCHANNEL_TOPIC
    from fluidframework_tpu.utils.chip import compile_cache_dir

    log_dir = os.path.join(tmp, "log")
    state_dir = os.path.join(tmp, "applier")
    env = dict(os.environ, FLUID_TPU_APPLIER_MAX_DOCS=str(SERVED_DOCS),
               FLUID_TPU_APPLIER_MAX_SLOTS=str(MAX_SLOTS))
    per_worker = SERVED_DOCS // SERVED_WORKERS
    docs = [f"sw{w}d{i}" for w in range(SERVED_WORKERS)
            for i in range(per_worker)]
    procs = []
    t0 = time.perf_counter()
    try:
        stage = _spawn(["fluidframework_tpu.service.stage_runner",
                        "--stage", "applier", "--log-dir", log_dir,
                        "--state-dir", state_dir], env,
                       os.path.join(tmp, "stage.err"))
        procs.append(stage)
        _line(stage, "READY", "applier stage")
        dev = json.loads(_line(stage, "STAGE", "applier stage"))
        check(dev["platform"] == expected_platform(),
              f"applier stage runs on {dev['platform']}, not "
              f"{expected_platform()}")
        check(dev["kernel_lane"] == expected_lane(dev["platform"]),
              f"applier stage resolved kernel lane {dev['kernel_lane']}")
        check(dev["max_docs"] == SERVED_DOCS, f"stage geometry {dev}")
        core = _spawn(["fluidframework_tpu.service.front_end", "--port",
                       "0", "--log-dir", log_dir, "--consume-backchannel",
                       state_dir], env, os.path.join(tmp, "core.err"))
        procs.append(core)
        port = int(_line(core, "LISTENING", "front end").rsplit(":", 1)[1])
        t_load = time.perf_counter()
        workers = [_spawn(
            ["fluidframework_tpu.service.load_async", "--port", str(port),
             "--docs", str(per_worker), "--clients-per-doc", str(CLIENTS),
             "--rounds", str(ROUNDS), "--batch", str(BOXCAR),
             "--rate", "2", "--seed", str(w), "--doc-prefix", f"sw{w}d",
             "--tenant", TENANT, "--timeout", str(WAIT_S)],
            env, os.path.join(tmp, f"load{w}.err"))
            for w in range(SERVED_WORKERS)]
        procs.extend(workers)
        ops = acked = 0
        for w in workers:
            out, _ = w.communicate(timeout=WAIT_S + 120)
            check(w.returncode == 0, f"load worker rc {w.returncode}")
            r = json.loads(out)
            check(not r["errors"], f"load worker errors {r['errors'][:3]}")
            ops += r["ops"]
            acked += r["acked"]
        load_s = time.perf_counter() - t_load
        check(ops == SERVED_DOCS * CLIENTS * ROUNDS * BOXCAR,
              f"{ops} ops submitted")
        check(acked == ops, f"{acked} of {ops} ops acked")

        # the stage's "applied" records must reach every doc's last seq
        log = DurableLog(log_dir, readonly=True)
        bc = DurableLog(state_dir, readonly=True)
        applied: dict = {}
        seen = 0
        deadline = time.monotonic() + WAIT_S
        while True:
            n = bc.refresh_topic(BACKCHANNEL_TOPIC)
            for i in range(seen, n):
                rec = bc.read(BACKCHANNEL_TOPIC, i)
                if rec.get("kind") == "applied":
                    applied[rec["doc"]] = max(applied.get(rec["doc"], 0),
                                              rec["applied_seq"])
            seen = n
            last = _last_seqs(log, docs)
            lag = [d for d in docs if applied.get(d, 0) < last[d]]
            if not lag:
                break
            check(stage.poll() is None, f"applier stage exited rc "
                  f"{stage.returncode}")
            check(time.monotonic() < deadline,
                  f"{len(lag)} docs not applied to their tail, e.g. "
                  f"{lag[:3]}")
            time.sleep(0.5)
        applied_s = time.perf_counter() - t_load

        # drain-stop the stage: it checkpoints, reports and frees the chip
        stage.send_signal(signal.SIGINT)
        stats = json.loads(_line(stage, "STOPPED", "applier stage"))
        check(stage.wait(timeout=120) == 0, f"stage rc {stage.returncode}")
        check(stats["host_escalations"] == 0,
              f"{stats['host_escalations']} host escalations")
        check(stats["docs"] == SERVED_DOCS, f"stage placed {stats['docs']}")
        check(stats["ops_applied"] == ops,
              f"stage applied {stats['ops_applied']} of {ops} acked ops")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait()
    return {"docs": SERVED_DOCS, "clients": CLIENTS, "ops": ops,
            "acked": acked, "load_s": load_s, "applied_s": applied_s,
            "wall_s": time.perf_counter() - t0,
            "device_kind": dev["device_kind"],
            "platform": dev["platform"], "kernel_lane": dev["kernel_lane"],
            "host_escalations": stats["host_escalations"],
            "ops_applied": stats["ops_applied"],
            "dispatches": stats["dispatches"],
            "compile_s": stats["compile_s"], "compiles": stats["compiles"],
            "peak_bytes_in_use": stats["peak_bytes_in_use"],
            "compile_cache": compile_cache_dir()}, docs, log_dir, state_dir


def check_served_texts(docs, log_dir: str, state_dir: str) -> int:
    """After the stage exited: its checkpoint against the oracle."""
    from fluidframework_tpu.service.durable_log import DurableLog
    from fluidframework_tpu.service.tpu_applier import load_applier_checkpoint

    applier = load_applier_checkpoint(os.path.join(state_dir, "applier"))
    check(not applier._host_docs, "checkpoint holds host-escalated docs")
    return compare_texts(applier, DurableLog(log_dir, readonly=True), docs)


# ------------------------------------------------------------- inproc

def phase_inproc(tmp: str, clock, device) -> dict:
    from fluidframework_tpu.service.durable_log import DurableLog
    from fluidframework_tpu.service.load_gen import run_inproc
    from fluidframework_tpu.service.tpu_applier import TpuDocumentApplier
    from fluidframework_tpu.utils.chip import (compile_cache_dir,
                                               peak_bytes_in_use)

    c0 = clock.seconds
    t0 = time.perf_counter()
    log = DurableLog(os.path.join(tmp, "inproc-log"))
    applier = TpuDocumentApplier(max_docs=INPROC_DOCS, max_slots=MAX_SLOTS)
    stats = run_inproc(n_docs=INPROC_DOCS, clients_per_doc=CLIENTS,
                       ops_per_client=INPROC_OPS, applier=applier,
                       flush_every=4 * INPROC_DOCS, seed=5,
                       batch_size=INPROC_OPS, array_lane=True, log=log)
    check(stats.ops_acked == stats.ops_submitted == stats.applier_ops,
          f"submitted {stats.ops_submitted} acked {stats.ops_acked} "
          f"applied {stats.applier_ops}")
    check(stats.applier_escalations == 0,
          f"{stats.applier_escalations} escalations")
    check(applier.kernel_lane == expected_lane(device.platform),
          f"kernel lane {applier.kernel_lane}")
    t_run = time.perf_counter() - t0
    n = compare_texts(applier, log, [f"doc{i}" for i in range(INPROC_DOCS)])
    return {"docs": INPROC_DOCS, "clients": CLIENTS,
            "ops": stats.ops_submitted, "acked": stats.ops_acked,
            "run_s": t_run, "wall_s": time.perf_counter() - t0,
            "texts_equal": n, "kernel_lane": applier.kernel_lane,
            "host_escalations": applier.host_escalations,
            "dispatches": applier.dispatches,
            "compile_s": clock.seconds - c0,
            "device_kind": device.device_kind,
            "peak_bytes_in_use": peak_bytes_in_use(device),
            "compile_cache": compile_cache_dir()}


# --------------------------------------------------------------- mesh

def phase_mesh(tmp: str, clock, n_chips: int) -> dict:
    import jax

    from fluidframework_tpu.service.durable_log import DurableLog
    from fluidframework_tpu.service.load_gen import run_inproc
    from fluidframework_tpu.service.durable_log import log_channel_stream
    from fluidframework_tpu.service.synthetic import CHANNEL_ID, DS_ID
    from fluidframework_tpu.service.tpu_applier import TpuDocumentApplier
    from fluidframework_tpu.utils.chip import (compile_cache_dir,
                                               peak_bytes_in_use)

    check(len(jax.devices()) == n_chips,
          f"{len(jax.devices())} devices, not {n_chips}")
    c0 = clock.seconds
    t0 = time.perf_counter()
    log = DurableLog(os.path.join(tmp, "mesh-log"))
    mesh = TpuDocumentApplier(max_docs=MESH_DOCS, max_slots=MAX_SLOTS,
                              mesh=n_chips)
    stats = run_inproc(n_docs=MESH_DOCS, clients_per_doc=CLIENTS,
                       ops_per_client=INPROC_OPS, applier=mesh,
                       flush_every=4 * MESH_DOCS, seed=7,
                       batch_size=INPROC_OPS, array_lane=True, log=log)
    check(stats.ops_acked == stats.ops_submitted == stats.applier_ops,
          f"submitted {stats.ops_submitted} acked {stats.ops_acked} "
          f"applied {stats.applier_ops}")
    mesh_s = time.perf_counter() - t0
    devices = {s.device for s in mesh.state.length.addressable_shards}
    check(len(devices) == n_chips, f"mesh state on {len(devices)} devices")
    check(mesh.mesh_waves == mesh.dispatches > 0,
          f"mesh_waves {mesh.mesh_waves} dispatches {mesh.dispatches}")

    # the one-chip dense lane on the same logged op stream
    dense = TpuDocumentApplier(max_docs=MESH_DOCS, max_slots=MAX_SLOTS)
    docs = [f"doc{i}" for i in range(MESH_DOCS)]
    for d in docs:
        msgs = list(log_channel_stream(log, TENANT, d, DS_ID, CHANNEL_ID))
        dense.ingest_batch(TENANT, d, [(m, m.contents) for m in msgs])
    dense.finalize()
    platform = jax.devices()[0].platform
    for a in (mesh, dense):
        check(a.kernel_lane == expected_lane(platform),
              f"kernel lane {a.kernel_lane}")
        check(a.host_escalations == 0, f"{a.host_escalations} escalations")
    bad = [d for d in docs
           if mesh.get_text(TENANT, d) != dense.get_text(TENANT, d)]
    check(not bad, f"{len(bad)} docs differ mesh vs dense, e.g. {bad[:3]}")
    return {"docs": MESH_DOCS, "chips": n_chips,
            "docs_per_chip": MESH_DOCS // n_chips,
            "ops": stats.ops_submitted, "mesh_run_s": mesh_s,
            "wall_s": time.perf_counter() - t0, "texts_equal": len(docs),
            "mesh_waves": mesh.mesh_waves, "dispatches": mesh.dispatches,
            "dense_dispatches": dense.dispatches,
            "kernel_lane": mesh.kernel_lane,
            "state_devices": len(devices),
            "compile_s": clock.seconds - c0,
            "device_kind": jax.devices()[0].device_kind,
            "peak_bytes_in_use": [peak_bytes_in_use(d)
                                  for d in jax.devices()],
            "compile_cache": compile_cache_dir()}


# --------------------------------------------------------------- main

def _hold_chip():
    """This process takes the chip: compile cache, then the device."""
    from fluidframework_tpu.utils.chip import (CompileClock,
                                               init_compile_cache,
                                               require_tpu)

    init_compile_cache()
    device = require_tpu()
    check(device.platform == expected_platform(),
          f"JAX runs on {device.platform}, not {expected_platform()}")
    return device, CompileClock()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="run the served path with the device applier once "
                    "on the chip and check its results")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the doc-mesh phase on four chips")
    args = ap.parse_args(argv)
    tmp = tempfile.mkdtemp(prefix="chip-smoke-")
    try:
        if args.chips == 4:
            device, clock = _hold_chip()
            report("mesh", phase_mesh(tmp, clock, 4))
        else:
            # the parent stays off JAX while the stage holds the chip
            served, docs, log_dir, state_dir = phase_served(tmp)
            check("jax" not in sys.modules, "the smoke imported JAX while "
                  "the applier stage held the chip")
            device, clock = _hold_chip()
            served["texts_equal"] = check_served_texts(docs, log_dir,
                                                       state_dir)
            report("served", served)
            report("inproc", phase_inproc(tmp, clock, device))
        import jax

        print(json.dumps({"ok": True, "device": {
            "platform": jax.devices()[0].platform,
            "kind": jax.devices()[0].device_kind,
            "count": len(jax.devices())}}), flush=True)
        return 0
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        for name in sorted(os.listdir(tmp)):
            if name.endswith(".err"):
                with open(os.path.join(tmp, name)) as f:
                    tail = f.read()[-3000:]
                if tail.strip():
                    print(f"--- {name} ---\n{tail}", file=sys.stderr)
        return 1
    finally:
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
