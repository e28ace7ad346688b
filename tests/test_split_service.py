"""Per-stage multi-process service composition (VERDICT r3 item 2).

Ref: the reference runs alfred/deli/scribe/… as independent processes
connected only by the Kafka log (routerlicious/src/*/www.ts,
kafka-service/runner.ts:13, docker-compose.yml). Here: the CORE process
(front_end --log-dir) owns sockets + deli + scriptorium + broadcaster
and is the durable log's single writer; the SCRIBE and APPLIER stages
run as separate OS processes tailing that log read-only
(service/stage_runner.py) and answering on their own backchannel logs.

The recovery property under test: kill -9 a stage mid-stream and
restart it over the same state dir — it resumes from its checkpoint,
replays idempotently, and the pipeline completes.
"""

from __future__ import annotations

import contextlib
import json
import os
import signal
import subprocess
import sys
import time

import pytest

from fluidframework_tpu.driver.network import NetworkDocumentServiceFactory
from fluidframework_tpu.loader import Loader

# the checkout under test (``python -m`` puts the cwd first on sys.path)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def wait_for(cond, timeout=30.0):
    t0 = time.time()
    while time.time() - t0 < timeout:
        if cond():
            return True
        time.sleep(0.02)
    return False


def _spawn(args, ready_line):
    # stderr goes to a per-process temp file so a failing composition can
    # dump every tier's diagnostics (VERDICT r4 weak #7: the harness used
    # to DEVNULL it, leaving composition failures evidence-free)
    import tempfile
    errf = tempfile.NamedTemporaryFile(
        mode="w+", prefix=f"{args[0].rsplit('.', 1)[-1]}-", suffix=".err",
        delete=False)
    proc = subprocess.Popen(
        [sys.executable] + (args if args[0] == "-c" else ["-m"] + args),
        stdout=subprocess.PIPE, stderr=errf, text=True,
        cwd=REPO)
    proc._stderr_path = errf.name
    line = proc.stdout.readline().strip()
    assert line.startswith(ready_line), line
    return proc, line


def _dump_stderr(procs) -> None:
    """Print every spawned process's captured stderr (on test failure)."""
    for p in (procs.values() if isinstance(procs, dict) else procs):
        path = getattr(p, "_stderr_path", None)
        if path and os.path.exists(path):
            with open(path) as f:
                text = f.read().strip()
            if text:
                print(f"--- stderr [{' '.join(p.args[2:4])}] ---\n{text}")


def _cleanup(procs) -> None:
    for p in (procs.values() if isinstance(procs, dict) else procs):
        if p.poll() is None:
            p.terminate()
            p.wait(timeout=10)
        path = getattr(p, "_stderr_path", None)
        if path and os.path.exists(path):
            os.unlink(path)


# a stage process that kill -9s itself the k-th time its checkpoint
# reaches a crash window: argv is the window, k, then stage_runner's own
_KILL_AT_WINDOW = """
import os, signal, sys
from fluidframework_tpu.service import stage_runner
window, k = sys.argv[1], int(sys.argv[2])
hits = [0]
def plane(point, **_ctx):
    if point == window:
        hits[0] += 1
        if hits[0] == k:
            os.kill(os.getpid(), signal.SIGKILL)
stage_runner.ApplierStage.fault_plane = staticmethod(plane)
sys.argv = sys.argv[:1] + sys.argv[3:]
stage_runner.main()
"""


def _spawn_stage(stage, log_dir, state_dir, kill_at=None):
    args = ["--stage", stage, "--log-dir", str(log_dir), "--state-dir",
            str(state_dir)]
    if kill_at is None:
        args = ["fluidframework_tpu.service.stage_runner"] + args
    else:
        args = ["-c", _KILL_AT_WINDOW, kill_at[0], str(kill_at[1])] + args
    proc, _ = _spawn(args, "READY")
    return proc


@contextlib.contextmanager
def split_deployment(tmp_path, stages=("scribe", "applier"),
                     kill_applier_at=None):
    log_dir = tmp_path / "log"
    storage_dir = tmp_path / "blobs"
    state_dirs = {s: tmp_path / f"{s}-state" for s in stages}
    procs = {}
    # spawn INSIDE the try: a tier that dies before its ready line must
    # still dump stderr and not leak the already-started processes
    try:
        for s in stages:
            procs[s] = _spawn_stage(
                s, log_dir, state_dirs[s],
                kill_applier_at if s == "applier" else None)
        core_args = ["fluidframework_tpu.service.front_end", "--port", "0",
                     "--log-dir", str(log_dir),
                     "--storage-dir", str(storage_dir)]
        if "scribe" in stages:
            core_args.append("--external-scribe")
        for s in stages:
            core_args += ["--consume-backchannel", str(state_dirs[s])]
        core, line = _spawn(core_args, "LISTENING")
        procs["core"] = core
        port = int(line.rsplit(":", 1)[1])
        yield port, procs, state_dirs, log_dir
    except BaseException:
        _dump_stderr(procs)
        raise
    finally:
        _cleanup(procs)


def _applied_seq(state_dir, tenant, doc):
    """Newest applied-seq status the applier stage reported."""
    from fluidframework_tpu.service.durable_log import DurableLog
    from fluidframework_tpu.service.stage_runner import BACKCHANNEL_TOPIC

    try:
        log = DurableLog(str(state_dir), readonly=True)
    except OSError:
        return 0
    try:
        n = log.refresh_topic(BACKCHANNEL_TOPIC)
        best = 0
        for i in range(n):
            rec = log.read(BACKCHANNEL_TOPIC, i)
            if rec.get("kind") == "applied" and rec["tenant"] == tenant \
                    and rec["doc"] == doc:
                best = max(best, rec["applied_seq"])
        return best
    finally:
        log.close()


def test_summary_flow_through_external_scribe(tmp_path):
    """Client summary validated + acked by the scribe PROCESS: upload →
    SUMMARIZE sequenced by the core's deli → scribe stage validates
    against the announced upload → ack ordered back through the
    backchannel → a fresh client boots from the committed version."""
    from fluidframework_tpu.runtime.summarizer import SummaryManager

    with split_deployment(tmp_path, stages=("scribe",)) as (port, _, _, _):
        loader = Loader(NetworkDocumentServiceFactory("127.0.0.1", port))
        c1 = loader.resolve("t", "doc")
        sm = SummaryManager(c1, max_ops=3)
        s = c1.runtime.create_data_store("default").create_channel(
            "text", "shared-string")
        s.insert_text(0, "abcdef")
        s.remove_text(0, 2)
        assert wait_for(lambda: sm.summaries_acked >= 1)
        c2 = loader.resolve("t", "doc")
        assert c2._base_snapshot is not None
        assert wait_for(lambda: c2.runtime.get_data_store("default")
                        .get_channel("text").get_text() == "cdef")


def test_scribe_stage_killed_and_restarted_mid_stream(tmp_path):
    """kill -9 the scribe process while a summary is in flight: no ack
    while it is down; a restart over the same state dir replays from its
    checkpoint and the ack lands."""
    from fluidframework_tpu.runtime.summarizer import SummaryManager

    with split_deployment(tmp_path, stages=("scribe",)) as (
            port, procs, state_dirs, log_dir):
        loader = Loader(NetworkDocumentServiceFactory("127.0.0.1", port))
        c1 = loader.resolve("t", "doc")
        sm = SummaryManager(c1, max_ops=3)
        s = c1.runtime.create_data_store("default").create_channel(
            "text", "shared-string")
        s.insert_text(0, "first")
        assert wait_for(lambda: sm.summaries_acked >= 1)

        os.kill(procs["scribe"].pid, signal.SIGKILL)
        procs["scribe"].wait(timeout=10)

        # summary submitted while the validator is DEAD
        for i in range(4):
            s.insert_text(0, f"{i}")
        time.sleep(1.0)
        assert sm.summaries_acked == 1  # nothing is acking

        procs["scribe"] = _spawn_stage("scribe", log_dir,
                                       state_dirs["scribe"])
        assert wait_for(lambda: sm.summaries_acked >= 2)


@pytest.mark.parametrize("window", [None, "stage.pre_checkpoint",
                                    "stage.post_checkpoint"])
def test_applier_stage_catches_up_and_survives_kill(tmp_path, window):
    """The TPU applier as its own process: consumes the deltas log,
    reports applied seqs on its backchannel, and after kill -9 +
    restart resumes from its device-farm checkpoint (warm restart, no
    full replay) to catch back up to the stream tail. The kill lands
    anywhere, or at the third delta checkpoint: before its delta (the
    restart replays from older offsets) or after it and before the
    offsets (it replays against newer state). The drain-stop then leaves
    a base and no delta, holding the doc's text."""
    from fluidframework_tpu.service.tpu_applier import (
        _delta_files, load_applier_checkpoint)

    kill_at = (window, 3) if window else None
    with split_deployment(tmp_path, stages=("scribe", "applier"),
                          kill_applier_at=kill_at) as (
            port, procs, state_dirs, log_dir):
        loader = Loader(NetworkDocumentServiceFactory("127.0.0.1", port))
        c1 = loader.resolve("t", "doc")
        s = c1.runtime.create_data_store("default").create_channel(
            "text", "shared-string")

        def caught_up(target):
            return _applied_seq(state_dirs["applier"], "t", "doc") >= target
        # in batches, each sequenced and then waited for: every batch is
        # a turn that drains records and ends with a delta checkpoint
        for batch in range(5):
            for i in range(4):
                s.insert_text(0, "ab")
            assert wait_for(lambda: c1.runtime.pending.count == 0,
                            timeout=60)
            tail = c1.delta_manager.last_processed_seq
            assert wait_for(lambda: caught_up(tail)
                            or procs["applier"].poll() is not None,
                            timeout=120)

        if window is None:
            os.kill(procs["applier"].pid, signal.SIGKILL)
        assert procs["applier"].wait(timeout=30) == -signal.SIGKILL
        for i in range(10):
            s.insert_text(0, "cd")
        assert wait_for(lambda: c1.runtime.pending.count == 0, timeout=60)
        tail2 = c1.delta_manager.last_processed_seq
        assert tail2 > tail

        procs["applier"] = _spawn_stage("applier", log_dir,
                                        state_dirs["applier"])
        assert wait_for(lambda: caught_up(tail2), timeout=120)
        procs["applier"].send_signal(signal.SIGINT)
        assert procs["applier"].wait(timeout=60) == 0
    path = str(state_dirs["applier"] / "applier")
    assert _delta_files(path) == []
    assert load_applier_checkpoint(path).get_text("t", "doc") == s.get_text()


def test_doc_partitioned_appliers_and_rebalance(tmp_path):
    """Two applier PROCESSES split the doc space by the stable doc hash;
    a redeploy with swapped assignments MOVES every doc to the other
    process, which catches up to the stream tail (VERDICT r3 item 2:
    rebalance between processes)."""
    from fluidframework_tpu.service.stage_runner import doc_partition

    def spawn_applier(log_dir, state_dir, part):
        proc, _ = _spawn(
            ["fluidframework_tpu.service.stage_runner", "--stage",
             "applier", "--log-dir", str(log_dir),
             "--state-dir", str(state_dir), "--partition", part],
            "READY")
        return proc

    log_dir = tmp_path / "log"
    states = [tmp_path / "a0", tmp_path / "a1"]
    appliers = [spawn_applier(log_dir, states[0], "0/2"),
                spawn_applier(log_dir, states[1], "1/2")]
    core, line = _spawn(
        ["fluidframework_tpu.service.front_end", "--port", "0",
         "--log-dir", str(log_dir),
         "--storage-dir", str(tmp_path / "blobs")], "LISTENING")
    port = int(line.rsplit(":", 1)[1])
    try:
        loader = Loader(NetworkDocumentServiceFactory("127.0.0.1", port))
        docs = [f"pdoc{i}" for i in range(4)]
        strings, tails = {}, {}
        for d in docs:
            c = loader.resolve("t", d)
            s = c.runtime.create_data_store("default").create_channel(
                "text", "shared-string")
            for _ in range(6):
                s.insert_text(0, "ab")
            strings[d] = (c, s)
            tails[d] = c.delta_manager.last_processed_seq
        owner = {d: doc_partition("t", d, 2) for d in docs}
        assert set(owner.values()) == {0, 1}  # both partitions in play

        # each doc is applied ONLY by its owner
        for d in docs:
            k = owner[d]
            assert wait_for(
                lambda d=d, k=k: _applied_seq(states[k], "t", d)
                >= tails[d], timeout=150)  # applier JAX boot + first
            # compile run ~50 s ALONE on this host; full-suite CPU
            # contention stretches it past the old 60 s window (flake)
            assert _applied_seq(states[1 - k], "t", d) == 0

        # REBALANCE: redeploy with swapped assignments; keep editing
        for p in appliers:
            p.terminate()
            p.wait(timeout=10)
        for d in docs:
            c, s = strings[d]
            s.insert_text(0, "z")
            tails[d] = c.delta_manager.last_processed_seq
        appliers = [spawn_applier(log_dir, states[0], "1/2"),
                    spawn_applier(log_dir, states[1], "0/2")]
        for d in docs:
            new_state = states[0] if owner[d] == 1 else states[1]
            assert wait_for(
                lambda d=d, st=new_state: _applied_seq(st, "t", d)
                >= tails[d], timeout=90)
    except BaseException:
        _dump_stderr(appliers + [core])
        raise
    finally:
        _cleanup(appliers + [core])


def _set_ctl(state_dir, mode: str, steps: int) -> None:
    import json as _json

    os.makedirs(state_dir, exist_ok=True)
    tmp = str(state_dir) + ".ctltmp"
    with open(tmp, "w") as f:
        _json.dump({"mode": mode, "steps": steps}, f)
    os.replace(tmp, os.path.join(state_dir, "ctl.json"))


def test_cross_process_deterministic_stepping(tmp_path):
    """Drive the scribe PROCESS one record at a time (VERDICT r4 #9 —
    opProcessingController.ts:16 across the process boundary): with the
    stage paused the summary is never acked even though the core is
    live; stepping releases exactly one log record per step, and the
    ack appears at one specific step boundary (the SUMMARIZE record's),
    never before."""
    from fluidframework_tpu.runtime.summarizer import SummaryManager
    from fluidframework_tpu.service.durable_log import DurableLog

    # pause the stage BEFORE it starts: the whole stream is stepped
    scribe_state = tmp_path / "scribe-state"
    _set_ctl(scribe_state, "pause", 0)

    with split_deployment(tmp_path, stages=("scribe",)) as (
            port, _, state_dirs, log_dir):
        loader = Loader(NetworkDocumentServiceFactory("127.0.0.1", port))
        c1 = loader.resolve("t", "doc")
        sm = SummaryManager(c1, max_ops=3)
        s = c1.runtime.create_data_store("default").create_channel(
            "text", "shared-string")
        s.insert_text(0, "abcdef")
        s.remove_text(0, 2)
        # the summarize op is in flight...
        assert wait_for(lambda: sm._pending_handle is not None)
        # ...but the paused validator never acks it
        time.sleep(2.0)
        assert sm.summaries_acked == 0

        # step the stage record by record; the ack must land at exactly
        # one boundary and stay monotonic. Per step, wait on the stage's
        # own observables — its post-step checkpoint (cp topic) and
        # backchannel emissions — instead of sleeping a fixed window
        # (a blind 5 s x ~7 pre-ack steps was ~35 s of pure sleep).
        state_view = DurableLog(str(scribe_state), readonly=True)
        try:
            last_cp = state_view.refresh_topic("cp/t/doc")
            last_bc = state_view.refresh_topic("backchannel")
            acked_at = None
            for step in range(1, 200):
                _set_ctl(scribe_state, "pause", step)
                t0 = time.time()
                while time.time() - t0 < 10.0 and sm.summaries_acked == 0:
                    cp = state_view.refresh_topic("cp/t/doc")
                    if cp > last_cp:
                        last_cp = cp
                        break  # stage consumed this step's budget
                    time.sleep(0.02)
                bc = state_view.refresh_topic("backchannel")
                if bc > last_bc:
                    # the stage emitted (ack/version) this step: give the
                    # core's backchannel poll the window to relay it
                    last_bc = bc
                    t1 = time.time()
                    while time.time() - t1 < 10.0 \
                            and sm.summaries_acked == 0:
                        time.sleep(0.02)
                if sm.summaries_acked >= 1:
                    acked_at = step
                    break
        finally:
            state_view.close()
        assert acked_at is not None, "stepping never released the ack"
        # the stream up to the summarize spans several records (joins,
        # the two edits, the upload announcement, the summarize): the
        # ack cannot have been released by the first step
        shared = DurableLog(str(log_dir), readonly=True)
        try:
            n_deltas = shared.refresh_topic("deltas/t/doc")
        finally:
            shared.close()
        assert acked_at > 1
        assert n_deltas >= acked_at - 1  # steps consumed real records


def test_full_production_composition(tmp_path):
    """EVERY tier at once, each its own OS process: storage server
    (commit/ref DAG), ordering core over the durable log with an
    external scribe, a scribe stage, two partitioned applier stages,
    and a gateway terminating the client socket. A client edits through
    the gateway, its summary is validated by the scribe PROCESS, the
    ref advances in the storage PROCESS, a fresh client boots from it,
    and an applier stage reports the doc applied to the stream tail."""
    from fluidframework_tpu.runtime.summarizer import SummaryManager
    from fluidframework_tpu.service.stage_runner import doc_partition
    from fluidframework_tpu.service.storage_client import (
        RemoteStorage,
        StorageConnection,
    )

    log_dir = tmp_path / "log"
    sstate = tmp_path / "scribe"
    astates = [tmp_path / "ap0", tmp_path / "ap1"]
    procs = []
    try:
        store, line = _spawn(
            ["fluidframework_tpu.service.storage_server",
             "--dir", str(tmp_path / "store")], "LISTENING")
        procs.append(store)
        sport = int(line.rsplit(":", 1)[1])
        procs.append(_spawn_stage("scribe", log_dir, sstate))
        for i, st in enumerate(astates):
            p, _ = _spawn(
                ["fluidframework_tpu.service.stage_runner", "--stage",
                 "applier", "--log-dir", str(log_dir),
                 "--state-dir", str(st), "--partition", f"{i}/2"],
                "READY")
            procs.append(p)
        core, line = _spawn(
            ["fluidframework_tpu.service.front_end", "--port", "0",
             "--log-dir", str(log_dir),
             "--storage-server", str(sport), "--external-scribe",
             "--consume-backchannel", str(sstate),
             "--consume-backchannel", str(astates[0]),
             "--consume-backchannel", str(astates[1])], "LISTENING")
        procs.append(core)
        port = int(line.rsplit(":", 1)[1])
        gw, line = _spawn(["fluidframework_tpu.service.gateway",
                           "--core-port", str(port)], "LISTENING")
        procs.append(gw)
        gport = int(line.rsplit(":", 1)[1])

        loader = Loader(NetworkDocumentServiceFactory("127.0.0.1", gport))
        c1 = loader.resolve("t", "doc")
        # max_ops must be reachable: the scenario produces exactly 4
        # OPERATION messages (2 channel attaches + 2 inserts) — at
        # max_ops=6 the heuristic would never fire and the ack assert
        # starves without any tier being at fault (the round-4 failure)
        sm = SummaryManager(c1, max_ops=4)
        s = c1.runtime.create_data_store("default").create_channel(
            "text", "shared-string")
        for w in ("full ", "stack "):
            s.insert_text(0, w)
        assert wait_for(lambda: sm.summaries_acked >= 1, timeout=60)

        st = RemoteStorage(StorageConnection("127.0.0.1", sport),
                           "t", "doc")
        assert st.get_ref() is not None      # scribe→core→storage ref
        c2 = loader.resolve("t", "doc")      # boots from the ref
        assert c2._base_snapshot is not None
        assert wait_for(lambda: c2.runtime.get_data_store("default")
                        .get_channel("text").get_text()
                        == "stack full ")
        owner = doc_partition("t", "doc", 2)
        tail = c1.delta_manager.last_processed_seq
        assert wait_for(
            lambda: _applied_seq(astates[owner], "t", "doc") >= tail,
            timeout=90)
    except BaseException:
        _dump_stderr(procs)
        raise
    finally:
        _cleanup(procs)


# ---------------------------------------------------- applier stage repairs

def _drive_docs(port: int, n_docs: int, prefix: str) -> None:
    """One raw load_async client per doc, one 4-op boxcar each."""
    out = subprocess.run(
        [sys.executable, "-m", "fluidframework_tpu.service.load_async",
         "--port", str(port), "--docs", str(n_docs), "--clients-per-doc",
         "1", "--rounds", "1", "--batch", "4", "--rate", "10",
         "--doc-prefix", prefix, "--tenant", "t", "--timeout", "60"],
        stdout=subprocess.PIPE, text=True, cwd=REPO, timeout=120,
        check=True)
    import json as _json

    r = _json.loads(out.stdout)
    assert r["acked"] == r["ops"] == n_docs * 4, r


def test_applier_stage_takes_max_docs_from_env(tmp_path, monkeypatch):
    """FLUID_TPU_APPLIER_MAX_DOCS sizes the stage's farm (it used to be
    pinned at 64): 70 docs land, and a drain-stop (SIGINT) checkpoints
    and reports them with exit code 0."""
    import json as _json

    monkeypatch.setenv("FLUID_TPU_APPLIER_MAX_DOCS", "72")
    monkeypatch.setenv("FLUID_TPU_APPLIER_MAX_SLOTS", "64")
    with split_deployment(tmp_path, stages=("applier",)) as (
            port, procs, state_dirs, _):
        stage = procs["applier"]
        report = _json.loads(stage.stdout.readline().split(" ", 1)[1])
        assert report["max_docs"] == 72 and report["max_slots"] == 64
        assert report["kernel_lane"] == "xla"  # the CPU: no Pallas lane
        _drive_docs(port, 70, "many")
        assert wait_for(lambda: all(
            _applied_seq(state_dirs["applier"], "t", f"many{i}") > 0
            for i in range(70)), timeout=120)
        stage.send_signal(signal.SIGINT)
        stopped = stage.stdout.readline()
        assert stopped.startswith("STOPPED "), stopped
        stats = _json.loads(stopped.split(" ", 1)[1])
        assert stats["docs"] == 70 and stats["host_escalations"] == 0
        assert stage.wait(timeout=30) == 0


def _line_with(proc, prefix: str) -> str:
    while True:
        line = proc.stdout.readline()
        assert line, f"no {prefix!r} line"
        if line.startswith(prefix):
            return line[len(prefix):].strip()


def test_armed_tracing_writes_both_span_files(tmp_path):
    """``--trace-dir`` on the core and the applier stage: SIGUSR1 arms
    the program's spans in both, SIGUSR2 writes each process's file and
    prints its path; together they hold every span on the served path,
    the stage's under a profile that marks where their window starts."""
    from benchmark.program_trace import window_start_ns

    log_dir, state_dir = tmp_path / "log", tmp_path / "applier-state"
    trace_dir = tmp_path / "trace"
    procs = {}
    try:
        procs["applier"], _ = _spawn(
            ["fluidframework_tpu.service.stage_runner", "--stage",
             "applier", "--log-dir", str(log_dir), "--state-dir",
             str(state_dir), "--trace-dir", str(trace_dir)], "READY")
        _line_with(procs["applier"], "STAGE")
        procs["core"], line = _spawn(
            ["fluidframework_tpu.service.front_end", "--port", "0",
             "--log-dir", str(log_dir), "--consume-backchannel",
             str(state_dir), "--trace-dir", str(trace_dir)], "LISTENING")
        port = int(line.rsplit(":", 1)[1])
        for p in procs.values():
            p.send_signal(signal.SIGUSR1)
        time.sleep(0.5)
        _drive_docs(port, 4, "traced")
        assert wait_for(lambda: all(
            _applied_seq(state_dir, "t", f"traced{i}") > 0
            for i in range(4)), timeout=120)
        time.sleep(0.3)
        docs = {}
        for role, p in procs.items():
            p.send_signal(signal.SIGUSR2)
            with open(_line_with(p, "SPANS")) as f:
                docs[role] = json.load(f)
    except BaseException:
        _dump_stderr(procs)
        raise
    finally:
        _cleanup(procs)
    stage, core = docs["applier"], docs["core"]
    assert {s[0] for s in stage["spans"]} >= {
        "stage.poll", "stage.drain", "stage.state_flush", "stage.checkpoint",
        "applier.flush", "applier.fence", "applier.stage_wave",
        "applier.dispatch", "checkpoint.delta",
        "checkpoint.delta.device_get", "checkpoint.delta.write",
        "stage.publish", "log.flush"}
    assert {s[0] for s in core["spans"]} >= {
        "frontend.ingress_batch", "frontend.backchannel", "log.flush",
        "frontend.presence_flush"}
    # the checkpoint's children: the fence, the delta and the publish
    by_idx = {i: s for i, s in enumerate(stage["spans"])}
    kids = {by_idx[s[3]][0]: [] for s in stage["spans"] if s[3] >= 0}
    for s in stage["spans"]:
        if s[3] >= 0:
            kids[by_idx[s[3]][0]].append(s[0])
    assert {"applier.fence", "checkpoint.delta", "stage.publish"} <= set(
        kids["stage.checkpoint"])
    assert stage["samples"]["stage.deli_to_applied.ms"]
    assert len(core["samples"]["frontend.loop.lag_ms"]) >= 50
    assert stage["counters"]["stage.checkpoint.bytes"] > 0
    assert stage["counters"]["stage.checkpoint.delta_docs"] >= 4
    assert stage["counters"]["stage.records.drained"] > 0
    # the shared log's tail: its streams refreshed in one native call a
    # poll, their blocks read in batches, no SegmentReader built
    assert stage["counters"]["storage.tail.streams"] > 0
    assert stage["counters"]["storage.tail.blocks"] > 0
    assert stage["counters"].get("storage.segment.reader_builds", 0) == 0
    assert window_start_ns(str(trace_dir)) is not None
    assert stage["overflow"] == core["overflow"] == 0


def test_applier_stage_exits_when_slots_run_out(tmp_path, monkeypatch):
    """More docs than applier_max_docs ends the stage with a non-zero
    exit naming the setting — never a stage dead but still running."""
    monkeypatch.setenv("FLUID_TPU_APPLIER_MAX_DOCS", "8")
    monkeypatch.setenv("FLUID_TPU_APPLIER_MAX_SLOTS", "64")
    with split_deployment(tmp_path, stages=("applier",)) as (
            port, procs, _, _):
        _drive_docs(port, 9, "full")
        stage = procs["applier"]
        assert stage.wait(timeout=120) != 0
        with open(stage._stderr_path) as f:
            assert "FLUID_TPU_APPLIER_MAX_DOCS" in f.read()


def test_escalated_doc_in_stage_replays_from_the_log(tmp_path):
    """A doc forced to host escalation inside the applier stage rebuilds
    from the deltas topic the stage tails: its text is the oracle's, not
    the empty replica the old replay source produced."""
    from fluidframework_tpu.mergetree.client import MergeTreeClient
    from fluidframework_tpu.service.durable_log import (DurableLog,
                                                        log_channel_stream)
    from fluidframework_tpu.service.load_gen import run_inproc
    from fluidframework_tpu.service.stage_runner import ApplierStage
    from fluidframework_tpu.service.synthetic import CHANNEL_ID, DS_ID

    log = DurableLog(str(tmp_path / "log"))
    run_inproc(n_docs=2, clients_per_doc=2, ops_per_client=24,
               batch_size=4, seed=3, log=log)
    log.flush()
    stage = ApplierStage(str(tmp_path / "log"), str(tmp_path / "state"))
    target = stage.applier.slot_of("bench", "doc1")
    ingests = {"n": 0}

    def plane(point, slot=None, **_ctx):
        # escalate doc1 at its third batch: two batches already staged
        # for the device are discarded by the flip
        if point == "applier.ingest" and slot == target:
            ingests["n"] += 1
            if ingests["n"] == 3:
                return "escalate_host"
        return None

    stage.applier.fault_plane = plane
    while stage.run_once():
        pass
    assert stage.applier.host_escalations == 1
    for doc in ("doc0", "doc1"):
        oracle = MergeTreeClient("oracle")
        for m in log_channel_stream(log, "bench", doc, DS_ID, CHANNEL_ID):
            oracle.apply_msg(m, local=False)
        assert oracle.get_text()
        assert stage.applier.get_text("bench", doc) == oracle.get_text()


def test_applier_stage_publishes_in_the_turn_that_drained(tmp_path):
    """A turn that drains a doc's records ends with a delta of the docs
    that moved, then an offset checkpoint and an "applied" record for
    each, at its last consumed seq; a doc with nothing new gets neither,
    and a turn that drained nothing writes nothing. A restart publishes
    every doc once."""
    from fluidframework_tpu.service.durable_log import (DurableLog,
                                                        record_messages)
    from fluidframework_tpu.service.load_gen import run_inproc
    from fluidframework_tpu.service.stage_runner import (BACKCHANNEL_TOPIC,
                                                         ApplierStage)

    src = DurableLog(str(tmp_path / "src"))
    run_inproc(n_docs=3, clients_per_doc=2, ops_per_client=8,
               batch_size=4, seed=4, log=src)
    src.flush()
    log = DurableLog(str(tmp_path / "log"))
    topics = src.list_topics("deltas/")
    held = topics[-1]

    def copy(topic, lo, hi):
        for i in range(lo, hi):
            log.append(topic, src.read(topic, i))
        log.flush()
        return record_messages(src.read(topic, hi - 1))[-1].sequence_number

    tails = {t: copy(t, 0, src.refresh_topic(t) - (2 if t == held else 0))
             for t in topics}
    stage = ApplierStage(str(tmp_path / "log"), str(tmp_path / "state"))
    seen = [0]

    def published():
        n = stage.state.refresh_topic(BACKCHANNEL_TOPIC)
        out = [stage.state.read(BACKCHANNEL_TOPIC, i)
               for i in range(seen[0], n)]
        seen[0] = n
        return {f"deltas/{r['tenant']}/{r['doc']}": r["applied_seq"]
                for r in out if r["kind"] == "applied"}

    def snap():
        return stage.counters.snapshot().get(
            "stage.checkpoint.delta_docs", 0)

    assert stage.run_once()
    assert published() == tails
    assert snap() == 3
    assert not stage.run_once()  # nothing new: no delta, no record
    assert published() == {} and snap() == 3
    n = src.refresh_topic(held)
    tail = copy(held, n - 2, n)
    cps = {t: stage.state.refresh_topic("cp/" + t.split("/", 1)[1])
           for t in topics}
    assert stage.run_once()
    assert published() == {held: tail}
    assert snap() == 4
    for t in topics:
        grew = stage.state.refresh_topic("cp/" + t.split("/", 1)[1]) \
            - cps[t]
        assert grew == (1 if t == held else 0), t
    # a restart publishes every doc it has a checkpoint for, once, with
    # nothing new to drain (a kill may have fallen between a doc's
    # offset checkpoint and its "applied" record)
    stage.checkpoint(final=True)
    stage.state.flush()
    stage = ApplierStage(str(tmp_path / "log"), str(tmp_path / "state"))
    seen[0] = stage.state.refresh_topic(BACKCHANNEL_TOPIC)
    stage.discover()
    assert stage._checkpoint_due()
    assert not stage.run_once()
    assert set(published()) == set(topics)
    assert not stage._checkpoint_due()


def test_stage_stops_tracing_between_turns_not_in_the_handler(tmp_path):
    """SIGUSR2 landing in the middle of a checkpoint only asks for the
    stop: tracing stays armed until the checkpoint is over, and the
    loop's next turn writes the file, with no span cut. A handler that
    stopped tracing itself could take the window's lock while a span on
    the same thread held it."""
    from fluidframework_tpu.service.durable_log import DurableLog
    from fluidframework_tpu.service.load_gen import run_inproc
    from fluidframework_tpu.service.stage_runner import ApplierStage
    from fluidframework_tpu.utils import telemetry

    log = DurableLog(str(tmp_path / "log"))
    run_inproc(n_docs=2, clients_per_doc=2, ops_per_client=16,
               batch_size=4, seed=5, log=log)
    log.flush()
    stage = ApplierStage(str(tmp_path / "log"), str(tmp_path / "state"))
    stage.trace_dir = str(tmp_path / "trace")
    seen: dict = {}
    publish, serve = stage._publish, stage._serve_trace_request

    def publish_after_signal():
        if not seen:
            os.kill(os.getpid(), signal.SIGUSR2)
            assert wait_for(lambda: stage.trace_request == "stop",
                            timeout=10)
            seen["armed"] = telemetry.tracing_armed()
        seen["publishes"] = seen.get("publishes", 0) + 1
        if seen["publishes"] > 20:  # the stop was never served
            stage.stopping = True
        publish()

    def serve_then_end():
        serve()
        if seen and not telemetry.tracing_armed():
            stage.stopping = True

    stage._publish = publish_after_signal
    stage._serve_trace_request = serve_then_end
    before = signal.signal(signal.SIGUSR2, lambda *_: setattr(
        stage, "trace_request", "stop"))
    stage.trace_request = "start"
    try:
        stage.run_forever()
    finally:
        signal.signal(signal.SIGUSR2, before)
        telemetry.tracing_stop()
    # the signal's turn, which drained the log; the drain-stop's
    # checkpoint, after the next turn stopped tracing first (that turn
    # drained nothing, so it checkpoints nothing)
    assert seen == {"armed": True, "publishes": 2}
    (name,) = os.listdir(tmp_path / "trace")
    with open(tmp_path / "trace" / name) as f:
        doc = json.load(f)
    assert doc["open"] == []
    names = [s[0] for s in doc["spans"]]
    assert "stage.publish" in names and "stage.checkpoint" in names
    assert all(s[2] >= 0 for s in doc["spans"])


def test_require_devices_never_swaps_a_chip_for_host_devices(monkeypatch):
    """On a chip host (no JAX_PLATFORMS=cpu) the mesh helper uses the
    chips present: too few raises, and neither jax_platforms nor
    XLA_FLAGS is touched to make up the count with CPU devices."""
    import types

    import jax

    from fluidframework_tpu.parallel import mesh

    platforms = jax.config.jax_platforms
    jax.config.update("jax_platforms", "")
    try:
        chips = [types.SimpleNamespace(platform="tpu",
                                       device_kind="TPU v5 lite")] * 4
        monkeypatch.setattr(jax, "devices", lambda *a, **k: chips)
        monkeypatch.delenv("XLA_FLAGS", raising=False)
        mesh.require_devices(4)
        with pytest.raises(RuntimeError, match="8 devices asked for, 4 tpu"):
            mesh.require_devices(8)
        assert "XLA_FLAGS" not in os.environ
        assert jax.config.jax_platforms == ""
    finally:
        jax.config.update("jax_platforms", platforms)
