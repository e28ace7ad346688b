"""Headline benchmark: the service path end-to-end, plus the raw kernel.

Three measurements in ONE JSON line (round-1 VERDICT #2: an end-to-end
number, not a dispatch microbenchmark):

- ``value`` (headline): sequenced ops/sec through the FULL in-process
  service path at 1024 docs × 2 clients — boxcar submission, deli's
  vectorized ticket fast lane, scriptorium persistence, scribe protocol
  replica, broadcast fan-out to every connected client, AND the async
  TpuDocumentApplier consuming the stream as packed device waves
  (BASELINE config 4 analog; north star 50k ops/s).
- ``kernel_ops_per_sec``: the batched device kernel alone at scale
  (10k-doc scribe-replay role, BASELINE config 5), timed to a host
  readback of the final counts (it waits for every queued step, and the
  overflow/count asserts need the values anyway).
- ``net_p99_ack_ms`` / ``net_p50_ack_ms``: op-ack latency through real
  TCP sockets (submit → own op broadcast back), north star p99 < 50 ms.

vs_north_star_50k is the headline value against the 50k north star
(BASELINE.json — the reference repo publishes no numbers of its own);
vs_scalar_deli_x is the same value against the single-process scalar
``_ticket`` lane, the per-op reference the array/columnar path amortizes.
"""

from __future__ import annotations

import json
import time

import numpy as np

NORTH_STAR_OPS_PER_SEC = 50_000.0


def bench_kernel() -> tuple:
    """Batched device apply+zamboni at 8k docs, honest readback timing.

    Returns (pallas_ops_per_sec, xla_ops_per_sec): the Pallas
    VMEM-resident kernel (ops/pallas_apply.py) is the headline; the XLA
    scan rides along as the comparison baseline."""
    import jax
    import jax.numpy as jnp

    from fluidframework_tpu.ops.apply import (
        apply_ops_batch,
        compact_batch,
        wave_min_seq,
    )
    from fluidframework_tpu.ops.doc_state import DocState
    from fluidframework_tpu.ops.opgen import generate_batch_ops
    from fluidframework_tpu.ops.pallas_apply import pallas_apply_ops_batch

    # K=64 halves the per-dispatch fixed overhead per op vs K=32 (the
    # scan step cost is dominated by dispatch, not depth); S=256 leaves
    # zero docs overflowing on this stream — checked below, because an
    # overflowed doc silently skips work and would inflate the number
    D, S, K, NB = 8192, 256, 64, 2
    rng = np.random.default_rng(42)

    state0 = jax.vmap(lambda _: DocState.empty(S))(jnp.arange(D))
    stream = generate_batch_ops(
        rng, D, K * NB, remove_fraction=0.4, annotate_fraction=0.1, max_insert=8)
    batches = [jnp.asarray(stream[:, i * K : (i + 1) * K]) for i in range(NB)]

    results = []
    for apply_fn in (pallas_apply_ops_batch, apply_ops_batch):
        @jax.jit
        def step(state, ops, apply_fn=apply_fn):
            state = apply_fn(state, ops)
            return compact_batch(state, wave_min_seq(ops))

        # compile + warm up, with a real transfer as the sync point
        s = step(state0, batches[0])
        assert int(np.asarray(s.count).min()) > 0

        t0 = time.perf_counter()
        cur = state0
        for ops in batches:
            cur = step(cur, ops)
        counts = np.asarray(cur.count)  # host readback = the honest fence
        dt = time.perf_counter() - t0
        assert counts.min() > 0, "streams failed to apply"
        assert not np.asarray(cur.overflow).any(), "overflowed docs skip work"
        results.append(D * K * NB / dt)
    return results[0], results[1]


def bench_scalar_deli() -> float:
    """The scalar ``_ticket`` lane in isolation: one process, one doc,
    per-op RawMessages through deli.handler — no boxcars, no arrays.

    This is the per-op-object reference the boxcar/array/columnar path
    amortizes; ``vs_scalar_deli_x`` publishes how much of the headline
    comes from batching vs from the kernel. Median of 3 trials."""
    from fluidframework_tpu.protocol.messages import (
        DocumentMessage,
        MessageType,
    )
    from fluidframework_tpu.service.core import QueuedMessage
    from fluidframework_tpu.service.deli import DeliLambda, RawMessage

    def chanop(i: int) -> dict:
        return {"kind": "chanop", "address": "default",
                "contents": {"address": "text",
                             "contents": {"type": 0, "pos": i,
                                          "text": "abcdefgh"}}}

    n = 100_000
    rates = []
    for trial in range(3):
        deli = DeliLambda("bench", "scalar",
                          send_sequenced=lambda m: None,
                          send_nack=lambda c, nk: None,
                          clock=lambda: 1000.0)
        deli.handler(QueuedMessage(1, "raw", 0, RawMessage(
            "bench", "scalar", None,
            DocumentMessage(-1, -1, MessageType.CLIENT_JOIN,
                            {"clientId": "c1"}), 1000.0)))
        records = [
            QueuedMessage(i + 2, "raw", 0, RawMessage(
                "bench", "scalar", "c1",
                DocumentMessage(i + 1, 0, MessageType.OPERATION,
                                chanop(i)), 1000.0))
            for i in range(n)
        ]
        handler = deli.handler
        t0 = time.perf_counter()
        for rec in records:
            handler(rec)
        dt = time.perf_counter() - t0
        assert deli.sequence_number == n + 1  # join + every op ticketed
        rates.append(n / dt)
    return sorted(rates)[1]


def bench_service() -> dict:
    """Full in-process pipeline with the TPU applier riding the stream.

    BASELINE config 4 scale: 1024 docs × 2 clients, each client's 32-op
    submissions riding the raw log as one boxcar (deli's vectorized fast
    lane), the async TpuDocumentApplier consuming the broadcast as a
    packed-wave device pipeline stage. Median of 3 trials: the shared
    bench host has bursty CPU contention."""
    import gc

    from fluidframework_tpu.service.load_gen import run_inproc
    from fluidframework_tpu.service.tpu_applier import TpuDocumentApplier

    # compile warm-up on a THROWAWAY applier: reusing it would leave
    # warm-up doc state in the placement slots the measured docs hash to
    # (same names, fresh server, seqs restarting at 1)
    warm = TpuDocumentApplier(max_docs=1024, max_slots=256,
                              ops_per_dispatch=32)
    run_inproc(n_docs=8, clients_per_doc=2, ops_per_client=8,
               applier=warm, seed=99, batch_size=8)
    warm.close()
    # GC posture for the measured trials: the op path allocates acyclic
    # graphs only, and collector walks over the live scriptorium logs
    # were the dominant mid-trial latency source — disable the cycle
    # collector outright (service processes run the same posture) and
    # sweep between trials.
    def trial(seed: int, array_lane: bool) -> dict:
        gc.collect()      # contention can depress 2 trials in a row
        gc.freeze()
        gc.disable()
        applier = TpuDocumentApplier(
            max_docs=1024, max_slots=256, ops_per_dispatch=32,
            async_dispatch=True, min_wave_ops=32768)
        stats = run_inproc(n_docs=1024, clients_per_doc=2, ops_per_client=48,
                           applier=applier, flush_every=4096, seed=seed,
                           batch_size=24, array_lane=array_lane)
        applier.close()
        gc.enable()
        gc.unfreeze()
        assert stats.applier_escalations == 0
        assert stats.ops_acked == stats.ops_submitted
        assert stats.applier_ops == stats.ops_submitted
        return stats.summary()

    # headline: the ARRAY LANE (the deli-tpu marshal, SURVEY §7 —
    # boxcars ride the pipeline as int arrays; deli tickets with numpy;
    # the applier bulk-loads device chunks; no per-op objects anywhere
    # on the hot path). The dict lane rides along for comparison — the
    # same pipeline fed per-op message objects.
    trials = [trial(1 + t, True) for t in range(5)]
    trials.sort(key=lambda s: s["ops_per_sec"])
    headline = trials[len(trials) // 2]
    dict_lane = sorted(trial(20 + t, False)["ops_per_sec"]
                       for t in range(3))[1]
    headline["ops_per_sec_dict_lane"] = dict_lane

    # the same pipeline over the DURABLE C++ op log (the split-service
    # core's posture: every raw/delta record encoded + written to disk)
    import tempfile

    from fluidframework_tpu.service.durable_log import DurableLog

    def durable_trial(seed: int) -> float:
        gc.collect()
        gc.freeze()
        gc.disable()
        applier = TpuDocumentApplier(
            max_docs=1024, max_slots=256, ops_per_dispatch=32,
            async_dispatch=True, min_wave_ops=32768)
        stats = run_inproc(n_docs=1024, clients_per_doc=2,
                           ops_per_client=48, applier=applier,
                           flush_every=4096, seed=seed, batch_size=24,
                           array_lane=True,
                           log=DurableLog(tempfile.mkdtemp()))
        applier.close()
        gc.enable()
        gc.unfreeze()
        assert stats.ops_acked == stats.ops_submitted
        return stats.ops_per_sec
    headline["ops_per_sec_durable_log"] = round(
        sorted(durable_trial(40 + t) for t in range(3))[1], 1)

    # the north star names 10k-doc scale: prove the number holds at 8192
    # concurrent docs (393k ops through the full path, same assertions)
    warm8k = TpuDocumentApplier(max_docs=8192, max_slots=256,
                                ops_per_dispatch=32)
    run_inproc(n_docs=8, clients_per_doc=2, ops_per_client=8,
               applier=warm8k, seed=99, batch_size=8)
    warm8k.close()
    big = []
    for t in range(5):  # median of 5, same protocol as the headline
        gc.collect()
        gc.freeze()
        gc.disable()
        applier = TpuDocumentApplier(
            max_docs=8192, max_slots=256, ops_per_dispatch=32,
            async_dispatch=True, min_wave_ops=196608)
        stats = run_inproc(n_docs=8192, clients_per_doc=2,
                           ops_per_client=24, applier=applier,
                           flush_every=32768, seed=5 + t, batch_size=24,
                           array_lane=True)
        applier.close()
        gc.enable()
        gc.unfreeze()
        assert stats.applier_escalations == 0
        assert stats.ops_acked == stats.ops_submitted
        assert stats.applier_ops == stats.ops_submitted
        big.append(stats.ops_per_sec)
    big.sort()
    headline["ops_per_sec_8k_docs"] = round(big[2], 1)
    # run-to-run spread at the scale config (task: keep < 15%)
    headline["ops_per_sec_8k_docs_spread"] = round(
        (big[-1] - big[0]) / big[2], 3)
    return headline


def bench_segment_storage() -> dict:
    """Columnar segment store vs the scalar record lane over the SAME
    ~100k-op deltas stream: recovery-replay seconds per GB of log, and
    seq-range backfill throughput.

    The segmented lane persists each 32-op boxcar as one packed column
    block; recovery decode is a frombuffer per block and backfill is a
    binary search plus raw byte-range copies (``backfill_decodes`` is
    counter-verified ZERO — no block is decoded server-side). The
    legacy lane is the pre-segment record format (``segmented=False``),
    replayed through the same DurableLog API."""
    import os
    import shutil
    import tempfile

    from fluidframework_tpu.service.array_batch import (
        ArrayBoxcar,
        SequencedArrayBatch,
    )
    from fluidframework_tpu.service.durable_log import DurableLog

    N_RECORDS, OPS = 3125, 32  # 100k ops in knee-geometry boxcars
    topic = "deltas/t/bench-doc"

    def record(base_seq: int) -> dict:
        text = "abcdefgh" * (OPS // 4)
        box = ArrayBoxcar(
            tenant_id="t", document_id="bench-doc", client_id="c1",
            ds_id="default", channel_id="text",
            kind=np.zeros(OPS, np.int8),
            a=np.arange(OPS, dtype=np.int32),
            b=np.zeros(OPS, np.int32),
            cseq=np.arange(base_seq, base_seq + OPS, dtype=np.int32),
            rseq=np.full(OPS, base_seq - 1, np.int32),
            text=text,
            text_off=np.arange(0, 2 * OPS + 2, 2, dtype=np.int32),
            props=None, timestamp=float(base_seq))
        return {"tenant_id": "t", "document_id": "bench-doc",
                "abatch": SequencedArrayBatch(
                    boxcar=box, base_seq=base_seq,
                    msns=np.arange(base_seq, base_seq + OPS,
                                   dtype=np.int64),
                    timestamp=float(base_seq))}

    def stream_bytes(d: str) -> int:
        return sum(os.path.getsize(os.path.join(d, f))
                   for f in os.listdir(d))

    out: dict = {}
    total_ops = N_RECORDS * OPS
    for segmented, tag in ((True, ""), (False, "_legacy")):
        d = tempfile.mkdtemp(prefix="bench-seglog-")
        try:
            log = DurableLog(d, segmented=segmented)
            seq = 1
            for _ in range(N_RECORDS):
                log.append(topic, record(seq))
                seq += OPS
            log.sync()
            log.close()
            nbytes = stream_bytes(d)

            # recovery replay: a fresh process decodes the whole stream
            log = DurableLog(d, segmented=segmented)
            log._read_cache.clear()
            t0 = time.perf_counter()
            n = log.length(topic)
            replayed = 0
            for i in range(n):
                replayed += log.read(topic, i)["abatch"].n
            recovery_s = time.perf_counter() - t0
            assert replayed == total_ops

            # backfill: the full seq range through the columnar door
            # (raw byte ranges) or, on the legacy lane, the record
            # replay a scalar backfill performs
            before = log.counters.snapshot()
            t0 = time.perf_counter()
            res = log.delta_blocks(topic, 0, total_ops + 1)
            if res is not None:
                payloads, legacy_msgs = res
                served = len(payloads)
            else:
                served = 0
                log._read_cache.clear()
                for i in range(n):
                    served += len(
                        log.read(topic, i)["abatch"].messages())
            backfill_s = time.perf_counter() - t0
            after = log.counters.snapshot()
            if segmented:
                assert served == N_RECORDS
                out["backfill_decodes"] = (
                    after.get("storage.segment.decodes", 0)
                    - before.get("storage.segment.decodes", 0))
                assert out["backfill_decodes"] == 0
            log.close()

            out[f"durable_log_recovery_s_per_gb{tag}"] = round(
                recovery_s / (nbytes / 1e9), 3)
            out[f"backfill_ops_per_sec{tag}"] = round(
                total_ops / backfill_s, 1)
            out[f"durable_log_bytes_per_op{tag}"] = round(
                nbytes / total_ops, 2)
        finally:
            shutil.rmtree(d, ignore_errors=True)
    return out


REPO = __import__("os").path.dirname(__import__("os").path.abspath(__file__))


def _lean_cmd(mod: str, *args: str) -> list:
    """Service/worker process command line."""
    import sys

    return [sys.executable, "-m", mod, *args]


def _spawn_listening(mod: str, *args: str):
    import subprocess

    proc = subprocess.Popen(
        _lean_cmd(mod, *args), stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, text=True, cwd=REPO)
    line = proc.stdout.readline().strip()
    assert line.startswith("LISTENING"), line
    return proc, int(line.rsplit(":", 1)[1])


def _proc_cpu_s(pid: int) -> float:
    """utime+stime of a process from /proc/<pid>/stat, in seconds —
    the per-core-lane CPU attribution for the sharded rows (on a 1-CPU
    host the lanes time-slice, and this is the published proof each
    subprocess did real sequencing work rather than idling)."""
    import os

    try:
        with open(f"/proc/{pid}/stat") as f:
            s = f.read()
        fields = s[s.rindex(")") + 2:].split()
        ticks = int(fields[11]) + int(fields[12])  # utime + stime
        return ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError):
        return 0.0


def _query_counters(port: int) -> dict:
    """The front end's socket-tier batching counters (admin_counters
    RPC) — published so a run that never engaged ingress coalescing /
    flush eliding / fan-out caching is visible in the report."""
    import socket

    try:
        with socket.create_connection(("127.0.0.1", port), timeout=5) as s:
            body = json.dumps({"t": "admin_counters", "rid": 1}).encode()
            s.sendall(len(body).to_bytes(4, "big") + body)

            def read_exactly(n):
                buf = b""
                while len(buf) < n:
                    chunk = s.recv(n - len(buf))
                    if not chunk:
                        raise ConnectionError("closed")
                    buf += chunk
                return buf

            while True:
                n = int.from_bytes(read_exactly(4), "big")
                frame = json.loads(read_exactly(n).decode())
                if frame.get("rid") == 1:
                    return frame.get("counters", {})
    except (OSError, ValueError):
        return {}


def _query_hop_breakdown(port: int) -> dict:
    """Per-hop-pair observation counts from the core's labeled metrics
    registry (admin_metrics_scrape RPC, Prometheus text): the published
    proof that every tier stamped — a refactor that silently drops a
    TraceHop stamp shows up here as a missing pair, not as a latency
    mystery two rounds later."""
    import socket

    from fluidframework_tpu.obs import parse_prometheus

    try:
        with socket.create_connection(("127.0.0.1", port), timeout=5) as s:
            body = json.dumps(
                {"t": "admin_metrics_scrape", "rid": 1}).encode()
            s.sendall(len(body).to_bytes(4, "big") + body)

            def read_exactly(n):
                buf = b""
                while len(buf) < n:
                    chunk = s.recv(n - len(buf))
                    if not chunk:
                        raise ConnectionError("closed")
                    buf += chunk
                return buf

            while True:
                n = int.from_bytes(read_exactly(4), "big")
                frame = json.loads(read_exactly(n).decode())
                if frame.get("rid") == 1:
                    series = parse_prometheus(frame.get("scrape", ""))
                    return {
                        dict(k).get("pair"): v
                        for k, v in series.get(
                            "fluid_obs_hop_ms_count", {}).items()}
    except (OSError, ValueError):
        return {}


def _query_probe_p99(port: int) -> dict:
    """door → p99 ms of the canary's blackbox probes, scraped from the
    armed core's windowed registry after the knee-rate run: what each
    real door (connect/submit/history/route) cost END TO END while the
    core served tenant load."""
    from fluidframework_tpu.obs import parse_prometheus

    try:
        frame = _admin_rpc(port, {"t": "admin_metrics_scrape"},
                           timeout=10.0)
    except (OSError, ValueError, RuntimeError):
        return {}
    series = parse_prometheus(frame.get("scrape", ""))
    out = {}
    for key, v in series.get("fluid_health_probe_ms", {}).items():
        labels = dict(key)
        if labels.get("quantile") in ("0.99", 0.99):
            out[labels.get("door", "?")] = round(float(v), 3)
    return out


def bench_network() -> dict:
    """Socket load against a front-end PROCESS: at-load op-ack latency.

    Orchestrator + asyncio runner processes (ref: service-load-test
    nodeStressTest.ts — workers must not share a GIL with the server or
    each other). Clients submit boxcars of 32 ops (the outbound
    DeltaQueue flush, same batching the in-proc headline uses) over the
    binary wire; the sweep raises the boxcar rate until ack p99 crosses
    the 50 ms north star and reports the highest sustainable load.

    Three measurements:
    - knee sweep at 256 docs × 2 clients (512 connections, direct);
    - the same geometry through 2 gateway processes (scale-out tier —
      on a single-core bench host the extra hop costs CPU from the same
      budget, so direct usually wins here; the gateway number is the
      honest cross-check, not the headline);
    - BASELINE config-4 geometry: 1000 docs × 10 clients = 10,000 live
      sockets at a reduced per-client rate.
    """
    import os
    import subprocess
    import tempfile
    import time as _time

    def run_workers(ports: list, nworkers: int, docs: int, cpd: int,
                    rate: float, batch: int, rounds: int, prefix: str,
                    start_margin: float = 6.0, timeout: float = 300.0,
                    extra: tuple = ()) -> dict:
        start_at = _time.time() + start_margin
        workers = [
            subprocess.Popen(
                _lean_cmd("fluidframework_tpu.service.load_async",
                          "--port", str(ports[w % len(ports)]),
                          "--docs", str(docs),
                          "--clients-per-doc", str(cpd),
                          "--rounds", str(rounds), "--batch", str(batch),
                          "--rate", str(rate), "--seed", str(w),
                          "--start-at", str(start_at),
                          "--doc-prefix", f"{prefix}w{w}d", *extra),
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                text=True, cwd=REPO)
            for w in range(nworkers)
        ]
        lats, ops, acked, secs, errors = [], 0, 0, 0.0, []
        late = 0.0
        hops: dict = {}
        for w in workers:
            out, _ = w.communicate(timeout=timeout)
            r = json.loads(out)
            lats.extend(r["lat_ms"])
            ops += r["ops"]
            acked += r["acked"]
            secs = max(secs, r["seconds"])
            late = max(late, r.get("late_s", 0.0))
            errors.extend(r.get("errors", []))
            for k, v in r["hops"].items():
                hops.setdefault(k, []).extend(v)
        assert acked == ops, (acked, ops, errors[:3])

        def pct(vals, p):
            vals = sorted(vals)
            return round(vals[int(p * (len(vals) - 1))], 3) if vals else 0.0

        return {
            "rate_hz": rate,
            "ops_per_sec": round(ops / secs, 1) if secs else 0.0,
            "p50_ack_ms": pct(lats, 0.50),
            "p99_ack_ms": pct(lats, 0.99),
            # a worker that finished connecting AFTER the synchronized
            # start measured the join storm, not steady load: the trial
            # is tainted and the caller should retry with a wider margin
            "late_s": late,
            "hops": {name: {"p50_ms": pct(v, 0.50), "p99_ms": pct(v, 0.99)}
                     for name, v in hops.items()},
        }

    fe, port = _spawn_listening("fluidframework_tpu.service.front_end",
                                "--port", "0")
    gws = []
    try:
        # production topology: clients terminate at gateway processes,
        # each muxing its sessions over ONE core backbone socket — the
        # core then serves G sockets instead of hundreds, which measures
        # FASTER than direct termination even on one host (fan-out
        # encode/sends move off the ordering process's queueing point)
        for _ in range(4):
            gw, gw_port = _spawn_listening(
                "fluidframework_tpu.service.gateway",
                "--core-port", str(port))
            gws.append((gw, gw_port))
        gw_ports = [p for _, p in gws]
        knee_ports = gw_ports[:2]

        # warm-up: orderer creation, joins, first broadcasts (discarded)
        run_workers(knee_ports, 2, 8, 2, 2.0, 8, 4, "warm",
                    start_margin=3.0)

        # ---- knee sweep: 256 docs × 2 clients, boxcars of 32, through
        # 2 gateways. A failed rung is retried once: the bench host has
        # bursty co-tenant CPU (round-3 note), and one burst must not
        # stop the sweep at an artificially low knee. ----
        best = None
        for rate in (1.25, 1.5, 1.75, 2.0, 2.5):
            for attempt in ("", "b"):  # one retry per rung
                r = run_workers(knee_ports, 4, 64, 2, rate, 32,
                                max(8, int(8 * rate)), f"k{rate}{attempt}")
                if r["p99_ack_ms"] < 50.0:
                    break
            if r["p99_ack_ms"] < 50.0:
                best = r
            else:
                if best is None:
                    best = r  # even the lightest load misses: report it
                break
        # confirm the knee: median p99 of 5 runs (bursty co-tenant CPU
        # can depress two consecutive trials). If the confirm median
        # misses the target, step DOWN a rung and re-confirm, all the
        # way to 0.5 (8k ops/s): the published knee is the highest rate
        # whose own confirmation median holds p99 < 50 ms — never a
        # rate that only hit the target in a lucky sweep run (VERDICT
        # r4 #2: the knee must be honest even if it is small).
        knee_rate = best["rate_hz"]
        while True:
            confirms = sorted(
                (run_workers(knee_ports, 4, 64, 2, knee_rate, 32,
                             max(8, int(8 * knee_rate)), f"c{knee_rate}{t}")
                 for t in range(5)),
                key=lambda r: r["p99_ack_ms"])
            best = confirms[2]
            if best["p99_ack_ms"] < 50.0 or knee_rate <= 0.5:
                break
            knee_rate = round(knee_rate - 0.25, 2)

        # ---- the same geometry terminating directly at the core ----
        direct = run_workers([port], 4, 64, 2, knee_rate, 32,
                             max(8, int(8 * knee_rate)), "direct")

        # batching counters accumulated over everything the core served
        # so far (sweep + confirms + direct): proof the amortization
        # engaged under load, reported as net_batching
        batching = _query_counters(port)

        # relay-depth leg: a dedicated 2-level relay tree (leaf gateway
        # dialing a mid gateway via --upstream-gateway; the mid runs the
        # asyncio relay, which is the tier that SERVES the backbone
        # protocol downward — the native epoll relay the knee gateways
        # run does not stack). One short traced burst at the knee rate:
        # each tier appends its own HOP_RELAY stamp, so the core's
        # registry gains the relay_to_relay pair (the per-tier relay
        # cost the flat gateway geometry can never witness)
        mid, mid_port = _spawn_listening(
            "fluidframework_tpu.service.gateway",
            "--core-port", str(port), "--python")
        leaf, leaf_port = _spawn_listening(
            "fluidframework_tpu.service.gateway",
            "--upstream-gateway", f"127.0.0.1:{mid_port}")
        try:
            run_workers([leaf_port], 2, 8, 2, knee_rate, 32,
                        max(8, int(8 * knee_rate)), "rlyrly",
                        extra=("--trace-sample-n", "4"))
        finally:
            leaf.terminate()
            mid.terminate()
            leaf.wait(timeout=10)
            mid.wait(timeout=10)

        # per-hop-pair counts from the core's metrics registry over the
        # same window: the knee runs went through gateways with 1-in-16
        # trace sampling armed, so all four server-visible legs (submit→
        # relay→admit→deli→fanout) must have counted — plus
        # relay_to_relay from the stacked-leaf burst above
        hop_breakdown = _query_hop_breakdown(port)

        # armed/disarmed A/B at the knee rate: the sampling knob must
        # cost ~nothing when off AND ~nothing at 1-in-16 — two
        # back-to-back runs, same geometry, published side by side
        rounds = max(8, int(8 * knee_rate))
        trace_ab = {
            "armed_ops_per_sec": run_workers(
                knee_ports, 4, 64, 2, knee_rate, 32, rounds,
                "abarm")["ops_per_sec"],
            "disarmed_ops_per_sec": run_workers(
                knee_ports, 4, 64, 2, knee_rate, 32, rounds,
                "aboff", extra=("--trace-sample-n", "0"))["ops_per_sec"],
        }

        # audit-journal A/B at the knee rate: same geometry against two
        # fresh direct-terminated cores, one with --journal armed. The
        # journal only writes on control-plane EVENTS (never per op), so
        # armed steady-state throughput must match disarmed within
        # noise — the published proof the audit spine is free when idle
        # and ~free when armed
        journal_ab = {}
        for tag, fe_extra in (
                ("armed", ("--journal",
                           os.path.join(tempfile.mkdtemp(
                               prefix="bench-journal-"), "fe.jsonl"))),
                ("disarmed", ())):
            jfe, jport = _spawn_listening(
                "fluidframework_tpu.service.front_end", "--port", "0",
                *fe_extra)
            try:
                journal_ab[f"{tag}_ops_per_sec"] = run_workers(
                    [jport], 4, 64, 2, knee_rate, 32, rounds,
                    f"jab{tag}")["ops_per_sec"]
            finally:
                jfe.terminate()
                jfe.wait(timeout=10)

        # health-plane A/B at the knee rate: same geometry against two
        # fresh direct-terminated cores, one with --probe armed (canary
        # ticker + streaming health engine). The canary is one synthetic
        # session per tick on a reserved tenant that every admission
        # seam excludes, so armed steady-state throughput must match
        # disarmed within noise — the published proof that watching the
        # doors costs ~nothing. The armed core's registry is scraped
        # after the run for health.probe.ms p99 per door: the blackbox
        # door latencies AT LOAD, not on an idle core.
        health_ab = {}
        for tag, fe_extra in (
                ("armed", ("--probe", "--probe-tick", "0.5",
                           "--health-tick", "0.5")),
                ("disarmed", ())):
            hfe, hport = _spawn_listening(
                "fluidframework_tpu.service.front_end", "--port", "0",
                *fe_extra)
            try:
                health_ab[f"{tag}_ops_per_sec"] = run_workers(
                    [hport], 4, 64, 2, knee_rate, 32, rounds,
                    f"hab{tag}")["ops_per_sec"]
                if tag == "armed":
                    health_ab["probe_p99_ms"] = _query_probe_p99(hport)
            finally:
                hfe.terminate()
                hfe.wait(timeout=10)

        # ---- BASELINE config 4: 1000 docs × 10 clients, 4 gateways.
        # The 10× fan-out geometry has its own (lower) knee: step the
        # per-client rate down until the p99 target holds. If even the
        # lightest rate misses, the lightest run is reported and its
        # published p99 field is the saturation marker. ----
        cfg4 = None
        for rate in (0.15, 0.125, 0.1, 0.075, 0.05, 0.035):
            for attempt, margin in (("", 40.0), ("b", 110.0)):
                # one retry per rate, at a much wider start margin: a
                # co-tenant burst during the 10k-connection phase makes
                # workers START LATE (late_s > 0), and a late trial
                # measures the join storm riding into the load window —
                # the dominant cause of the multi-second cfg4 p99 tails
                cfg4 = run_workers(gw_ports, 4, 250, 10, rate, 8, 3,
                                   f"cfg4r{rate}{attempt}",
                                   start_margin=margin, timeout=420.0)
                if cfg4["p99_ack_ms"] < 50.0 and cfg4["late_s"] == 0:
                    break
            if cfg4["p99_ack_ms"] < 50.0 and cfg4["late_s"] == 0:
                break

        # ---- NORTH-STAR geometry: 10,000 DOCS (1 client each, 10k
        # sockets, 4 gateways). The north star names 10k docs; cfg4's
        # 1k-docs × 10-clients row exercises fan-out, this row
        # exercises doc-table scale (10× the orderers, no fan-out
        # amplification). Same taint/retry machinery as cfg4: a late
        # worker (late_s > 0) measured the join storm, so each rate
        # retries once at a wider start margin before stepping down. ----
        n10k = None
        for rate in (0.15, 0.125, 0.1, 0.075, 0.05, 0.035):
            for attempt, margin in (("", 40.0), ("b", 110.0)):
                n10k = run_workers(gw_ports, 4, 2500, 1, rate, 8, 3,
                                   f"t10k{rate}{attempt}",
                                   start_margin=margin, timeout=420.0)
                if n10k["p99_ack_ms"] < 50.0 and n10k["late_s"] == 0:
                    break
            if n10k["p99_ack_ms"] < 50.0 and n10k["late_s"] == 0:
                break
        # the single-core tier is torn down — and WAITED on — before the
        # sharded run: 4 gateways dropping 10k sockets spend seconds in
        # teardown, and that CPU must not bleed into the sharded trial
        for gw, _ in gws:
            gw.terminate()
        for gw, _ in gws:
            try:
                gw.wait(timeout=10)
            except subprocess.TimeoutExpired:
                gw.kill()
        gws = []
        fe.terminate()
        fe.wait(timeout=10)
        fe = None

        sharded = bench_sharded(best["rate_hz"], run_workers)
        sharded4 = bench_sharded(best["rate_hz"], run_workers, n_cores=4)
        blip = bench_migration_blip()
        return {
            "knee": best,
            "direct": direct,
            "cfg4": cfg4,
            "net_10k_docs": n10k,
            "sharded": sharded,
            "sharded_4core": sharded4,
            "migration_blip": blip,
            "batching": batching,
            "hop_breakdown": hop_breakdown,
            "trace_ab": trace_ab,
            "journal_ab": journal_ab,
            "health_ab": health_ab,
        }
    finally:
        for gw, _ in gws:
            gw.terminate()
        if fe is not None:
            fe.terminate()
            fe.wait(timeout=10)


def bench_overload_sweep(knee: dict) -> dict:
    """Closed-loop overload control at 0.5×–4× the measured knee.

    Every rung runs TWO tenants against a fresh front end with the
    admission gate armed (``--tenant-rate`` + ``--slo`` on the
    ``submit_to_admit`` leg — the queueing-visible hop: admit→deli is
    one event-loop iteration, but frames waiting to be READ show up
    between the client's submit stamp and the admit stamp):

    - ``bulk``: token bucket capped at 0.9× the knee throughput,
      offered the swept multiple of the knee load — the shed candidate;
    - ``steady``: no configured rate (structurally unsheddable), a
      fixed ~5%-of-knee trickle on every rung — what an innocent
      co-tenant feels while the neighbor floods.

    The 4× rung repeats with ``--no-shed`` (buckets still account, the
    SLO still trips, nothing sheds) as the collapse control, and a
    caps-free pair at 1× (armed vs plain front) prices the windowed
    registry + SLO ticker themselves. Workers resubmit shed ops after
    the server's jittered ``retry_after_ms`` (load_async shed lane), so
    ``acked_frac`` < 1 on a rung means the backlog outlived the
    worker's ack-wait budget — the honest saturation marker."""
    import subprocess
    import time as _time

    knee_rate = knee.get("rate_hz") or 0.0
    knee_ops = knee.get("ops_per_sec") or 0.0
    knee_p99 = knee.get("p99_ack_ms") or 50.0
    if not (knee_rate and knee_ops):
        return {"skipped": "no knee measurement"}
    budget_ms = round(max(20.0, 1.5 * knee_p99), 1)
    cap = round(0.9 * knee_ops, 1)

    def pct(vals, p):
        vals = sorted(vals)
        return round(vals[int(p * (len(vals) - 1))], 3) if vals else None

    def spawn_worker(port, w, tenant, docs, cpd, rate, batch, rounds,
                     prefix, start_at, timeout):
        return subprocess.Popen(
            _lean_cmd("fluidframework_tpu.service.load_async",
                      "--port", str(port), "--docs", str(docs),
                      "--clients-per-doc", str(cpd),
                      "--rounds", str(rounds), "--batch", str(batch),
                      "--rate", str(rate), "--seed", str(w),
                      "--start-at", str(start_at), "--tenant", tenant,
                      "--timeout", str(timeout),
                      "--doc-prefix", f"{prefix}w{w}d"),
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True, cwd=REPO)

    def run_rung(mult, tag, shed=True, caps=True, slo=True):
        fe_args = ["--port", "0"]
        if slo:
            fe_args += ["--slo",
                        f"overload=submit_to_admit:{budget_ms}:5:2"]
        if caps:
            fe_args += ["--tenant-rate", f"bulk:{cap}:{cap}"]
        if not shed:
            fe_args.append("--no-shed")
        fe, port = _spawn_listening(
            "fluidframework_tpu.service.front_end", *fe_args)
        try:
            flood_rate = round(knee_rate * mult, 4)
            rounds = max(6, int(8 * flood_rate))
            start_at = _time.time() + 6.0
            floods = [
                spawn_worker(port, w, "bulk", 64, 2, flood_rate, 32,
                             rounds, f"ov{tag}", start_at, 60.0)
                for w in range(4)]
            steady = spawn_worker(port, 9, "steady", 16, 2, 2.0, 8, 16,
                                  f"ov{tag}s", start_at, 60.0)
            results = []
            for w in floods + [steady]:
                out, _ = w.communicate(timeout=300)
                results.append(json.loads(out))
            st = results[-1]
            fl = results[:-1]
            secs = max(r["seconds"] for r in results)
            acked = sum(r["acked"] for r in results)
            offered = sum(r["ops"] for r in results)
            return {
                "offered_x": mult,
                "offered_ops": offered,
                # goodput over the whole window INCLUDING the ack/drain
                # wait — the collapse signal the control rung exposes
                "ops_per_sec": round(acked / secs, 1) if secs else 0.0,
                "acked_frac": round(acked / offered, 4) if offered else None,
                "shed_nacks": sum(r.get("shed", 0) for r in results),
                "steady_p99_ack_ms": pct(st["lat_ms"], 0.99),
                "steady_acked_frac": (round(st["acked"] / st["ops"], 4)
                                      if st["ops"] else None),
                "bulk_p99_ack_ms": pct(
                    [v for r in fl for v in r["lat_ms"]], 0.99),
            }
        finally:
            fe.terminate()
            try:
                fe.wait(timeout=10)
            except subprocess.TimeoutExpired:
                fe.kill()

    rungs = [run_rung(m, f"s{m}") for m in (0.5, 1.0, 2.0, 4.0)]
    control = run_rung(4.0, "c", shed=False)
    ab_armed = run_rung(1.0, "aa", caps=False)
    ab_plain = run_rung(1.0, "ap", caps=False, slo=False)
    return {
        "budget_ms": budget_ms,
        "bulk_cap_ops_per_sec": cap,
        "rungs": rungs,
        "control_no_shed_4x": control,
        # the SLO/windowed-registry machinery alone (no caps, nothing
        # sheds): the two throughputs must sit within run-to-run noise
        "slo_ab": {"armed_ops_per_sec": ab_armed["ops_per_sec"],
                   "plain_ops_per_sec": ab_plain["ops_per_sec"]},
    }


def _admin_rpc(port: int, frame: dict, timeout: float = 30.0) -> dict:
    """One rid-matched admin RPC round trip against a front-end process.

    The timeout is the caller's to size: ``admin_summarize`` replies only
    after the server's host replica has ingested the whole log tail and
    committed the version, which on a 100k-op doc is tens of seconds."""
    import socket

    with socket.create_connection(("127.0.0.1", port), timeout=timeout) as s:
        body = json.dumps(dict(frame, rid=1)).encode()
        s.sendall(len(body).to_bytes(4, "big") + body)

        def read_exactly(n):
            buf = b""
            while len(buf) < n:
                chunk = s.recv(n - len(buf))
                if not chunk:
                    raise ConnectionError("closed")
                buf += chunk
            return buf

        while True:
            n = int.from_bytes(read_exactly(4), "big")
            reply = json.loads(read_exactly(n).decode())
            if reply.get("rid") != 1:
                continue
            if reply.get("t") == "error":
                raise RuntimeError(reply.get("message"))
            return reply


def bench_join_storm() -> dict:
    """Late-joiner catch-up on a long-lived doc: snapshot+Δ vs replay.

    ONE front-end process, ONE doc carrying ≥ 100k sequenced ops at the
    config-4 per-doc geometry (10 synthetic socket clients). Three
    measurements, ordered so each boot shape is forced honestly:

    - **whole-log replay**: cold Loader boots BEFORE any summary exists
      — every op replays through the client merge-tree (the
      pre-snapshot-plane catch-up cost, O(whole log));
    - **snapshot+Δ storm**: after ONE service summary (the
      ``admin_summarize`` door onto the summarizer), a storm of cold
      joiners — each with a fresh driver cache — boots while a trickle
      writer keeps the stream moving, so every time-to-interactive is
      a true MID-STREAM join: snapshot fetch + bounded Δ backfill;
    - **counter assertions** (in-bench, hard): the server frames the
      snapshot exactly ONCE for the whole storm (per-join re-encodes
      == 0), no joiner falls back to the legacy tree shim, and every
      joiner's backfill was snapshot-bounded. A storm that silently
      rode the JSON tree path would otherwise publish a plausible
      number that measures the wrong plane.
    """
    import subprocess
    import time as _time

    from fluidframework_tpu.driver.network import NetworkDocumentServiceFactory
    from fluidframework_tpu.loader.container import Loader
    from fluidframework_tpu.obs import tier_counters

    doc = "jstorm0"
    # ONE explicit Counters handed to every factory: tier_counters vends
    # a fresh instance per call, so per-boot deltas are only observable
    # through a shared instance
    drv = tier_counters("driver")

    def boot(label):
        """Cold boot: fresh factory (empty snapshot/chunk cache), timed
        resolve → the doc is interactive (caught up + channel readable)."""
        factory = NetworkDocumentServiceFactory("127.0.0.1", port,
                                                counters=drv)
        t0 = _time.perf_counter()
        c = Loader(factory).resolve("bench", doc)
        # interactive = the channel answers from converged state
        assert len(c.runtime.get_data_store("default")
                   .get_channel("text").get_text()) > 0, label
        dt = _time.perf_counter() - t0
        c.close()
        return dt

    def pct(vals, p):
        vals = sorted(vals)
        return round(vals[int(p * (len(vals) - 1))], 3) if vals else None

    fe, port = _spawn_listening(
        "fluidframework_tpu.service.front_end", "--port", "0")
    trickle = None
    try:
        # attach the doc with a real writer (raw synthetic clients never
        # send the attach op a booting runtime needs to route chanops)
        writer = Loader(NetworkDocumentServiceFactory(
            "127.0.0.1", port)).resolve("bench", doc)
        ss = writer.runtime.create_data_store("default").create_channel(
            "text", "shared-string")
        ss.insert_text(0, "join-storm seed ")
        deadline = _time.time() + 30
        while writer.runtime.pending.count and _time.time() < deadline:
            _time.sleep(0.01)
        assert writer.runtime.pending.count == 0, "writer never quiesced"
        writer.close()

        # the long-lived doc: 10 clients × 320 rounds × 32-op boxcars
        # = 102,400 ops on one stream (config-4 per-doc geometry)
        w = subprocess.Popen(
            _lean_cmd("fluidframework_tpu.service.load_async",
                      "--port", str(port), "--docs", "1",
                      "--clients-per-doc", "10", "--rounds", "320",
                      "--batch", "32", "--rate", "8", "--seed", "7",
                      "--doc-prefix", "jstorm", "--timeout", "300"),
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True, cwd=REPO)
        res = json.loads(w.communicate(timeout=900)[0])
        doc_ops = res["acked"]
        assert doc_ops >= 100_000, f"doc too short: {doc_ops} acked"

        # A: whole-log replay (no summary committed yet, so the columnar
        # door reports "no version" and the boot replays from seq 0)
        pre = drv.snapshot()
        replay_s = [round(boot(f"replay{i}"), 3) for i in range(2)]
        d = drv.snapshot()
        assert d.get("boot.backfill.full", 0) \
            - pre.get("boot.backfill.full", 0) == 2, \
            "replay boots were not whole-log"

        # ONE service summary through the operator door
        version = _admin_rpc(
            port, {"t": "admin_summarize", "tenant": "bench", "doc": doc},
            timeout=600.0)["version"]

        # trickle writer: the stream keeps moving, so every storm boot
        # is a mid-stream join with a real post-snapshot Δ to backfill
        trickle = subprocess.Popen(
            _lean_cmd("fluidframework_tpu.service.load_async",
                      "--port", str(port), "--docs", "1",
                      "--clients-per-doc", "2", "--rounds", "400",
                      "--batch", "8", "--rate", "2", "--seed", "11",
                      "--doc-prefix", "jstorm", "--timeout", "60"),
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            cwd=REPO)
        _time.sleep(1.0)

        # B: the storm — cold snapshot+Δ boots
        joins = 8
        pre_srv = _query_counters(port)
        pre_drv = drv.snapshot()
        tti = [round(boot(f"join{i}"), 3) for i in range(joins)]
        post_srv = _query_counters(port)
        post_drv = drv.snapshot()

        def delta(post, pre, name):
            return post.get(name, 0) - pre.get(name, 0)

        encodes = delta(post_srv, pre_srv, "storage.snapshot.encodes")
        reencodes = encodes - 1  # first serve fills the framed cache
        assert reencodes == 0, \
            f"snapshot re-encoded during the storm ({encodes} encodes)"
        assert delta(post_drv, pre_drv, "boot.snapshot.fallback") == 0, \
            "a joiner fell back to the legacy tree shim"
        assert delta(post_drv, pre_drv, "boot.snapshot.used") == joins
        assert delta(post_drv, pre_drv, "boot.backfill.bounded") == joins, \
            "a joiner's backfill was not snapshot-bounded"

        speedup = round(pct(replay_s, 0.5) / pct(tti, 0.5), 1)
        assert speedup >= 10.0, \
            f"snapshot+Δ only {speedup}x faster than whole-log replay"
        return {
            "doc_ops": doc_ops,
            "joins": joins,
            "replay_boot_s": replay_s,
            "tti_p50_s": pct(tti, 0.5),
            "tti_p99_s": pct(tti, 0.99),
            "speedup_vs_replay_x": speedup,
            "reencodes_per_join": reencodes,
            "snapshot_version": version,
            "counters": {
                "storage.snapshot.encodes": encodes,
                "storage.snapshot.served": delta(
                    post_srv, pre_srv, "storage.snapshot.served"),
                "storage.snapshot.cache_hits": delta(
                    post_srv, pre_srv, "storage.snapshot.cache_hits"),
                "storage.snapshot.legacy_tree": delta(
                    post_srv, pre_srv, "storage.snapshot.legacy_tree"),
                "boot.chunks.fetched": delta(
                    post_drv, pre_drv, "boot.chunks.fetched"),
            },
        }
    finally:
        if trickle is not None:
            trickle.terminate()
        fe.terminate()
        try:
            fe.wait(timeout=10)
        except subprocess.TimeoutExpired:
            fe.kill()


def bench_net_read_storm() -> dict:
    """Read-scale fan-out through a 2-level relay tree: writer ack and
    core-tier egress vs read-only subscriber count.

    Topology: core ← gw1 (``--core-port``, Python backbone) ← gw2
    (``--upstream-gateway``), every subscriber parked on gw2, ONE writer
    attached directly to the core. Subscribers are raw binary sockets
    (``readonly=1`` connect, no Loader, no join/quorum) living in THIS
    process behind a selectors drain — the host fd budget, not client
    CPU, bounds the swarm, so the 10k target scales to the host and the
    row carries ``host_limited`` when capped. Three probe windows
    (0 readers → n/10 → n) price the claim three ways:

    - **writer ack p99** at full fan-out vs the zero-reader baseline:
      the relay tree must keep reader cost off the write path (asserted
      within 10% unless host_limited — on a 1-CPU host every tier
      time-slices the writer's core);
    - **core-tier bytes/op**: gw1's ``fanout.upstream.bytes`` delta per
      acked op, window-scoped so connect-burst replies don't pollute it
      — asserted ~flat across the 10× subscriber growth (the
      once-per-doc-per-link subscription is what makes it flat);
    - **zero re-encodes above the core**: ``fanout.relay.encodes`` == 0
      at BOTH gateway levels, always asserted — every hop splices
      cached backbone bytes, never re-serializes.

    Delivery is proven at the edge, not inferred: every subscriber
    socket must grow past its pre-window watermark before a window's
    counters are read (which also quiesces in-flight fan-out so the
    byte deltas are complete).
    """
    import os
    import resource
    import selectors
    import socket as _socket
    import time as _time

    from fluidframework_tpu.driver.network import NetworkDocumentServiceFactory
    from fluidframework_tpu.loader.container import Loader

    target = 10_000
    cpus = os.cpu_count() or 1
    soft = resource.getrlimit(resource.RLIMIT_NOFILE)[0]
    # fd budget: n reader sockets here + n accepted in gw2 (its own
    # limit), minus headroom for the writer/admin/spawn plumbing
    n = min(target, max(256, soft - 512), target if cpus >= 4 else 2_000)
    host_limited = (cpus < 4) or (n < target)
    doc = "rstorm0"

    def pct(vals, p):
        vals = sorted(vals)
        return round(vals[int(p * (len(vals) - 1))], 3)

    def gw_counters(port: int) -> dict:
        # same wire shape as _query_counters, different door: the
        # gateway answers THIS tier's fanout.* counters locally instead
        # of relaying to the core
        with _socket.create_connection(("127.0.0.1", port), timeout=10) as s:
            body = json.dumps({"t": "gateway_counters", "rid": 1}).encode()
            s.sendall(len(body).to_bytes(4, "big") + body)

            def read_exactly(k):
                buf = b""
                while len(buf) < k:
                    chunk = s.recv(k - len(buf))
                    if not chunk:
                        raise ConnectionError("closed")
                    buf += chunk
                return buf

            while True:
                m = int.from_bytes(read_exactly(4), "big")
                frame = json.loads(read_exactly(m).decode())
                if frame.get("rid") == 1:
                    return frame.get("counters", {})

    sel = selectors.DefaultSelector()
    socks: list = []
    rx: dict = {}
    core = gw1 = gw2 = writer = None

    def pump(cond, deadline_s: float) -> bool:
        """Drain subscriber sockets until cond() or deadline."""
        end = _time.monotonic() + deadline_s
        while not cond():
            if _time.monotonic() >= end:
                return False
            for key, _ in sel.select(0.2):
                s = key.fileobj
                try:
                    b = s.recv(1 << 16)
                except (BlockingIOError, InterruptedError):
                    continue
                except OSError:
                    b = b""
                if b:
                    rx[s] += len(b)
                else:
                    sel.unregister(s)  # EOF: rx stops growing, the
                    # delivery watermark check names the window
        return True

    def add_readers(count: int, gw2_port: int) -> None:
        base = len(socks)
        for i in range(count):
            s = _socket.create_connection(("127.0.0.1", gw2_port),
                                          timeout=30)
            s.setsockopt(_socket.IPPROTO_TCP, _socket.TCP_NODELAY, 1)
            body = json.dumps({"t": "connect", "tenant": "bench",
                               "doc": doc, "bin": 1, "readonly": 1,
                               "rid": base + i}).encode()
            s.sendall(len(body).to_bytes(4, "big") + body)
            s.setblocking(False)
            rx[s] = 0
            sel.register(s, selectors.EVENT_READ)
            socks.append(s)
            # self-pacing: keep the un-replied connect burst under the
            # accept backlog by waiting for handshakes to catch up
            if len(socks) % 64 == 0:
                want = len(socks) - 64
                assert pump(
                    lambda: sum(1 for t in socks if rx[t] > 0) >= want,
                    120.0), "reader handshakes stalled mid-burst"
        assert pump(lambda: all(rx[t] > 0 for t in socks), 120.0), \
            "reader handshakes stalled"

    def probe(k: int) -> list:
        lats = []
        for i in range(k):
            t0 = _time.perf_counter()
            sstr.insert_text(0, "x")
            deadline = _time.monotonic() + 30.0
            while (writer.runtime.pending.count
                   and _time.monotonic() < deadline):
                _time.sleep(0.0005)
            assert writer.runtime.pending.count == 0, \
                f"read-storm probe op {i} never acked"
            lats.append((_time.perf_counter() - t0) * 1e3)
        return lats

    def window(k: int) -> list:
        marks = {s: rx[s] for s in socks}
        lats = probe(k)
        # edge delivery proof + fan-out quiesce before counters are read
        assert pump(lambda: all(rx[s] > marks[s] for s in socks), 60.0), \
            "a subscriber saw no broadcast bytes this window"
        return lats

    try:
        core, core_port = _spawn_listening(
            "fluidframework_tpu.service.front_end", "--port", "0")
        gw1, gw1_port = _spawn_listening(
            "fluidframework_tpu.service.gateway",
            "--core-port", str(core_port), "--python")
        gw2, gw2_port = _spawn_listening(
            "fluidframework_tpu.service.gateway",
            "--upstream-gateway", f"127.0.0.1:{gw1_port}")

        writer = Loader(NetworkDocumentServiceFactory(
            "127.0.0.1", core_port)).resolve("bench", doc)
        sstr = writer.runtime.create_data_store(
            "default").create_channel("text", "shared-string")
        sstr.insert_text(0, "read-storm seed ")
        deadline = _time.monotonic() + 30
        while writer.runtime.pending.count and _time.monotonic() < deadline:
            _time.sleep(0.01)
        assert writer.runtime.pending.count == 0, "writer never quiesced"

        k = 60
        baseline = probe(k)

        pre_core = _query_counters(core_port)
        add_readers(n // 10, gw2_port)
        g1_pre, g2_pre = gw_counters(gw1_port), gw_counters(gw2_port)
        lat_small = window(k)
        g1_mid = gw_counters(gw1_port)

        add_readers(n - n // 10, gw2_port)
        post_core = _query_counters(core_port)
        # every raw subscriber must have landed as a READONLY session at
        # the core (an error-reply handshake would count bytes too)
        ro = (post_core.get("session.readonly.connects", 0)
              - pre_core.get("session.readonly.connects", 0))
        assert ro == n, f"expected {n} readonly connects at core, got {ro}"
        g1_mid2 = gw_counters(gw1_port)  # re-mark: exclude connect burst
        lat_full = window(k)
        g1_post, g2_post = gw_counters(gw1_port), gw_counters(gw2_port)

        bpo_small = (g1_mid.get("fanout.upstream.bytes", 0)
                     - g1_pre.get("fanout.upstream.bytes", 0)) / k
        bpo_full = (g1_post.get("fanout.upstream.bytes", 0)
                    - g1_mid2.get("fanout.upstream.bytes", 0)) / k
        assert bpo_small > 0, "no core egress reached gw1 (dead relay?)"
        growth = bpo_full / bpo_small
        assert growth <= 1.5, \
            f"core bytes/op grew {growth:.2f}x over 10x subscribers"

        # zero re-encode invariant above the core — ALWAYS asserted
        for name, g in (("gw1", g1_post), ("gw2", g2_post)):
            enc = g.get("fanout.relay.encodes", 0)
            assert enc == 0, f"{name} re-encoded {enc} fan-out frames"
        splices = (g2_post.get("fanout.relay.splices", 0)
                   - g2_pre.get("fanout.relay.splices", 0))
        assert splices > 0, "relay splice path never engaged at gw2"

        ack_ratio = round(pct(lat_full, 0.99)
                          / max(pct(baseline, 0.99), 1e-9), 3)
        if not host_limited:
            assert ack_ratio <= 1.10, \
                f"writer ack p99 {ack_ratio}x baseline under full fan-out"
        return {
            "target_readers": target,
            "readers": n,
            "host_limited": host_limited,
            "tree_levels": 2,
            "baseline_p99_ack_ms": pct(baseline, 0.99),
            "p99_ack_ms_small": pct(lat_small, 0.99),
            "p99_ack_ms_full": pct(lat_full, 0.99),
            "ack_p99_vs_baseline_x": ack_ratio,
            "core_bytes_per_op_small": round(bpo_small, 1),
            "core_bytes_per_op_full": round(bpo_full, 1),
            "core_bytes_per_op_growth_x": round(growth, 3),
            "relay_encodes": 0,
            "relay_splices_gw2": splices,
            "readonly_connects": n,
        }
    finally:
        for s in socks:
            try:
                s.close()
            except OSError:
                pass
        if writer is not None:
            try:
                writer.close()
            except Exception:  # noqa: BLE001
                pass
        for p in (gw2, gw1, core):
            if p is not None:
                p.terminate()
        for p in (gw2, gw1, core):
            if p is not None:
                try:
                    p.wait(timeout=10)
                except Exception:  # noqa: BLE001
                    p.kill()


def bench_sharded(knee_rate: float, run_workers, n_cores: int = 2) -> dict:
    """The SHARDED ordering core at the knee geometry (VERDICT r4 #4):
    ``n_cores`` core PROCESSES over placement leases, gateways routing
    by doc partition. On a MULTI-core host this row is the sequencer
    scaling out (target ≥1.5× per added core vs the 1-core knee); this
    bench host has ONE CPU (nproc=1), where the core lanes can only
    time-slice it — such a row is published ``host_limited`` with
    per-lane CPU attribution (/proc/<pid>/stat utime+stime across the
    measured rung) as the proof the lanes are separate processes doing
    real sequencing work (mechanism correctness is
    tests/test_sharded_core.py + tests/test_placement_plane.py)."""
    import os
    import tempfile

    shard_dir = tempfile.mkdtemp(prefix="bench-shard-")
    host_limited = (os.cpu_count() or 1) < n_cores
    cores = []
    gws = []
    try:
        for prefer in range(n_cores):
            c, _ = _spawn_listening(
                "fluidframework_tpu.service.front_end", "--port", "0",
                "--shard-dir", shard_dir, "--shards", str(n_cores),
                "--prefer", str(prefer))
            cores.append(c)
        for _ in range(2):
            gw, gp = _spawn_listening(
                "fluidframework_tpu.service.gateway", "--shard-dir",
                shard_dir, "--shards", str(n_cores))
            gws.append((gw, gp))
        ports = [p for _, p in gws]
        run_workers(ports, 2, 8, 2, 2.0, 8, 4, "swarm", start_margin=3.0)
        last = None
        for mult in (1.5, 1.0, 0.75):
            rate = round(knee_rate * mult, 3)
            cpu0 = [_proc_cpu_s(c.pid) for c in cores]
            try:
                r = run_workers(ports, 4, 64, 2, rate, 32,
                                max(8, int(8 * rate)),
                                f"sh{n_cores}c{rate}")
            except AssertionError:
                # rung drowned outright (acks never completed before the
                # workers' wait budget): on a 1-CPU host time-sliced
                # cores saturate below the 1-core knee — step down
                last = {"rate_hz": rate, "ops_per_sec": 0.0,
                        "p50_ack_ms": None, "p99_ack_ms": None,
                        "late_s": None, "drowned": True,
                        "n_cores": n_cores, "host_limited": host_limited}
                continue
            r["n_cores"] = n_cores
            r["host_limited"] = host_limited
            r["core_cpu_s"] = [round(_proc_cpu_s(c.pid) - c0, 2)
                               for c, c0 in zip(cores, cpu0)]
            last = r
            if r["p99_ack_ms"] < 50.0:
                return r
        return last
    finally:
        for gw, _ in gws:
            gw.terminate()
        for c in cores:
            c.terminate()
            c.wait(timeout=10)


def bench_migration_blip() -> dict:
    """p99 ack of a steady probe stream across a FORCED live migration
    (``admin_migrate_doc`` on the doc's source core, 2 sharded core
    processes + a gateway): the writer rides the gateway with
    auto-reconnect, so the migration-window p99 prices the whole
    seal → redirect-bounce → epoch flip → reconnect + pending-replay
    path. Published next to a no-migration baseline of the SAME probe;
    zero loss is asserted (pending must drain), not assumed."""
    import os
    import tempfile
    import threading
    import time as _time

    from fluidframework_tpu.driver.network import (
        NetworkDocumentServiceFactory,
        _Transport,
    )
    from fluidframework_tpu.loader.container import Loader
    from fluidframework_tpu.service.stage_runner import doc_partition

    shard_dir = tempfile.mkdtemp(prefix="bench-blip-")
    cores, core_ports, gw = [], [], None
    writer = None
    try:
        for prefer in ("0", "1"):
            c, p = _spawn_listening(
                "fluidframework_tpu.service.front_end", "--port", "0",
                "--shard-dir", shard_dir, "--shards", "2",
                "--prefer", prefer, "--lease-ttl", "1.5")
            cores.append(c)
            core_ports.append(p)
        gw, gw_port = _spawn_listening(
            "fluidframework_tpu.service.gateway", "--shard-dir",
            shard_dir, "--shards", "2")
        writer = Loader(NetworkDocumentServiceFactory(
            "127.0.0.1", gw_port), auto_reconnect=True).resolve(
            "bench", "blipdoc")
        sstr = writer.runtime.create_data_store(
            "default").create_channel("text", "shared-string")

        def probe(n: int) -> list:
            lats = []
            for i in range(n):
                t0 = _time.perf_counter()
                sstr.insert_text(0, "x")
                deadline = _time.monotonic() + 30.0
                while (writer.runtime.pending.count
                       and _time.monotonic() < deadline):
                    _time.sleep(0.0005)
                assert writer.runtime.pending.count == 0, \
                    f"blip probe op {i} never acked (lost across the flip)"
                lats.append((_time.perf_counter() - t0) * 1e3)
            return lats

        def pct(vals, p):
            vals = sorted(vals)
            return round(vals[int(p * (len(vals) - 1))], 3)

        baseline = probe(150)

        k = doc_partition("bench", "blipdoc", 2)
        target = f"127.0.0.1:{core_ports[1 - k]}"

        def migrate():
            _time.sleep(0.15)  # land mid-probe
            t = _Transport("127.0.0.1", core_ports[k], timeout=30.0)
            try:
                t.request({"t": "admin_migrate_doc", "tenant": "bench",
                           "doc": "blipdoc", "target": target})
            finally:
                t.close()

        mig = threading.Thread(target=migrate)
        mig.start()
        try:
            window = probe(150)
        finally:
            mig.join()
        return {
            "baseline_p99_ms": pct(baseline, 0.99),
            "migration_p99_ms": pct(window, 0.99),
            "migration_max_ms": round(max(window), 3),
            "host_limited": (os.cpu_count() or 1) < 2,
        }
    finally:
        if writer is not None:
            try:
                writer.close()
            except Exception:  # noqa: BLE001
                pass
        if gw is not None:
            gw.terminate()
        for c in cores:
            c.terminate()
        for c in cores:
            c.wait(timeout=10)


def bench_net_rebalance_storm() -> dict:
    """Armed-vs-disarmed A/B of the self-driving placement loop under a
    hotspot storm (service/rebalancer.py).

    Topology per arm: 4 partitions, 3 core processes — core 0 prefers
    ALL partitions (the pathological placement), cores 1/2 join cold —
    plus one gateway. Four writers ride the gateway with auto-reconnect,
    one per partition, one of them viral. The armed arm runs every core
    with ``--rebalance`` (0.25s tick, 2s dwell); the disarmed arm is the
    identical topology with the loop off. Every probe op must ack
    (pending drains to zero) in BOTH arms — op loss across an automatic
    migration would fail the run, not just skew a percentile.

    Published: head/tail windowed p99 of the viral writer per arm, the
    fleet ``placement.rebalance.*`` counter deltas (``admin_placement
    fleet=true``), end-of-run ownership spread, and per-core flap
    counts. The armed arm must actually migrate (fleet
    migrations_issued > 0), must not flap (0 re-moves inside dwell),
    and ends with every core owning partitions; the disarmed arm is
    the control that issued nothing."""
    import os
    import tempfile
    import time as _time

    from fluidframework_tpu.driver.network import (
        NetworkDocumentServiceFactory,
    )
    from fluidframework_tpu.loader.container import Loader
    from fluidframework_tpu.service.stage_runner import doc_partition

    n_shards = 4
    storm_s = 10.0

    def doc_for(k: int) -> str:
        i = 0
        while True:
            d = f"rb{i}"
            if doc_partition("bench", d, n_shards) == k:
                return d
            i += 1

    def pct(vals, p):
        vals = sorted(vals)
        return round(vals[int(p * (len(vals) - 1))], 3) if vals else None

    def run_arm(armed: bool) -> dict:
        shard_dir = tempfile.mkdtemp(prefix="bench-rbstorm-")
        cores, ports, gw = [], [], None
        writers = []
        try:
            extra = (("--rebalance", "--rebalance-tick", "0.25",
                      "--rebalance-dwell", "2.0", "--rebalance-budget",
                      "1") if armed else ())
            for i in range(3):
                prefer = ("--prefer", "0,1,2,3") if i == 0 else ()
                c, p = _spawn_listening(
                    "fluidframework_tpu.service.front_end", "--port", "0",
                    "--shard-dir", shard_dir, "--shards", str(n_shards),
                    "--lease-ttl", "1.5", *prefer, *extra)
                cores.append(c)
                ports.append(p)
            gw, gw_port = _spawn_listening(
                "fluidframework_tpu.service.gateway", "--shard-dir",
                shard_dir, "--shards", str(n_shards))
            chans = []
            for k in range(n_shards):
                w = Loader(NetworkDocumentServiceFactory(
                    "127.0.0.1", gw_port), auto_reconnect=True).resolve(
                    "bench", doc_for(k))
                writers.append(w)
                chans.append(w.runtime.create_data_store(
                    "default").create_channel("text", "shared-string"))

            def acked_insert(w, ch) -> float:
                t0 = _time.perf_counter()
                ch.insert_text(0, "x")
                deadline = _time.monotonic() + 30.0
                while (w.runtime.pending.count
                       and _time.monotonic() < deadline):
                    _time.sleep(0.0005)
                assert w.runtime.pending.count == 0, \
                    "storm op never acked (lost across a rebalance flip)"
                return (_time.perf_counter() - t0) * 1e3

            samples = []  # (t_since_start, ack_ms) of the viral writer
            t_start = _time.monotonic()
            while _time.monotonic() - t_start < storm_s:
                for _ in range(4):  # partition 0 is viral
                    ms = acked_insert(writers[0], chans[0])
                    samples.append((_time.monotonic() - t_start, ms))
                for w, ch in zip(writers[1:], chans[1:]):
                    acked_insert(w, ch)

            head = [ms for t, ms in samples if t <= 2.5]
            tail = [ms for t, ms in samples if t >= storm_s - 2.5]
            placement = _admin_rpc(
                ports[0], {"t": "admin_placement", "fleet": True}
            )["placement"]
            fleet = {k: v for k, v in placement["counters"].items()
                     if k.startswith("placement.rebalance.")}
            flaps, owning = 0, 0
            for p in ports:
                st = _admin_rpc(
                    p, {"t": "admin_rebalance_status"})["rebalance"]
                flaps += st.get("flaps", 0)
                own = _admin_rpc(
                    p, {"t": "admin_placement"})["placement"]["owned"]
                owning += 1 if own else 0
            return {
                "armed": armed,
                "hot_ops": len(samples),
                "head_p99_ms": pct(head, 0.99),
                "tail_p99_ms": pct(tail, 0.99),
                "tail_p50_ms": pct(tail, 0.50),
                "migrations_issued": fleet.get(
                    "placement.rebalance.migrations_issued", 0),
                "suppressed_hysteresis": fleet.get(
                    "placement.rebalance.suppressed_hysteresis", 0),
                "flaps": flaps,
                "cores_owning": owning,
            }
        finally:
            for w in writers:
                try:
                    w.close()
                except Exception:  # noqa: BLE001
                    pass
            if gw is not None:
                gw.terminate()
            for c in cores:
                c.terminate()
            for c in cores:
                c.wait(timeout=10)

    armed = run_arm(True)
    disarmed = run_arm(False)
    assert armed["migrations_issued"] > 0, \
        "armed storm issued no automatic migrations"
    assert armed["flaps"] == 0, \
        f"armed storm flapped ({armed['flaps']} re-moves inside dwell)"
    assert disarmed["migrations_issued"] == 0, \
        "disarmed control migrated — the A/B is not a control"
    return {
        "armed": armed,
        "disarmed": disarmed,
        # the loop's win: the viral writer's settled-window p99 once
        # the hotspot has been spread, vs the same window with the one
        # overloaded core still carrying everything. On a 1-CPU host
        # the three core lanes time-slice and the contrast compresses.
        "tail_p99_armed_vs_disarmed_ms": [
            armed["tail_p99_ms"], disarmed["tail_p99_ms"]],
        "host_limited": (os.cpu_count() or 1) < 4,
    }


def bench_net_fork_storm() -> dict:
    """Near-free fork at scale: 1k forks of a ≥100k-op doc.

    ONE in-process front end over a durable log + on-disk chunk store
    (the server must be reachable for byte accounting — the storm's
    storage cost is measured as real directory growth, not a counter's
    claim). The doc is driven to ≥100k sequenced ops at the config-4
    per-doc geometry, summarized ONCE, then forked 1000 times through
    the socket history door. Published and asserted:

    - **p50/p99 fork-boot ms**: wall time of each ``history fork`` RPC —
      the server seeds the fork's v0 (parent chunks re-referenced),
      adopts the post-base tail, and constructs the fork's pipeline
      before replying, so the RPC IS the boot;
    - **bytes-per-fork + dedupe ratio**: on-disk growth across the storm
      divided by forks, against the snapshot bytes each fork
      re-references — the near-zero-copy claim, asserted ≥ 10x;
    - **O(snapshot) client boots** (hard): a sample of forks cold-boots
      through fresh Loaders; ``boot.backfill.full`` must stay ZERO for
      the whole storm window (a fork that silently replays the parent's
      100k ops fails here, not in a latency mystery);
    - **integrate equivalence** (hard, seeds 0/7/42): fork + concurrent
      parent/fork writers + integrate, then the parent replayed TWO
      independent ways — history-first over sockets vs whole-log from a
      recorded file doc — must agree on every shared fingerprint seq
      and the final text.
    """
    import os
    import random
    import shutil
    import subprocess
    import tempfile
    import time as _time

    from fluidframework_tpu.driver.file import (
        FileDocumentService,
        record_document,
    )
    from fluidframework_tpu.driver.network import NetworkDocumentServiceFactory
    from fluidframework_tpu.loader.container import Loader
    from fluidframework_tpu.obs import tier_counters, tier_snapshot
    from fluidframework_tpu.replay.tool import ReplayController
    from fluidframework_tpu.service.durable_log import DurableLog
    from fluidframework_tpu.service.front_end import NetworkFrontEnd
    from fluidframework_tpu.service.local_server import LocalServer
    from fluidframework_tpu.service.service_summarizer import (
        HostReplicaSource,
        ServiceSummarizer,
    )

    doc = "fstorm0"
    n_forks = 1000
    boot_sample = 16

    def du(path):
        total = 0
        for dirpath, _, files in os.walk(path):
            for f in files:
                try:
                    total += os.path.getsize(os.path.join(dirpath, f))
                except OSError:
                    pass
        return total

    def pct(vals, p):
        vals = sorted(vals)
        return round(vals[int(p * (len(vals) - 1))], 3) if vals else None

    root = tempfile.mkdtemp(prefix="bench-fork-")
    server = LocalServer(log=DurableLog(os.path.join(root, "log")),
                         storage_dir=os.path.join(root, "store"))
    front = NetworkFrontEnd(server).start_background()
    port = front.port
    factory = NetworkDocumentServiceFactory("127.0.0.1", port)
    drv = tier_counters("driver")

    def quiesce(container, what):
        deadline = _time.time() + 60
        while container.runtime.pending.count and _time.time() < deadline:
            _time.sleep(0.01)
        assert container.runtime.pending.count == 0, \
            f"{what} never quiesced"

    try:
        # attach + drive the long-lived doc (10 clients × 320 × 32-op
        # boxcars = 102,400 ops — the join-storm geometry)
        writer = Loader(factory).resolve("bench", doc)
        ss = writer.runtime.create_data_store("default").create_channel(
            "text", "shared-string")
        ss.insert_text(0, "fork-storm seed ")
        quiesce(writer, "fork-storm writer")
        w = subprocess.Popen(
            _lean_cmd("fluidframework_tpu.service.load_async",
                      "--port", str(port), "--docs", "1",
                      "--clients-per-doc", "10", "--rounds", "320",
                      "--batch", "32", "--rate", "8", "--seed", "7",
                      "--doc-prefix", "fstorm", "--timeout", "300"),
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True, cwd=REPO)
        doc_ops = json.loads(w.communicate(timeout=900)[0])["acked"]
        assert doc_ops >= 100_000, f"doc too short: {doc_ops} acked"
        writer.close()

        ServiceSummarizer(server, HostReplicaSource(server)).summarize_doc(
            "bench", doc)
        head = server.history.log("bench", doc)[0]
        shared_bytes = sum(len(server.blob_store.get(cid))
                           for cid in head["chunk_ids"])

        # the storm: 1k fork RPCs through the socket history door
        h = factory.create_document_service("bench", doc).history()
        pre_bytes = du(root)
        pre_svc = tier_snapshot("service")
        pre_drv = drv.snapshot()
        fork_ms = []
        for i in range(n_forks):
            t0 = _time.perf_counter()
            res = h.fork(new_doc=f"fstormf{i:04d}")
            fork_ms.append(round((_time.perf_counter() - t0) * 1e3, 2))
            assert res["shared_chunks"] > 0, f"fork {i} shared no chunks"
        post_bytes = du(root)
        post_svc = tier_snapshot("service")

        def delta(post, pre, name):
            return post.get(name, 0) - pre.get(name, 0)

        boots = delta(post_svc, pre_svc, "history.fork.boots")
        assert boots == n_forks, \
            f"server counted {boots} fork boots for {n_forks} forks"
        bytes_per_fork = round((post_bytes - pre_bytes) / n_forks, 1)
        dedupe_x = round(shared_bytes / max(bytes_per_fork, 1.0), 1)
        assert dedupe_x >= 10.0, \
            (f"forks are not near-free: {bytes_per_fork} B/fork written "
             f"vs {shared_bytes} B re-referenced ({dedupe_x}x)")

        # O(snapshot) sample boots: cold Loaders on a spread of forks
        want_text = None
        tti = []
        for i in range(0, n_forks, max(1, n_forks // boot_sample)):
            jf = NetworkDocumentServiceFactory("127.0.0.1", port,
                                               counters=drv)
            t0 = _time.perf_counter()
            c = Loader(jf).resolve("bench", f"fstormf{i:04d}")
            text = (c.runtime.get_data_store("default")
                    .get_channel("text").get_text())
            tti.append(round(_time.perf_counter() - t0, 3))
            if want_text is None:
                want_text = text
            assert text == want_text, f"fork {i} diverged from the storm"
            c.close()
        post_drv = drv.snapshot()
        full = delta(post_drv, pre_drv, "boot.backfill.full")
        assert full == 0, \
            f"{full} whole-log replay(s) inside the fork storm window"
        assert delta(post_drv, pre_drv, "boot.backfill.bounded") \
            == len(tti), "a fork boot was not snapshot-bounded"

        # integrate equivalence at three seeds: concurrent fork/parent
        # writers, integrate, then two INDEPENDENT replays of the parent
        # (history-first over sockets vs whole-log from a file record)
        # must agree on every shared fingerprint
        eq_fps = 0
        for seed in (0, 7, 42):
            dn, fn = f"eq{seed}", f"eq{seed}f"
            rng = random.Random(seed)
            pw = Loader(factory).resolve("bench", dn)
            ps = pw.runtime.create_data_store("default").create_channel(
                "text", "shared-string")
            for i in range(24):
                ps.insert_text(rng.randrange(len(ps.get_text()) + 1),
                               f"s{i} ")
            quiesce(pw, f"eq{seed} base writer")
            ServiceSummarizer(
                server, HostReplicaSource(server)).summarize_doc(
                "bench", dn)
            factory.create_document_service("bench", dn).history().fork(
                new_doc=fn)
            fw = Loader(factory).resolve("bench", fn)
            fs = fw.runtime.get_data_store("default").get_channel("text")
            for i in range(6):  # interleaved divergence on both sides
                fs.insert_text(rng.randrange(len(fs.get_text()) + 1),
                               f"F{i} ")
                ps.insert_text(rng.randrange(len(ps.get_text()) + 1),
                               f"P{i} ")
            quiesce(fw, f"eq{seed} fork writer")
            quiesce(pw, f"eq{seed} parent writer")
            out = factory.create_document_service(
                "bench", fn).history().integrate()
            assert out["ops"] == 6, f"seed {seed}: {out['ops']} ops"
            deadline = _time.time() + 60
            while ps.get_text().count("F") < 6 \
                    and _time.time() < deadline:
                _time.sleep(0.01)
            assert ps.get_text().count("F") == 6, \
                f"seed {seed}: integrated edits never landed"
            hist = ReplayController(factory.create_document_service(
                "bench", dn)).run(25)
            with tempfile.TemporaryDirectory() as d:
                doc_dir = record_document(server, "bench", dn, d)
                snap = os.path.join(doc_dir, "snapshot.json")
                if os.path.exists(snap):
                    os.remove(snap)
                legacy = ReplayController(
                    FileDocumentService.from_dir(doc_dir)).run(25)
            assert hist["final_text"] == legacy["final_text"] \
                == ps.get_text(), f"seed {seed}: final-text drift"
            common = set(hist["snapshots"]) & set(legacy["snapshots"])
            assert common, f"seed {seed}: no shared fingerprint seqs"
            for q in common:
                assert hist["snapshots"][q] == legacy["snapshots"][q], \
                    f"seed {seed}: fingerprint drift at seq {q}"
            eq_fps += len(common)
            fw.close()
            pw.close()

        return {
            "doc_ops": doc_ops,
            "forks": n_forks,
            "fork_p50_ms": pct(fork_ms, 0.5),
            "fork_p99_ms": pct(fork_ms, 0.99),
            "bytes_per_fork": bytes_per_fork,
            "snapshot_bytes_shared": shared_bytes,
            "dedupe_ratio_x": dedupe_x,
            "boot_sample_tti_p50_s": pct(tti, 0.5),
            "boot_sample_boots": len(tti),
            "boot_backfill_full_in_bench": full,
            "integrate_equivalence": {"seeds": [0, 7, 42], "ok": True,
                                      "fingerprints_compared": eq_fps},
            "counters": {
                "history.fork.boots": boots,
                "history.fork.tail_ops": delta(
                    post_svc, pre_svc, "history.fork.tail_ops"),
                "history.commit.records": post_svc.get(
                    "history.commit.records", 0),
            },
        }
    finally:
        front.stop()
        shutil.rmtree(root, ignore_errors=True)


def bench_net_cold_storm() -> dict:
    """Fleet cold-start storm: kill a 2-core subprocess fleet and
    restart it from its topology spec with 1k/4k/10k docs on disk.

    One ``TopologySpec`` (service/topology.py) IS the fleet: cores +
    storage tier + admission knobs, restarted with ``Fleet.restart()``
    — no per-core argv reconstruction. Docs are seeded incrementally
    (each axis point reuses the previous point's corpus), summarized
    through the service summarizer and checkpointed by the cores' own
    2 s ticker, so every boot in the restarted generation is the lazy
    O(snapshot + durable-log tail) path. Per axis point:

    - **cold-boot time**: kill -9 → restart from spec → first-route
      every doc (raw readonly connects, ``boot_pending`` replies
      retried after their ``retryAfterMs``) until the whole corpus
      serves — the client-driven boot storm, wall-clocked end to end;
    - **time-to-first-edit**: one sampled cold doc boots through a
      real Loader and acks one edit, timed from connect start — what a
      reconnecting user feels;
    - **warm-doc ack p99 during the storm** vs the same probe on a
      quiet fleet: the admission gate's whole point is that docs
      already booted keep their latency while thousands of cold boots
      queue behind the token bucket (asserted ≤ 1.5x unless
      host_limited — on a 1-CPU host the storm time-slices the probe);
    - **the lazy contract, in-bench (hard)**: ``admin_boot_status``
      summed over the restarted cores must show ZERO
      ``boot.part.full_replay`` — a missing summary or checkpoint
      fails the bench here, not in a latency mystery.
    """
    import os
    import shutil
    import socket as _socket
    import tempfile
    import threading
    import time as _time

    from fluidframework_tpu.driver.network import (
        NetworkDocumentServiceFactory,
        _Transport,
    )
    from fluidframework_tpu.loader.container import Loader
    from fluidframework_tpu.service.placement_plane import EpochTable
    from fluidframework_tpu.service.stage_runner import doc_partition
    from fluidframework_tpu.service.topology import Fleet, default_spec

    axis = [1000, 4000, 10000]
    n_parts = 8
    warm_docs = [f"warm{i}" for i in range(4)]
    host_limited = (os.cpu_count() or 1) < 4

    def pct(vals, p):
        vals = sorted(vals)
        return round(vals[int(p * (len(vals) - 1))], 3)

    def fr(obj):
        body = json.dumps(obj, separators=(",", ":")).encode()
        return len(body).to_bytes(4, "big") + body

    def read_frame(s, buf):
        while True:
            if len(buf[0]) >= 4:
                n = int.from_bytes(buf[0][:4], "big")
                if len(buf[0]) >= 4 + n:
                    body, buf[0] = buf[0][4:4 + n], buf[0][4 + n:]
                    return json.loads(body)
            chunk = s.recv(65536)
            if not chunk:
                raise ConnectionError("cold-storm socket closed")
            buf[0] += chunk

    def chanop(cseq, i):
        return {"clientSequenceNumber": cseq,
                "referenceSequenceNumber": 0, "type": "op",
                "contents": {"kind": "chanop", "address": "default",
                             "contents": {"address": "text",
                                          "contents": {"type": 0,
                                                       "pos": 0,
                                                       "text": f"s{i} "}}}}

    root = tempfile.mkdtemp(prefix="bench-cold-storm-")
    # lease_ttl: long enough that a core stalled by storm work on a
    # time-sliced host doesn't lose partitions mid-measurement (churn
    # is the chaos drill's subject, not this bench's); restart still
    # only waits one ttl for the killed generation's leases to stale
    spec = default_spec(os.path.join(root, "fleet"), n_cores=2,
                        n_partitions=n_parts, lease_ttl=6.0,
                        summarize_every=10 ** 6)
    fl = Fleet(spec, subprocess=True).start()
    fl.wait_claimed()
    table = EpochTable.for_shard_dir(spec.shard_dir)

    def port_for(doc):
        k = doc_partition("bench", doc, n_parts)
        rec = table.read()["parts"][str(k)]
        return int(rec["addr"].rsplit(":", 1)[1])

    def resolve_net(doc):
        """Loader boot at the doc's CURRENT owner; ownership can churn
        for a beat after wait_claimed (the chaos drill's reroute
        idiom), so re-read the table and retry on routing refusals."""
        deadline = _time.monotonic() + 30.0
        while True:
            try:
                return Loader(NetworkDocumentServiceFactory(
                    "127.0.0.1", port_for(doc))).resolve("bench", doc)
            except (RuntimeError, ConnectionError) as e:
                if ("not the owner" not in str(e)
                        or _time.monotonic() >= deadline):
                    raise
                _time.sleep(0.2)

    def seed(doc):
        deadline = _time.monotonic() + 30.0
        while True:
            s = _socket.create_connection(("127.0.0.1", port_for(doc)),
                                          timeout=30)
            buf = [b""]
            s.sendall(fr({"t": "connect", "tenant": "bench", "doc": doc,
                          "rid": 1, "bin": 0}))
            reply = read_frame(s, buf)
            while reply.get("rid") != 1:
                reply = read_frame(s, buf)
            if reply.get("t") == "error":
                # ownership can churn for a beat around a takeover:
                # re-read the table (port_for) and retry at the owner
                s.close()
                assert ("not the owner" in str(reply.get("message"))
                        and _time.monotonic() < deadline), \
                    f"seed refused: {reply}"
                _time.sleep(0.2)
                continue
            s.sendall(fr({"t": "submit",
                          "ops": [chanop(i + 1, i) for i in range(4)]}))
            s.close()
            return

    def route_cold(doc):
        """One first route: raw readonly connect, boot_pending replies
        retried after their advertised backoff. Returns retry count."""
        parked = 0
        while True:
            s = _socket.create_connection(("127.0.0.1", port_for(doc)),
                                          timeout=30)
            buf = [b""]
            s.sendall(fr({"t": "connect", "tenant": "bench", "doc": doc,
                          "rid": 1, "bin": 0, "readonly": 1}))
            reply = read_frame(s, buf)
            while reply.get("rid") != 1:
                reply = read_frame(s, buf)
            s.close()
            if reply.get("t") != "error":
                return parked
            if "not the owner" in str(reply.get("message", "")):
                _time.sleep(0.2)  # reroute: the loop re-reads the table
                continue
            assert reply.get("code") == "boot_pending", \
                f"cold route refused: {reply}"
            parked += 1
            _time.sleep((reply.get("retryAfterMs") or 50) / 1000)

    def summarize_all(docs):
        trans = {p: _Transport("127.0.0.1", p, timeout=30.0)
                 for p in fl.core_ports.values()}
        try:
            for doc in docs:
                deadline = _time.monotonic() + 30.0
                while True:
                    try:
                        trans[port_for(doc)].request_rid(
                            {"t": "admin_summarize", "tenant": "bench",
                             "doc": doc})
                        break
                    except RuntimeError as e:
                        if ("not the owner" not in str(e)
                                or _time.monotonic() >= deadline):
                            raise
                        _time.sleep(0.2)
        finally:
            for t in trans.values():
                t.close()

    def boot_totals():
        tot = {}
        for p in fl.core_ports.values():
            t = _Transport("127.0.0.1", p, timeout=30.0)
            try:
                _, rep = t.request_rid({"t": "admin_boot_status"})
            finally:
                t.close()
            for k, v in rep["boot"]["counters"].items():
                tot[k] = tot.get(k, 0) + v
        return tot

    def warm_probe(sstrs, lats, stop=None):
        """Round-robin timed edits on the warm docs until ``stop`` is
        set (or one pass when no stop event is given)."""
        while True:
            for c, sstr in sstrs:
                t0 = _time.perf_counter()
                sstr.insert_text(0, "w")
                deadline = _time.monotonic() + 60.0
                while (c.runtime.pending.count
                       and _time.monotonic() < deadline):
                    _time.sleep(0.0005)
                assert c.runtime.pending.count == 0, \
                    "warm probe op never acked during the storm"
                lats.append((_time.perf_counter() - t0) * 1e3)
                _time.sleep(0.002)
            if stop is None or stop.is_set():
                return

    rows = []
    seeded = 0
    try:
        for doc in warm_docs:
            seed(doc)
        for target in axis:
            for d in range(seeded, target):
                seed(f"cs{d}")
            seeded = target
            summarize_all([f"cs{d}" for d in range(target)] + warm_docs)
            _time.sleep(3.0)  # two checkpoint-ticker passes

            fl.restart()
            fl.wait_claimed()

            # warm docs boot first, then a quiet-fleet baseline probe
            warm = []
            for doc in warm_docs:
                c = resolve_net(doc)
                warm.append((c, c.runtime.get_data_store(
                    "default").get_channel("text")))
            baseline: list = []
            for _ in range(15):
                warm_probe(warm, baseline)

            # the storm: first-route every cold doc, wall-clocked;
            # doc 0 boots through a real Loader (time-to-first-edit)
            parked = [0]
            tti = [0.0]

            def storm(n=target):
                t0 = _time.perf_counter()
                c = resolve_net("cs0")
                ds = c.runtime.data_stores
                sstr = (c.runtime.get_data_store("default")
                        .get_channel("text")
                        if "default" in ds else
                        c.runtime.create_data_store(
                            "default").create_channel(
                                "text", "shared-string"))
                sstr.insert_text(0, "first ")
                while c.runtime.pending.count:
                    _time.sleep(0.0005)
                tti[0] = (_time.perf_counter() - t0) * 1e3
                c.close()
                for d in range(1, n):
                    parked[0] += route_cold(f"cs{d}")

            stop = threading.Event()
            storm_lats: list = []
            prober = threading.Thread(
                target=warm_probe, args=(warm, storm_lats, stop))
            t0 = _time.monotonic()
            prober.start()
            try:
                storm()
            finally:
                stop.set()
                prober.join()
            cold_boot_s = _time.monotonic() - t0

            tot = boot_totals()
            replays = tot.get("boot.part.full_replay", 0)
            assert replays == 0, \
                (f"{replays} doc(s) whole-log replayed at the {target} "
                 f"point — the O(snapshot+tail) contract broke: {tot}")
            lazy = tot.get("boot.part.lazy", 0)
            assert lazy >= target, \
                f"only {lazy} lazy boots for {target} docs: {tot}"
            ratio = round(pct(storm_lats, 0.99)
                          / max(pct(baseline, 0.99), 1e-9), 3)
            if not host_limited:
                assert ratio <= 1.5, \
                    (f"warm-doc ack p99 {ratio}x baseline during the "
                     f"{target}-doc storm (admission gate not holding)")
            for c, _sstr in warm:
                c.close()
            rows.append({
                "docs": target,
                "cold_boot_s": round(cold_boot_s, 2),
                "boots_per_s": round(target / cold_boot_s, 1),
                "time_to_first_edit_ms": round(tti[0], 1),
                "warm_p99_ack_ms_baseline": pct(baseline, 0.99),
                "warm_p99_ack_ms_storm": pct(storm_lats, 0.99),
                "warm_p99_vs_baseline_x": ratio,
                "parked_retries": parked[0],
                "boot_part_lazy": lazy,
                "boot_part_full_replay": 0,
            })
        return {
            "axis": rows,
            "cores": 2,
            "partitions": n_parts,
            "host_limited": host_limited,
            "admission": {"rate_per_s": spec.boot_rate,
                          "burst": spec.boot_burst},
        }
    finally:
        fl.stop()
        shutil.rmtree(root, ignore_errors=True)


def bench_net_multihost() -> dict:
    """Weak scaling across simulated host groups: 1 → 2 → 4 hosts, one
    core + one gateway each, per-host offered load held constant.

    Each fleet comes from one ``multihost_spec`` (service/topology.py):
    ``h0`` is the placement host (shard dir, storage tier, table door);
    every other group runs in a DISJOINT working dir with its cores on
    ``RemoteTableClient`` — the lease/epoch plane reached only over the
    ``admin_table_*`` door. Per axis point:

    - **ops/s, total and per host**: every host's gateway carries the
      same load mix — 4 docs owned by its OWN host (doc names mined so
      their partitions land in that host's pinned prefer set) plus, on
      multi-host points, 2 docs owned by the NEXT host. Weak-scaling
      efficiency = total(H) / (H × total(1)).
    - **same-host vs cross-host ack + hop p99**: the mined prefixes
      classify every worker as same- or cross-host at its entry
      gateway, so the ack split (and the per-hop-pair catalog split,
      trace tails sampled 1-in-16) is exact, not inferred.
    - **locality hit rate**: ``fanout.upstream.same_host /
      (same_host + cross_host)`` summed over the gateways' own counter
      scrape — the host-aware routing proof.
    - **disjointness, in-bench (hard)**: every remote-group process's
      ``/proc/<pid>/fd`` table is scanned — an fd open under the
      placement host's shard dir fails the bench (remote groups share
      sockets, never files), and remote working dirs must contain no
      ``placement/`` lease/table state at all.
    - **the remote-table boot path (hard)**: at the 2-host point the
      h1 group is kill -9'd (its own process group) and respawned from
      its spec copy; its checkpointed docs must re-serve with
      ``boot.part.full_replay == 0`` — lazy O(snapshot+tail) boots
      through the door, not through any shared file.
    """
    import os
    import shutil
    import socket as _socket
    import subprocess
    import tempfile
    import time as _time

    from fluidframework_tpu.driver.network import _Transport
    from fluidframework_tpu.service.stage_runner import doc_partition
    from fluidframework_tpu.service.topology import Fleet, multihost_spec

    axis = [1, 2, 4]
    parts_per_host = 4
    docs_same, docs_cross = 4, 2
    rate, batch, rounds = 2.0, 8, 24
    host_limited = (os.cpu_count() or 1) < 4

    def pct(vals, p):
        vals = sorted(vals)
        return round(vals[int(p * (len(vals) - 1))], 3) if vals else None

    def fr(obj):
        body = json.dumps(obj, separators=(",", ":")).encode()
        return len(body).to_bytes(4, "big") + body

    def read_frame(s, buf):
        while True:
            if len(buf[0]) >= 4:
                n = int.from_bytes(buf[0][:4], "big")
                if len(buf[0]) >= 4 + n:
                    body, buf[0] = buf[0][4:4 + n], buf[0][4 + n:]
                    return json.loads(body)
            chunk = s.recv(65536)
            if not chunk:
                raise ConnectionError("multihost socket closed")
            buf[0] += chunk

    def mine_prefix(tag, owner_parts, n_docs, n_parts):
        """A doc prefix whose first n_docs docs ALL partition into
        owner_parts — exact entry-gateway-vs-owner classification."""
        for t in range(200_000):
            p = f"{tag}x{t}d"
            if all(doc_partition("bench", f"{p}{d}", n_parts)
                   in owner_parts for d in range(n_docs)):
                return p
        raise AssertionError(f"no prefix mined for {tag}")

    def gw_counters(addr):
        s = _socket.create_connection(addr, timeout=10)
        buf = [b""]
        try:
            s.sendall(fr({"t": "gateway_counters", "rid": 1}))
            reply = read_frame(s, buf)
            while reply.get("rid") != 1:
                reply = read_frame(s, buf)
            return reply["counters"]
        finally:
            s.close()

    def run_point(root, n_hosts):
        n_parts = parts_per_host * n_hosts
        spec = multihost_spec(
            os.path.join(root, f"fleet{n_hosts}"), n_hosts, 1, n_parts,
            lease_ttl=6.0, summarize_every=10 ** 6)
        host_parts = {h: set(spec.cores[h].prefer)
                      for h in range(n_hosts)}
        fl = Fleet(spec, subprocess=True).start()
        try:
            fl.wait_claimed()

            # one load worker per (gateway, locality class)
            plans = []  # (host, cls, prefix, docs)
            for h in range(n_hosts):
                plans.append((h, "same", mine_prefix(
                    f"mh{n_hosts}s{h}", host_parts[h], docs_same,
                    n_parts), docs_same))
                if n_hosts > 1:
                    plans.append((h, "cross", mine_prefix(
                        f"mh{n_hosts}c{h}",
                        host_parts[(h + 1) % n_hosts], docs_cross,
                        n_parts), docs_cross))
            start_at = _time.time() + 6.0
            workers = []
            for w, (h, cls, prefix, docs) in enumerate(plans):
                gh, gp = fl.gateway_addr(h)
                workers.append((cls, subprocess.Popen(
                    _lean_cmd("fluidframework_tpu.service.load_async",
                              "--host", gh, "--port", str(gp),
                              "--docs", str(docs),
                              "--clients-per-doc", "1",
                              "--rounds", str(rounds),
                              "--batch", str(batch),
                              "--rate", str(rate), "--seed", str(w),
                              "--start-at", str(start_at),
                              "--doc-prefix", prefix),
                    stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                    text=True, cwd=REPO)))
            lats = {"same": [], "cross": []}
            hops = {"same": {}, "cross": {}}
            ops = acked = 0
            secs = 0.0
            for cls, w in workers:
                out, _ = w.communicate(timeout=300)
                r = json.loads(out)
                lats[cls].extend(r["lat_ms"])
                for k, v in r["hops"].items():
                    hops[cls].setdefault(k, []).extend(v)
                ops += r["ops"]
                acked += r["acked"]
                secs = max(secs, r["seconds"])
                assert not r["errors"], (n_hosts, cls, r["errors"][:3])
            assert acked == ops, (n_hosts, acked, ops)

            # locality hit rate from the gateways' OWN counters
            same = cross = 0
            for h in range(n_hosts):
                c = gw_counters(fl.gateway_addr(h))
                same += c.get("fanout.upstream.same_host", 0)
                cross += c.get("fanout.upstream.cross_host", 0)
            assert same > 0, "no same-host routes counted"
            if n_hosts > 1:
                assert cross > 0, "cross-host workers counted no " \
                                  "cross-host routes"

            # disjointness: remote groups share SOCKETS, never files —
            # no remote-group fd may be open under the placement dir,
            # and no placement/lease/table state may exist in a remote
            # working dir (the placement dir is effectively unreadable
            # to them: nothing ever opened it)
            canon = os.path.join(spec.shard_dir, "")
            leaked = []
            for hid, procs in fl.host_procs.items():
                if not spec.host_is_remote(hid):
                    continue
                for p in procs:
                    fd_dir = f"/proc/{p.pid}/fd"
                    for fd in os.listdir(fd_dir):
                        try:
                            tgt = os.readlink(os.path.join(fd_dir, fd))
                        except OSError:
                            continue
                        if tgt.startswith(canon):
                            leaked.append((hid, p.pid, tgt))
                entries = os.listdir(spec.host_dir(hid))
                assert "placement" not in entries, \
                    (f"host {hid} grew local placement state: "
                     f"{entries}")
            assert not leaked, \
                f"remote groups touched placement-host files: {leaked}"

            # the remote-table boot path: kill -9 the h1 group, respawn
            # it from its spec copy, and every checkpointed doc must
            # lazy-boot (zero whole-log replays) through the door
            replay = lazy = None
            if n_hosts == 2:
                h1_prefix = next(p for h, cls, p, _ in plans
                                 if h == 1 and cls == "same")
                t = _Transport("127.0.0.1", fl.core_ports[1],
                               timeout=30.0)
                try:
                    for d in range(docs_same):
                        t.request_rid({"t": "admin_summarize",
                                       "tenant": "bench",
                                       "doc": f"{h1_prefix}{d}"})
                finally:
                    t.close()
                _time.sleep(3.0)  # two checkpoint-ticker passes
                fl.kill_host("h1")
                fl.start_host("h1")
                fl.wait_claimed(parts=host_parts[1], timeout=60.0)
                for d in range(docs_same):
                    s = _socket.create_connection(
                        ("127.0.0.1", fl.core_ports[1]), timeout=30)
                    buf = [b""]
                    s.sendall(fr({"t": "connect", "tenant": "bench",
                                  "doc": f"{h1_prefix}{d}", "rid": 1,
                                  "bin": 0, "readonly": 1}))
                    reply = read_frame(s, buf)
                    while reply.get("rid") != 1:
                        reply = read_frame(s, buf)
                    s.close()
                    if (reply.get("t") == "error"
                            and reply.get("code") == "boot_pending"):
                        _time.sleep(
                            (reply.get("retryAfterMs") or 50) / 1000)
                t = _Transport("127.0.0.1", fl.core_ports[1],
                               timeout=30.0)
                try:
                    _, rep = t.request_rid({"t": "admin_boot_status"})
                finally:
                    t.close()
                tot = rep["boot"]["counters"]
                replay = tot.get("boot.part.full_replay", 0)
                lazy = tot.get("boot.part.lazy", 0)
                assert replay == 0, \
                    (f"{replay} whole-log replays through the "
                     f"remote-table boot path: {tot}")

            row = {
                "hosts": n_hosts,
                "partitions": n_parts,
                "ops_per_sec": round(ops / secs, 1) if secs else 0.0,
                "ops_per_sec_per_host":
                    round(ops / secs / n_hosts, 1) if secs else 0.0,
                "same_host_ack_p99_ms": pct(lats["same"], 0.99),
                "cross_host_ack_p99_ms": pct(lats["cross"], 0.99),
                "hop_p99_ms": {
                    cls: {name: pct(v, 0.99)
                          for name, v in hv.items()}
                    for cls, hv in hops.items() if hv},
                "locality": {
                    "same_host_routes": same,
                    "cross_host_routes": cross,
                    "hit_rate": round(same / max(same + cross, 1), 3)},
                "remote_fd_leaks": 0,
            }
            if replay is not None:
                row["host_restart"] = {
                    "boot_part_full_replay": replay,
                    "boot_part_lazy": lazy}
            return row
        finally:
            fl.stop()

    root = tempfile.mkdtemp(prefix="bench-multihost-")
    try:
        rows = [run_point(root, h) for h in axis]
        base = rows[0]["ops_per_sec"] or 1e-9
        for r in rows[1:]:
            r["weak_scaling_efficiency"] = round(
                r["ops_per_sec"] / (r["hosts"] * base), 3)
        return {"axis": rows, "cores_per_host": 1,
                "rate_hz_per_client": rate,
                "host_limited": host_limited}
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_multichip() -> dict:
    """Per-device scaling of the doc-mesh lane (tools/bench_multichip):
    docs axis 1→2→4→8 as far as the devices go (the chips present, or
    forced host devices on a JAX_PLATFORMS=cpu run), in a child process
    that main() runs before this process touches JAX. Writes the
    MULTICHIP_r06 artifact as a side effect."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.abspath(__file__))
    out = subprocess.run(
        [sys.executable, "-m", "tools.bench_multichip",
         "--out", os.path.join(repo, "MULTICHIP_r06.json")],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        cwd=repo, timeout=600)
    if out.returncode:
        return {"ok": False, "rc": out.returncode}
    result = json.loads(out.stdout.strip().splitlines()[-1])
    return {
        "ok": result["ok"],
        "n_devices": result["n_devices"],
        "forced_host": result["forced_host"],
        "mesh_vs_local_1shard": result["mesh_vs_local_1shard"],
        "rungs": [
            {k: r[k] for k in ("docs_axis", "ops_per_sec",
                               "scaling_efficiency", "staging_ms_per_wave")}
            for r in result["rungs"]],
    }


def main() -> None:
    from fluidframework_tpu.utils.chip import init_compile_cache, require_tpu

    init_compile_cache()
    # every child that needs the chip (the split-service applier stage,
    # the multichip sweep) runs BEFORE this process touches JAX: a chip
    # belongs to one process, and a parent holding it starves the child
    net = bench_network()
    overload = bench_overload_sweep(net["knee"])
    join_storm = bench_join_storm()
    read_storm = bench_net_read_storm()
    rebalance_storm = bench_net_rebalance_storm()
    fork_storm = bench_net_fork_storm()
    cold_storm = bench_net_cold_storm()
    multihost = bench_net_multihost()
    multichip = bench_multichip()
    require_tpu()
    kernel_ops, kernel_xla_ops = bench_kernel()
    scalar_deli = bench_scalar_deli()
    service = bench_service()
    seg_storage = bench_segment_storage()
    print(
        json.dumps(
            {
                "metric": "service_path_ops_per_sec",
                "value": service["ops_per_sec"],
                "unit": "ops/s",
                # against the 50k NORTH STAR (BASELINE.json: the
                # reference repo publishes no numbers of its own)
                "vs_north_star_50k": round(
                    service["ops_per_sec"] / NORTH_STAR_OPS_PER_SEC, 3),
                # the scalar _ticket lane (one process, per-op message
                # objects, no boxcars) and the headline's speedup over
                # it: what the boxcar/array/columnar batching buys
                "scalar_deli_ops_per_sec": round(scalar_deli, 1),
                "vs_scalar_deli_x": round(
                    service["ops_per_sec"] / scalar_deli, 2),
                # the same pipeline fed per-op message objects instead
                # of the array-lane boxcars (deli-tpu marshal)
                "ops_per_sec_dict_lane": service.get("ops_per_sec_dict_lane"),
                # and over the durable C++ op log (split-core posture)
                "ops_per_sec_durable_log": service.get(
                    "ops_per_sec_durable_log"),
                # columnar segment store vs the scalar record lane over
                # the same 100k-op deltas stream: recovery replay s/GB
                # and seq-range backfill throughput (backfill_decodes
                # is counter-verified zero — raw byte-range serving)
                **seg_storage,
                # ack latency AT the headline load (submit → own
                # broadcast, per boxcar): the north star's "p99 < 50 ms
                # at >= 50k ops/s" measured on one path simultaneously
                "p99_ack_ms_at_load": service["p99_ack_ms"],
                # Pallas VMEM-resident kernel; the XLA scan for comparison
                "kernel_ops_per_sec": round(kernel_ops, 1),
                "kernel_xla_ops_per_sec": round(kernel_xla_ops, 1),
                # the same full path at 8192 concurrent docs (scale proof)
                "ops_per_sec_8k_docs": service.get("ops_per_sec_8k_docs"),
                # at-load socket knee (256 docs × 2 clients, binary wire,
                # 32-op boxcars, 2-gateway production topology): the
                # highest rate whose median-of-5 confirmation holds
                # p99 < 50 ms (stepped down from the sweep if needed; at
                # the floor the published p99 marks a miss)
                "net_max_load_ops_per_sec": net["knee"]["ops_per_sec"],
                "net_p50_ack_ms": net["knee"]["p50_ack_ms"],
                "net_p99_ack_ms": net["knee"]["p99_ack_ms"],
                "net_docs": 256,
                "net_clients": 512,
                "net_hops": net["knee"].get("hops", {}),
                # same geometry terminating directly at the core — the
                # gateway tier must not lose to it
                "net_direct_ops_per_sec": net["direct"]["ops_per_sec"],
                "net_direct_p99_ack_ms": net["direct"]["p99_ack_ms"],
                # BASELINE config 4: 1000 docs × 10 clients (10k sockets)
                "net_ops_per_sec_1k_docs": net["cfg4"]["ops_per_sec"],
                "net_p50_ack_ms_1k_docs": net["cfg4"]["p50_ack_ms"],
                "net_p99_ack_ms_1k_docs": net["cfg4"]["p99_ack_ms"],
                # north-star geometry: 10,000 docs × 1 client (10k
                # sockets, doc-table scale without fan-out amplification)
                "net_10k_docs": net["net_10k_docs"],
                # 2-core SHARDED ordering core at the knee geometry
                # (VERDICT r4 #4: the sequencer scales out; target
                # >= 1.5x the 1-core knee)
                "net_sharded_2core_ops_per_sec":
                    net["sharded"]["ops_per_sec"],
                "net_sharded_2core_p99_ack_ms":
                    net["sharded"]["p99_ack_ms"],
                # 4-core lane ladder (placement control plane): on a
                # multi-CPU host the target is ≥1.5× per added core vs
                # the 1-core knee; on this 1-CPU host both sharded rows
                # carry host_limited=true plus per-lane CPU attribution
                # (/proc/<pid>/stat) proving the subprocess lanes worked
                "net_sharded_2core_cpu_s": net["sharded"].get("core_cpu_s"),
                "net_sharded_2core_host_limited":
                    net["sharded"].get("host_limited"),
                "net_sharded_4core_ops_per_sec":
                    net["sharded_4core"]["ops_per_sec"],
                "net_sharded_4core_p99_ack_ms":
                    net["sharded_4core"]["p99_ack_ms"],
                "net_sharded_4core_cpu_s":
                    net["sharded_4core"].get("core_cpu_s"),
                "net_sharded_4core_host_limited":
                    net["sharded_4core"].get("host_limited"),
                # p99/max ack of a steady probe across one forced live
                # migration vs the same probe undisturbed: the price of
                # a seal → flip → reconnect+replay window under traffic
                "migration_blip_ms": net["migration_blip"],
                # socket-tier batching counters from the core that served
                # the knee+direct runs: nonzero ingress coalescing and
                # flush eliding is the proof the amortization engaged
                "net_batching": {
                    k: v for k, v in net.get("batching", {}).items()
                    if k.startswith("net.")},
                # per-hop-pair observation counts scraped from the core's
                # metrics registry (admin_metrics_scrape) over the knee
                # window: every server-visible leg must have counted
                "net_hop_breakdown": net.get("hop_breakdown", {}),
                # trace sampling armed (1-in-16) vs disarmed at the knee
                # rate: the two throughputs must sit within run-to-run
                # noise of each other
                "net_trace_ab": net.get("trace_ab", {}),
                # live health plane armed (canary prober + streaming
                # engine) vs disarmed at the knee rate: the two
                # throughputs must sit within run-to-run noise, and the
                # armed run publishes the canary's per-door p99 at load
                "net_health_ab": net.get("health_ab", {}),
                # closed-loop overload control: offered load 0.5×–4× of
                # the knee against the armed admission gate (capped
                # "bulk" tenant sheds, uncapped "steady" tenant rides
                # through), plus the --no-shed collapse control and the
                # caps-free armed/plain overhead pair
                "net_overload_sweep": overload,
                # late-joiner catch-up on a ≥100k-op doc (config-4
                # per-doc geometry): p50/p99 time-to-interactive of a
                # cold-join storm through the columnar snapshot plane,
                # vs whole-log replay; encode-once counter-asserted
                # (per-join snapshot re-encodes == 0)
                "net_join_storm": join_storm,
                # read-scale fan-out: 10k-target read-only subscribers
                # (scaled to host, host_limited when capped) behind a
                # 2-level relay tree; writer ack p99 vs zero-reader
                # baseline, core-tier bytes/op across 10x subscriber
                # growth (~flat asserted), relay re-encodes
                # counter-asserted 0 above the core
                "net_read_storm": read_storm,
                # self-driving placement A/B: the same 3-core hotspot
                # storm with the rebalancer armed vs off. Armed must
                # migrate (fleet counters), never flap, lose nothing,
                # and end with every core owning partitions
                "net_rebalance_storm": rebalance_storm,
                # doc history plane at scale: 1k near-free forks of a
                # ≥100k-op doc through the socket history door — fork
                # RPC p50/p99, on-disk bytes-per-fork vs the snapshot
                # bytes each fork re-references (dedupe ≥10x asserted),
                # zero whole-log replays in-bench, and the integrated
                # parent fingerprint-equal across history-first and
                # whole-log replays at seeds 0/7/42
                "net_fork_storm": fork_storm,
                # fleet cold start from one topology spec: kill -9 a
                # 2-core subprocess fleet with 1k/4k/10k docs on disk,
                # restart from the spec, first-route the whole corpus.
                # Cold-boot time + time-to-first-edit per point, warm-
                # doc ack p99 during the storm vs quiet baseline, and
                # boot.part.full_replay == 0 asserted in-bench (every
                # boot is snapshot + durable tail, never whole log)
                "net_cold_storm": cold_storm,
                # weak scaling across simulated host groups (1→2→4):
                # per-host load constant, cores on RemoteTableClient
                # through the admin_table_* door, same- vs cross-host
                # ack/hop p99 split, gateway locality hit rate, /proc
                # fd-scanned file disjointness, and full_replay == 0
                # through the remote-table boot path after a host-group
                # kill -9 + respawn
                "net_multihost": multihost,
                # per-device scaling of the doc-mesh applier lane (docs
                # axis 1→2→4→8, forced host devices; full artifact in
                # MULTICHIP_r06.json). mesh_vs_local_1shard is the mesh
                # tax at one shard — the fast-lane claim needs it ≈ 1
                "multichip": multichip,
            }
        )
    )


if __name__ == "__main__":
    import sys as _sys

    if len(_sys.argv) > 1:
        # one lane by name (`python bench.py net_cold_storm`): any
        # argless bench_* runs standalone and prints its own row
        _fn = globals().get(f"bench_{_sys.argv[1]}")
        if not callable(_fn):
            _sys.exit(f"unknown bench lane: {_sys.argv[1]}")
        print(json.dumps({_sys.argv[1]: _fn()}, indent=2, default=str))
    else:
        main()
