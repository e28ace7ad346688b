"""Per-device scaling bench for the multi-chip doc mesh (ISSUE 9).

Sweeps the 'docs' mesh axis 1 → 2 → 4 → 8, as far as the devices go
(the chips present; 8 forced host devices on a ``JAX_PLATFORMS=cpu``
run), in WEAK-scaling geometry — 64 docs per shard,
K=32 ops per doc per wave — and publishes per rung: ops/s, scaling
efficiency vs the 1-shard rung, host staging cost per wave (the per-shard
wave-build + pre-partitioned transfer path), and staged bytes per wave.
The 1-shard rung is also raced against the LOCAL dense lane at the same
geometry: the mesh lane is only "the fast lane" if the mesh tax at
n_shards=1 is noise.

On this bench host every "device" is a forced host-platform virtual
device time-slicing ONE core, so ops/s cannot rise with the axis; the
artifact carries ``forced_host: true`` and the efficiency column is the
honest transfer-and-dispatch overhead curve, not an ICI scaling claim.

``--smoke`` (the ci.sh gate) skips the timing sweep and counter-asserts
the tentpole's structural claims instead:
  * per-wave staged bytes scale with ACTIVE shards, never with max_docs
    (the pre-refactor dense wave was O(max_docs) on every wave);
  * the sharded step compiles exactly once per wave shape — with the
    overlap pipeline armed;
  * ``applier.stage.overlap_ratio`` goes positive when waves pipeline
    (staging really ran while the device executed).

Artifact schema v3 (MULTICHIP_r07+) adds the overlap-staged dispatch
split::

    {"schema": 3, "platform": ..., "n_devices": 8, "forced_host": true,
     "host_limited": true, "host_limited_note": ...,
     "overlap": true, "efficiency_basis": "wall",
     "rungs": [{"docs_axis": n, "n_docs": D, "ops_per_sec": ...,
                "pipeline_ops_per_sec": ..., "scaling_efficiency": ...,
                "overlap_ratio": ..., "stage_ms_hidden": ...,
                "kernel_lane": "xla"|"pallas",
                "staging_ms_per_wave": ..., "staged_bytes_per_wave": ...},
               ...],
     "local_dense_ops_per_sec": ..., "mesh_vs_local_1shard": ...,
     "local_dense_ab": null,
     "ok": true, "rc": 0}

``local_dense_ab`` was the dense lane's overlap on/off A/B; the off
switch is gone, so new runs emit null and r07 keeps its measured arms.

``ops_per_sec`` stays wall-clock and ``scaling_efficiency`` is computed
on it (``efficiency_basis: "wall"`` — the number that cannot lie).
``pipeline_ops_per_sec`` divides by the HOST critical path instead:
un-hidden staging time plus the (async) dispatch call — the path the
overlap pipeline shrinks and the throughput predictor for a real mesh.
On forced host-platform devices every "chip" time-slices one core, so
wall throughput arithmetically cannot rise with the axis; the artifact
then carries ``host_limited: true`` with a note, and the overlap
mechanism is evidenced by per-rung ``overlap_ratio`` plus the smoke
gate's counter-asserts.

``read_multichip`` folds the committed generations: v2 (r06) rungs gain
null overlap fields; v3 (r07) passes through.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import types


#: per-rung fields added by schema v3 (null when folded from older runs)
_V3_RUNG_FIELDS = ("pipeline_ops_per_sec", "overlap_ratio",
                   "stage_ms_hidden", "kernel_lane")


def read_multichip(path: str) -> dict:
    """Load a schema v2 or v3 MULTICHIP artifact as schema v3."""
    with open(path) as f:
        raw = json.load(f)
    schema = raw.get("schema")
    if schema == 3:
        return raw
    if schema != 2:
        raise ValueError(f"{path}: MULTICHIP schema {schema!r} is not 2 or 3")
    # r06: real rungs, pre-overlap — the v3 split fields are unknown
    for r in raw.get("rungs", []):
        for f2 in _V3_RUNG_FIELDS:
            r.setdefault(f2, None)
    raw.setdefault("overlap", False)
    raw.setdefault("efficiency_basis", "wall")
    raw.setdefault("host_limited", raw.get("forced_host"))
    raw.setdefault("host_limited_note", None)
    raw.setdefault("local_dense_ab", None)
    raw["schema"] = 3
    return raw


def _msg(seq: int) -> types.SimpleNamespace:
    return types.SimpleNamespace(
        sequence_number=seq,
        reference_sequence_number=max(seq - 1, 0),
        minimum_sequence_number=max(seq - 4, 0),
        client_id="bench",
    )


_INS = {"type": 0, "pos": 0, "text": "x"}
_REM = {"type": 1, "start": 0, "end": 1}


def _stage_wave(applier, docs, seqs, k: int) -> int:
    """Stage k ops per doc (insert/remove pairs at the head, so live
    segments stay flat and zamboni has work every wave). Returns the op
    count staged."""
    for d in docs:
        for _ in range(k // 2):
            seqs[d] += 1
            applier.ingest("t", d, _msg(seqs[d]), _INS)
            seqs[d] += 1
            applier.ingest("t", d, _msg(seqs[d]), _REM)
    return len(docs) * (k // 2) * 2


def _fence(applier) -> None:
    import numpy as np

    np.asarray(applier.state.count)


def _time_applier(applier, docs, k: int, warmup: int = 2,
                  timed: int = 8) -> dict:
    """Ops/s over `timed` PIPELINED waves (ingest excluded: the bench
    isolates the stage → transfer → dispatch lane). All timed waves are
    pre-ingested and ONE flush drains them, so wave i+1 stages on the
    host while wave i executes on device — the overlap lane this bench
    exists to measure. (The pre-overlap bench fenced after every wave,
    serializing exactly the path under test.) Works for both lanes: the
    stage/execute split counters are fed by dense and mesh alike."""
    seqs = {d: 0 for d in docs}
    for _ in range(warmup):
        _stage_wave(applier, docs, seqs, k)
        applier.flush()
    _fence(applier)
    stage_s0 = applier.stage_seconds
    hidden_s0 = applier.stage_overlap_seconds
    bytes0 = applier.stage_bytes
    waves0 = applier.waves_staged
    exec_s0 = applier.dispatch_seconds
    total_ops = 0
    for _ in range(timed):
        total_ops += _stage_wave(applier, docs, seqs, k)
    t0 = time.perf_counter()
    applier.flush()
    _fence(applier)
    elapsed = time.perf_counter() - t0
    stage_s = applier.stage_seconds - stage_s0
    hidden_s = applier.stage_overlap_seconds - hidden_s0
    exec_s = applier.dispatch_seconds - exec_s0
    waves = applier.waves_staged - waves0
    # the HOST critical path per wave: staging not hidden behind device
    # execution, plus the (async) dispatch call. On a real mesh this
    # path bounds throughput once per-device compute is constant (weak
    # scaling); on forced-host devices one core also runs all the
    # "device" compute, so wall time cannot scale and this is the
    # honest predictor the overlap work moves.
    host_path_s = (stage_s - hidden_s) + exec_s
    return {
        "ops_per_sec": round(total_ops / elapsed, 1),
        "pipeline_ops_per_sec": (round(total_ops / host_path_s, 1)
                                 if host_path_s > 0 else None),
        "staging_ms_per_wave": (round(stage_s / waves * 1e3, 4)
                                if waves else None),
        "stage_ms_hidden": (round(hidden_s / waves * 1e3, 4)
                            if waves else None),
        "overlap_ratio": round(hidden_s / stage_s, 3) if stage_s else None,
        "staged_bytes_per_wave": ((applier.stage_bytes - bytes0) // waves
                                  if waves else None),
        "kernel_lane": applier.kernel_lane,
    }


DOCS_PER_SHARD = 64
K = 32


def run_sweep() -> dict:
    import jax

    from fluidframework_tpu.parallel.mesh import make_mesh
    from fluidframework_tpu.service.tpu_applier import TpuDocumentApplier

    forced_host = jax.devices()[0].platform == "cpu"
    axes = [n for n in (1, 2, 4, 8) if n <= len(jax.devices())]
    rungs = []
    for n in axes:
        D = DOCS_PER_SHARD * n
        applier = TpuDocumentApplier(
            max_docs=D, max_slots=64, ops_per_dispatch=K,
            mesh=make_mesh(n, seg_shards=1))
        docs = [f"d{i}" for i in range(D)]
        r = _time_applier(applier, docs, K)
        rungs.append({"docs_axis": n, "n_docs": D, **r})
    # weak-scaling efficiency vs the 1-shard rung, on WALL throughput —
    # the number that cannot lie. pipeline_ops_per_sec per rung shows
    # the host critical path the overlap pipeline shrinks; on forced
    # host devices it goes near-free at rungs the runtime can keep two
    # waves in flight, which would flatter the efficiency column, so it
    # stays informational and the artifact is annotated host_limited.
    base = rungs[0]["ops_per_sec"]
    for r in rungs:
        r["scaling_efficiency"] = round(
            r["ops_per_sec"] / (r["docs_axis"] * base), 3)

    # the mesh tax at n_shards=1: same geometry down the local dense lane
    local = TpuDocumentApplier(max_docs=DOCS_PER_SHARD, max_slots=64,
                               ops_per_dispatch=K)
    docs1 = [f"d{i}" for i in range(DOCS_PER_SHARD)]
    local_opsps = _time_applier(local, docs1, K)["ops_per_sec"]

    host_limited_note = (
        "forced host-platform devices time-slice one core: wall "
        "throughput cannot rise with the docs axis, and the CPU runtime "
        "intermittently serializes multi-wave dispatch at the 8-device "
        "rung (overlap_ratio collapses there). The overlap mechanism is "
        "proven by the lower rungs' overlap_ratio and the --smoke gate."
        if forced_host else None)

    return {
        "schema": 3,
        "platform": jax.devices()[0].platform,
        "n_devices": len(jax.devices()),
        "forced_host": forced_host,
        "host_limited": forced_host,
        "host_limited_note": host_limited_note,
        "overlap": True,
        "efficiency_basis": "wall",
        "rungs": rungs,
        "local_dense_ops_per_sec": local_opsps,
        "mesh_vs_local_1shard": round(rungs[0]["ops_per_sec"] / local_opsps,
                                      3),
        "local_dense_ab": None,
        "ok": True,
        "rc": 0,
    }


def run_smoke() -> None:
    """The ci.sh gate: structural counter-asserts, no timing."""
    from fluidframework_tpu.ops.apply import OP_FIELDS
    from fluidframework_tpu.parallel.mesh import make_mesh
    from fluidframework_tpu.service.tpu_applier import TpuDocumentApplier

    import jax

    D, n_shards, k = 64, min(8, len(jax.devices())), 8
    applier = TpuDocumentApplier(max_docs=D, max_slots=32,
                                 ops_per_dispatch=k,
                                 mesh=make_mesh(n_shards, seg_shards=1))
    sps = applier.placement.slots_per_shard
    per_shard = sps * k * OP_FIELDS * 2 + sps * 2 * 4  # int16 wave + bases
    dense = D * k * OP_FIELDS * 2 + D * 2 * 4          # the old O(max_docs)

    # one compile per wave shape, measured as growth: the packed step is
    # cached per mesh across applier instances, so an absolute count
    # would see shapes compiled by other users of the same mesh
    packed_fn, wide_fn = applier._sharded_step
    cache0 = packed_fn._cache_size()
    wide0 = wide_fn._cache_size()

    # one active doc → exactly one shard's buffers staged per wave
    seqs = {"d0": 0}
    for _ in range(10):
        _stage_wave(applier, ["d0"], seqs, k)
        applier.flush()
    assert applier.mesh_waves == 10, applier.mesh_waves
    assert applier.mesh_active_shards == 10, applier.mesh_active_shards
    b1 = applier.mesh_staged_bytes // applier.mesh_waves
    assert b1 == per_shard, (b1, per_shard)
    assert b1 * n_shards <= dense, (b1, dense)

    # all shards active → bytes scale with ACTIVE shards (8×), still not
    # with max_docs
    docs = [f"d{i}" for i in range(D)]
    seqs = {d: seqs.get(d, 0) for d in docs}
    w0, by0 = applier.mesh_waves, applier.mesh_staged_bytes
    for _ in range(10):
        _stage_wave(applier, docs, seqs, k)
        applier.flush()
    waves = applier.mesh_waves - w0
    b8 = (applier.mesh_staged_bytes - by0) // waves
    assert b8 == n_shards * per_shard, (b8, n_shards * per_shard)

    # overlap: pipeline 10 pre-ingested waves through ONE flush, so the
    # staging of wave i+1 runs while wave i executes. Both the instance
    # counter and the exported gauge must go positive — staging really
    # overlapped device execution, with overlap armed by default.
    for _ in range(10):
        _stage_wave(applier, docs, seqs, k)
    applier.flush()
    _fence(applier)
    ratio = applier.stage_overlap_ratio()
    assert ratio > 0, f"overlap_ratio {ratio} with pipelined waves"
    from fluidframework_tpu.obs import get_registry, parse_prometheus

    scraped = parse_prometheus(get_registry().scrape())
    gauge = scraped.get("fluid_applier_stage_overlap_ratio", {})
    assert gauge and max(gauge.values()) > 0, gauge

    # 30 same-shape waves → exactly one new compile on the packed step,
    # none on the wide lane (it never ran) — including across the
    # pipelined overlap phase above
    assert packed_fn._cache_size() - cache0 <= 1, (cache0,
                                                   packed_fn._cache_size())
    assert wide_fn._cache_size() == wide0, (wide0, wide_fn._cache_size())
    import numpy as np

    assert not np.asarray(applier.state.overflow).any()
    print("bench_multichip --smoke: ok "
          f"(per-wave bytes {b1} x active shards, dense was {dense}; "
          f"overlap_ratio {ratio:.3f} with pipelined waves)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--devices", type=int, default=None,
                    help="devices to require (default: 8 forced host "
                         "devices on a JAX_PLATFORMS=cpu run, else every "
                         "device present)")
    ap.add_argument("--smoke", action="store_true",
                    help="structural counter-asserts only (ci.sh gate)")
    ap.add_argument("--out", default=None,
                    help="also write the artifact JSON to this path")
    args = ap.parse_args(argv)
    import jax

    from fluidframework_tpu.parallel.mesh import require_devices

    if args.devices is None and jax.config.jax_platforms == "cpu":
        args.devices = 8
    if args.devices is not None:
        require_devices(args.devices)
    if args.smoke:
        run_smoke()
        return 0
    result = run_sweep()
    line = json.dumps(result)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(json.dumps(result, indent=1) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
