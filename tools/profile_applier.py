"""Split the applier feed into its cost components (VERDICT r4 #5).

Measures, on the chip (it refuses to run elsewhere unless
``JAX_PLATFORMS=cpu`` asks for the CPU):

  pack     — host-side wave assembly (_dispatch_wave's numpy work)
  h2d      — device_put of the packed wave, blocked to completion
  step     — the jitted dense step with the wave already on device
  e2e      — the production dispatch path end to end

and prints the implied bytes/op, link bandwidth, and the ceiling
``bandwidth / bytes_per_op`` that bounds the service-path ops/s on this
rig. Usage:  python tools/profile_applier.py [--docs D] [--k K]

The applier also accounts its own halves per lane —
``applier.stage.seconds`` (host wave assembly + transfer, with the
hidden-behind-execute fraction) vs ``applier.dispatch.seconds`` (the
step's enqueue, not its device time) — so a throughput swing is
attributable from its counters: a stage swing is host/link time, a
dispatch swing is the enqueue or a kernel change. This profile prints
that split below (``stage/execute split``) for the dense lane and, when
the machine has several devices, the mesh lane; the manual
pack/h2d/step rows remain the finer microscope.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def _reset_split(applier) -> None:
    applier.stage_seconds = applier.stage_overlap_seconds = 0.0
    applier.dispatch_seconds = 0.0
    applier.stage_bytes = 0
    applier.waves_staged = 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--docs", type=int, default=1024)
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--trials", type=int, default=20)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from fluidframework_tpu.ops.apply import OP_FIELDS, OP_INSERT, make_op
    from fluidframework_tpu.service.tpu_applier import TpuDocumentApplier
    from fluidframework_tpu.utils.chip import init_compile_cache, require_tpu

    init_compile_cache()
    dev = require_tpu()
    D, K, T = args.docs, args.k, args.trials
    print(f"device: {dev.platform} {getattr(dev, 'device_kind', '?')}")

    app = TpuDocumentApplier(max_docs=D, ops_per_dispatch=K)
    # register docs + seed a little text so applies do real work
    for d in range(D):
        app.slot_of("t", f"doc{d}")

    # ---- a full synthetic wave: every doc x K insert rows ----
    def stage_full_wave(seq0: int) -> None:
        for d in range(D):
            rows = np.zeros((K, OP_FIELDS), np.int32)
            for i in range(K):
                rows[i] = make_op(OP_INSERT, pos=0, seq=seq0 + i,
                                  ref_seq=seq0 + i - 1, client=0,
                                  text_len=1, text_start=seq0 + i,
                                  msn=seq0 + i - 1)
            app._push_chunk(d, rows)

    # warm: compile both lanes, then zero the split counters so the
    # stage/execute rows below report steady-state waves, not the
    # compile wave
    stage_full_wave(2)
    app.flush()
    app._sync(0)
    _reset_split(app)

    n_ops = D * K

    # ---- e2e: the production dispatch path ----
    t0 = time.perf_counter()
    for t in range(T):
        stage_full_wave(2 + (t + 1) * K)
        app.flush()
    jax.block_until_ready(app.state.length)
    e2e = (time.perf_counter() - t0) / T

    # ---- pack only: _dispatch_wave minus the device calls ----
    # re-measure by timing the numpy assembly on a staged wave
    stage_full_wave(2 + (T + 1) * K)
    parts = app._take_wave()
    all_chunks, slots, lens = [], [], []
    for slot, chunks, count in parts:
        if count:
            all_chunks.extend(chunks)
            slots.append(slot)
            lens.append(count)
    t0 = time.perf_counter()
    for _ in range(T):
        flat = (all_chunks[0] if len(all_chunks) == 1
                else np.concatenate(all_chunks))
        lens_a = np.array(lens)
        starts = np.cumsum(lens_a) - lens_a
        slots_a = np.array(slots, np.int64)
        doc_idx = np.repeat(slots_a, lens_a)
        pos_idx = (np.arange(len(flat), dtype=np.int64)
                   - np.repeat(starts, lens_a))
        wave16 = np.zeros((D, K, OP_FIELDS), np.int16)
        wave16[doc_idx, pos_idx] = flat.astype(np.int16)
    pack = (time.perf_counter() - t0) / T

    # ---- h2d: ship that wave, blocked ----
    t0 = time.perf_counter()
    for _ in range(T):
        jax.block_until_ready(jax.device_put(wave16))
    h2d = (time.perf_counter() - t0) / T
    wave_bytes = wave16.nbytes + D * 2 * 4  # + bases

    # ---- step: wave already on device ----
    wave_dev = jax.block_until_ready(jax.device_put(wave16))
    bases = np.zeros((D, 2), np.int32)
    bases[:, 0] = 2
    bases_dev = jax.block_until_ready(jax.device_put(bases))
    packed_fn, _ = app._dense_step
    state = app.state
    t0 = time.perf_counter()
    for _ in range(T):
        state, _aux = packed_fn(state, wave_dev, bases_dev)
    jax.block_until_ready(state.length)
    step = (time.perf_counter() - t0) / T
    app.state = state

    bw = wave_bytes / h2d
    bpo = wave_bytes / n_ops
    print(f"wave: {D} docs x {K} ops = {n_ops} ops, {wave_bytes} B "
          f"({bpo:.1f} B/op)")
    print(f"pack : {pack*1e3:8.2f} ms  ({n_ops/pack:10.0f} ops/s if alone)")
    print(f"h2d  : {h2d*1e3:8.2f} ms  ({n_ops/h2d:10.0f} ops/s if alone) "
          f"-> link {bw/1e6:.1f} MB/s")
    print(f"step : {step*1e3:8.2f} ms  ({n_ops/step:10.0f} ops/s if alone)")
    print(f"e2e  : {e2e*1e3:8.2f} ms  ({n_ops/e2e:10.0f} ops/s)")
    print(f"ceiling at this link = bw/bytes_per_op = "
          f"{bw/bpo:,.0f} ops/s")

    # ---- stage/execute split: the applier's own per-lane accounting ----
    # (the production path's first-class attribution — see docstring)
    def split_row(lane: str, a) -> None:
        waves = a.waves_staged
        if not waves:
            return
        stage_ms = a.stage_seconds / waves * 1e3
        exec_ms = a.dispatch_seconds / waves * 1e3
        print(f"  {lane:5s}: stage {stage_ms:7.2f} ms/wave "
              f"({a.stage_overlap_ratio()*100:5.1f}% hidden behind "
              f"execute), exec-call {exec_ms:7.2f} ms/wave, "
              f"kernel={a.kernel_lane}")

    print("stage/execute split:")
    split_row("dense", app)
    if len(jax.devices()) > 1:
        from fluidframework_tpu.parallel.mesh import make_mesh

        n_sh = len(jax.devices())
        mesh_app = TpuDocumentApplier(
            max_docs=D, ops_per_dispatch=K,
            mesh=make_mesh(n_sh, seg_shards=1))
        for d in range(D):
            mesh_app.slot_of("t", f"doc{d}")
        warmed = False
        for t in range(T):
            for d in range(D):
                rows = np.zeros((K, OP_FIELDS), np.int32)
                seq0 = 2 + t * K
                for i in range(K):
                    rows[i] = make_op(OP_INSERT, pos=0, seq=seq0 + i,
                                      ref_seq=seq0 + i - 1, client=0,
                                      text_len=1, text_start=seq0 + i,
                                      msn=seq0 + i - 1)
                mesh_app._push_chunk(d, rows)
            if not warmed:
                # first wave compiles; keep it out of the split rows
                mesh_app.flush()
                jax.block_until_ready(mesh_app.state.length)
                _reset_split(mesh_app)
                warmed = True
        mesh_app.flush()
        jax.block_until_ready(mesh_app.state.length)
        split_row("mesh", mesh_app)

    # ---- recompiles: which kernels traced, how many times ----
    # a kernel-number swing between runs (the r4→r5 note above) is only
    # attributable if the recompile count is in the artifact: a second
    # trace of the same kernel means the run paid compile time mid-trial
    from fluidframework_tpu.obs import get_registry, parse_prometheus

    series = parse_prometheus(get_registry().scrape())
    recompiles = series.get("fluid_applier_kernel_recompiled", {})
    print("recompiles:")
    for key in sorted(recompiles):
        labels = dict(key)
        print(f"  {labels.get('kernel', '?'):16s} "
              f"shape {labels.get('shape', '?'):12s} "
              f"x{recompiles[key]:g}")
    if not recompiles:
        print("  (none recorded — kernels served from the jit cache)")


if __name__ == "__main__":
    main()
