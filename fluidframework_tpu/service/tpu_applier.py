"""TpuDocumentApplier: batched server-side merge-tree replica farm.

THE TPU-differentiating service component. The reference's server keeps no
document state (architecture.md: server sequences, clients merge) and pays
for it when it needs content — scribe replays whole op logs in JS to build
service summaries (scribe writeServiceSummary, SURVEY §3.4). Here the
service maintains thousands of documents as ONE device-resident
structure-of-arrays batch (ops/doc_state.DocState with a leading doc dim)
and applies every sequenced merge-tree op as a vmapped tensor program
(ops/apply.py), optionally sharded over a ('docs','seg') mesh
(parallel/sharded_apply.py). That turns BASELINE config 5 (10k-doc scribe
replay) into a handful of XLA dispatches.

Semantics guardrails:
- Ops ingest ONLY from the sequenced stream, so the server-side invariants
  hold (every stamp below the incoming seq; tie-break = earliest
  boundary — see ops/apply.py docstring).
- Insert/remove/annotate all stay on the device. Anything the kernel does
  not model (slot-capacity, remove-overlap, or property-table overflow)
  flips the doc to HOST mode: the scalar oracle (mergetree/) replays the
  doc's authoritative op log from scriptorium. This is the
  overflow-to-host escape hatch of SURVEY §7(e).
- Every staged op carries the msn deli stamped on its sequenced message,
  so device zamboni (ops/apply.compact) runs fused after every wave at
  the exact collaboration-window floor — slot usage stays bounded under
  churn instead of growing until escalation.
- SharedMatrix grids ride beside the text docs (the grids section below):
  each grid's row and column vectors are two farm rows of their own (a
  doc may hold text beside its grid), and its cells an [H, H] plane of a
  device store that ops/matrix_cells.py writes. Handles are never reused
  within H, unlike upstream's handle recycling (packages/dds/matrix
  handletable.ts): a removed row's or column's cells become unreachable
  by position, and a grid whose inserts would pass H escalates to a host
  SharedMatrix (dds/matrix.py).
"""

from __future__ import annotations

import json
import os
import queue
import struct
import threading
import time
import warnings
from dataclasses import replace
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..dds.matrix import HANDLE_BASE, SharedMatrix
from ..mergetree.client import MergeTreeClient
from ..ops.apply import (
    F_CLIENT,
    F_END,
    F_KEY,
    F_MSN,
    F_POS,
    F_REFSEQ,
    F_SEQ,
    F_TLEN,
    F_TSTART,
    F_TYPE,
    F_VAL,
    NO_CLIENT,
    NO_VAL,
    OP_ANNOTATE,
    OP_FIELDS,
    OP_INSERT,
    OP_REMOVE,
    SYSTEM_CLIENT,
    apply_ops_batch,
    compact_batch,
    pack_wave_rows,
    unpack_wave16,
    wave_min_seq,
)
from ..ops.matrix_cells import CELL_FIELDS, matrix_cells, written_cells
from ..ops.doc_state import (FLAG_MARKER, NO_KEY, NO_SEQ, DocState, PropTable,
                             TextArena, decode_state)
from ..protocol.messages import MessageType, SequencedDocumentMessage
from ..protocol.refgraph import frame_record, scan_records
from ..parallel.placement import DocPlacement
from ..utils.contracts import register_kernel_contract
from ..utils.affinity import blocking
from ..utils.telemetry import span
from .synthetic import GRID_CHANNEL

MARKER_GLYPH = "￼"  # arena placeholder byte for markers (flags classify)
_INT16_MAX = 32767

#: a grid's row and column vectors are placed under its doc id plus
#: these (a doc id holds no NUL), beside the doc's own text row
ROWS_SUFFIX = "\x00rows"
COLS_SUFFIX = "\x00cols"
#: SharedMatrix vector op -> (vector: 0 rows, 1 cols; is an insert)
_VECTOR_OPS = {"insertRows": (0, True), "insertCols": (1, True),
               "removeRows": (0, False), "removeCols": (1, False)}

# SYSTEM_CLIENT / PACK_SYSTEM and the int16-delta wire format now live in
# ops/apply.py (shared with the mesh lane's packed sharded step)

# jitted dense steps shared across applier instances, keyed (D, K):
# per-instance closures would each re-trace/re-compile every shape bucket
_DENSE_STEP_CACHE: dict = {}

# process-wide small thread pool for per-shard staging jobs: the numpy
# fancy-index scatter and device_put both release the GIL, so active
# shards stage concurrently on multi-core hosts (shared across applier
# instances — worker threads are lazy and cheap, lifecycles are not)
_STAGE_POOL = None


def _stage_executor():
    global _STAGE_POOL
    if _STAGE_POOL is None:
        from concurrent.futures import ThreadPoolExecutor

        _STAGE_POOL = ThreadPoolExecutor(
            max_workers=4, thread_name_prefix="applier-stage")
    return _STAGE_POOL


class _StagedWave:
    """The output of the stage half of a dispatch: device-resident input
    buffers plus what the execute half needs to run and account for the
    wave. Holding one of these means the wave's ops have LEFT the staged
    dict but have not yet been issued to the device."""

    __slots__ = ("lane", "wide", "arrays", "n", "nbytes", "flip")

    def __init__(self, lane: str, wide: bool, arrays: tuple, n: int,
                 nbytes: int):
        self.lane = lane        # "dense" | "tile" | "mesh" (metrics label)
        self.wide = wide        # int32 escape lane (range / force_wide)
        self.arrays = arrays    # device arrays, step-call order
        self.n = n              # op rows in the wave
        self.nbytes = nbytes    # host bytes staged
        self.flip = 0           # which staging-buffer set holds the wave


def _resolve_kernel(kernel, cfg, tile_docs: int) -> bool:
    """Resolve the applier's contract kernel (``kernel``, else config
    ``applier_kernel``) to use_pallas. ``auto`` selects Pallas only on
    real TPU devices AND when the doc geometry tiles (R=8 docs per grid
    instance); a forced ``pallas`` raises on bad geometry, while ``auto``
    falls back to the XLA scan with a warning."""
    kernel = kernel if kernel is not None else cfg.applier_kernel
    if kernel not in ("auto", "pallas", "xla"):
        raise ValueError(
            f"applier_kernel={kernel!r}: expected auto|pallas|xla")
    if kernel == "auto":
        if jax.default_backend() != "tpu":
            return False
        if tile_docs % 8:
            warnings.warn(
                f"applier_kernel=auto on a TPU picks the XLA scan: "
                f"docs-per-shard {tile_docs} is not a multiple of 8, "
                "so the Pallas kernel cannot tile it", stacklevel=3)
            return False
        return True
    if kernel == "pallas" and tile_docs % 8:
        raise ValueError(
            "applier_kernel=pallas requires docs-per-shard % 8 == 0 "
            f"(got {tile_docs})")
    return kernel == "pallas"


def _count_trace(kernel: str, shape: str) -> None:
    """Runs at TRACE time only (a Python side effect inside a jitted
    body): each call is one XLA recompile of ``kernel`` for a new shape
    bucket. The registry series makes kernel-count swings between runs
    attributable (tools/profile_applier.py prints the breakdown)."""
    from ..obs import get_registry

    get_registry().inc("applier.kernel.recompiled",
                       kernel=kernel, shape=shape)


def _farm_apply(use_pallas: bool, pallas_interpret: bool):
    """The farm's batched apply: the Pallas kernel (tiles of R=8 rows)
    or the XLA scan."""
    if not use_pallas:
        return apply_ops_batch
    from ..ops.pallas_apply import pallas_apply_ops_batch

    return partial(pallas_apply_ops_batch, interpret=pallas_interpret)


def _dense_step_for(D: int, K: int, use_pallas: bool = False,
                    pallas_interpret: bool = False):
    """The wave arrives PACKED from the host: int16[D, K, F] deltas plus
    int32[D, 2] per-doc bases (seq, text_start), unpacked to the kernel's
    int32 field layout on device with elementwise math (the shared wire
    format — rationale and codec in ops/apply.py's packed-wave section).

    Why this shape: the host↔device link is the op path's bottleneck, so
    bytes-per-op is the number to minimize, and halving the padded wave
    to int16 does that. The host checks the delta ranges and falls back
    to the int32 wave when any field escapes (huge docs, giant windows).
    This step covers all D rows; a wave that occupies few of them takes
    the tile step instead (``_tile_step_for``), so the full step is left
    to waves that touch much of the farm: set-up, replay, catch-up.
    """
    fn = _DENSE_STEP_CACHE.get((D, K, use_pallas, pallas_interpret))
    if fn is None:
        apply_fn = _farm_apply(use_pallas, pallas_interpret)

        def dense_step(state, wave16, bases):
            _count_trace("dense_step", f"{D}x{K}")
            wave = unpack_wave16(wave16, bases)
            state = apply_fn(state, wave)
            return compact_batch(state, wave_min_seq(wave)), {}

        def dense_step_wide(state, wave):
            _count_trace("dense_step_wide", f"{D}x{K}")
            state = apply_fn(state, wave)
            return compact_batch(state, wave_min_seq(wave)), {}

        from ..parallel.sharded_apply import donation_supported

        # donation gated by backend: the CPU client runs donating
        # computations synchronously, which would serialize the
        # stage/execute overlap pipeline (see donation_supported)
        don = (0,) if donation_supported() else ()
        fn = (jax.jit(dense_step, donate_argnums=don),
              jax.jit(dense_step_wide, donate_argnums=don))
        _DENSE_STEP_CACHE[(D, K, use_pallas, pallas_interpret)] = fn
    return fn


def _tile_sizes(D: int) -> tuple:
    """The row counts of the tile step for a farm of D rows: about D/16
    and D/4, each rounded up to a multiple of 8 (the Pallas kernel's R),
    at least 8 and at most D/2. None where D is below 16."""
    sizes = []
    for frac in (16, 4):
        t = max(8, (-(-D // frac) + 7) // 8 * 8)
        if t <= D // 2 and t not in sizes:
            sizes.append(t)
    return tuple(sizes)


def _tile_rows(slots: np.ndarray, T: int) -> np.ndarray:
    """A tile's farm rows, int32 [T], sorted: the wave's ``slots``
    (distinct, at most T) and, as padding, the lowest rows it does not
    occupy. Of the first T + n rows at most n are occupied, so they hold
    the T - n padding rows."""
    free = np.ones(T + len(slots), bool)
    free[slots[slots < len(free)]] = False
    pad = np.flatnonzero(free)[:T - len(slots)]
    return np.sort(np.concatenate([slots, pad])).astype(np.int32)


def _tile_step_for(K: int, use_pallas: bool = False,
                   pallas_interpret: bool = False):
    """The packed step on a tile of T farm rows: gather the rows' state,
    unpack the int16 [T, K, F] wave and its [T, 2] bases as the full step
    does, apply, zamboni, and scatter the rows back into the donated
    farm. A row the wave does not occupy comes out of the full step
    bit-identical (NOOP ops; its zamboni floor lies below every remove
    seq), so stepping the occupied rows, padded to T with unoccupied
    ones, gives the same farm for T rows of work instead of D. One
    compile per (D, T, K)."""
    key = ("tile", K, use_pallas, pallas_interpret)
    fn = _DENSE_STEP_CACHE.get(key)
    if fn is None:
        apply_fn = _farm_apply(use_pallas, pallas_interpret)
        at = dict(indices_are_sorted=True, unique_indices=True,
                  mode="promise_in_bounds")

        def tile_step(state, rows, wave16, bases):
            _count_trace("tile_step", f"{rows.shape[0]}x{K}")
            tile = jax.tree.map(lambda a: a.at[rows].get(**at), state)
            wave = unpack_wave16(wave16, bases)
            tile = compact_batch(apply_fn(tile, wave), wave_min_seq(wave))
            return jax.tree.map(lambda a, t: a.at[rows].set(t, **at),
                                state, tile), {}

        from ..parallel.sharded_apply import donation_supported

        fn = jax.jit(tile_step,
                     donate_argnums=(0,) if donation_supported() else ())
        _DENSE_STEP_CACHE[key] = fn
    return fn


def _long_step_for(L: int, K: int, use_pallas: bool = False,
                   pallas_interpret: bool = False):
    """The long tier's step: an int32 [L, K, F] wave (positions in a
    book-length doc pass int16 at character 32,768, and the tier's wave
    is small enough that packing would buy nothing), the apply at the
    tier's kernel layout, and the fused zamboni."""
    key = ("long", L, K, use_pallas, pallas_interpret)
    fn = _DENSE_STEP_CACHE.get(key)
    if fn is None:
        if use_pallas:
            from ..ops.pallas_apply import pallas_apply_ops_batch_long

            def apply_fn(state, wave):
                return pallas_apply_ops_batch_long(
                    state, wave, interpret=pallas_interpret)
        else:
            apply_fn = apply_ops_batch

        def long_step(state, wave):
            _count_trace("long_step", f"{L}x{K}")
            state = apply_fn(state, wave)
            return compact_batch(state, wave_min_seq(wave))

        from ..parallel.sharded_apply import donation_supported

        fn = jax.jit(long_step,
                     donate_argnums=(0,) if donation_supported() else ())
        _DENSE_STEP_CACHE[key] = fn
    return fn


def _promote_rows(farm: DocState, long: DocState, slot, row):
    """Move farm row ``slot`` into long row ``row``, padded with empty
    slots to the tier's width, and leave the farm row empty. Both
    indices are traced: one compile per pair of geometries."""
    S, SL = farm.max_slots, long.max_slots
    empty, empty_long = DocState.empty(S), DocState.empty(SL)
    moved = {}
    cleared = {}
    for f in _STATE_FIELDS[:-2]:  # all but count and overflow
        src = getattr(farm, f)[slot]
        pad = getattr(empty_long, f)[S:]
        moved[f] = getattr(long, f).at[row].set(
            jnp.concatenate([src, pad], axis=0))
        cleared[f] = getattr(farm, f).at[slot].set(getattr(empty, f))
    return (DocState(**cleared, count=farm.count.at[slot].set(0),
                     overflow=farm.overflow.at[slot].set(False)),
            DocState(**moved, count=long.count.at[row].set(farm.count[slot]),
                     overflow=long.overflow.at[row].set(
                         farm.overflow[slot])))


_PROMOTE_FN = None


def _promote_fn():
    global _PROMOTE_FN
    if _PROMOTE_FN is None:
        from ..parallel.sharded_apply import donation_supported

        _PROMOTE_FN = jax.jit(
            _promote_rows,
            donate_argnums=(0, 1) if donation_supported() else ())
    return _PROMOTE_FN


_CELLS_FN = None
_WRITTEN_FN = None

#: cells a grid a base reads back from the grid store in one dispatch
GRID_PAGE = 4096


def _cells_fn():
    """``ops/matrix_cells.matrix_cells`` jitted under its own name, the
    store and its marks donated where the backend supports donation."""
    global _CELLS_FN
    if _CELLS_FN is None:
        from ..parallel.sharded_apply import donation_supported

        _CELLS_FN = jax.jit(
            matrix_cells,
            donate_argnums=(0, 1) if donation_supported() else ())
    return _CELLS_FN


def _written_fn():
    global _WRITTEN_FN
    if _WRITTEN_FN is None:
        _WRITTEN_FN = jax.jit(partial(written_cells, page=GRID_PAGE))
    return _WRITTEN_FN


def _contract_build():
    """The int16 packed wave applier at a small fixed geometry."""
    D, K = 8, 4
    packed_fn, _wide_fn = _dense_step_for(D, K)

    def example():
        S = 16
        state = jax.vmap(lambda _: DocState.empty(S))(jnp.arange(D))
        wave16 = jnp.zeros((D, K, OP_FIELDS), jnp.int16)
        bases = jnp.zeros((D, 2), jnp.int32)
        return (state, wave16, bases), {}

    return packed_fn, example


# contract: the wave arrives int16 and must be EXPLICITLY widened before
# any arithmetic (no_int16_arithmetic catches silent promotion); the
# unpack+apply is gather-free, the fused zamboni repack owns the only
# gathers (one per DocState field, once per wave, off the K-amplified
# path); one compile per (D, K) geometry.
register_kernel_contract(
    "service.dense_step_packed",
    build=_contract_build,
    no_scatter=True,
    max_gathers=10,
    no_int16_arithmetic=True,
    single_jit=True,
    notes="int16 packed-wave unpack + batched apply + fused zamboni",
)


def _contract_build_pallas():
    """The same packed-wave applier with kernel=pallas selected
    (interpret mode so the contract checks run on any backend — the
    traced program is identical to the Mosaic-lowered one)."""
    D, K = 8, 4
    packed_fn, _wide_fn = _dense_step_for(D, K, use_pallas=True,
                                          pallas_interpret=True)

    def example():
        S = 16
        state = jax.vmap(lambda _: DocState.empty(S))(jnp.arange(D))
        wave16 = jnp.zeros((D, K, OP_FIELDS), jnp.int16)
        bases = jnp.zeros((D, 2), jnp.int32)
        return (state, wave16, bases), {}

    return packed_fn, example


# contract: the default-on Pallas lane must honor the SAME wire-format
# invariants as the XLA lane — the checker walks INTO the pallas_call
# jaxpr, so a scatter or int16 promotion smuggled into the Mosaic body
# fails identically; zamboni's once-per-wave repack owns the only gathers
register_kernel_contract(
    "service.dense_step_packed_pallas",
    build=_contract_build_pallas,
    no_scatter=True,
    max_gathers=10,
    no_int16_arithmetic=True,
    single_jit=True,
    notes="int16 packed wave through the Pallas VMEM apply lane "
          "(applier.kernel=pallas selection of the dense step)",
)


def _contract_build_tile(use_pallas: bool = False):
    """The tile step at a small fixed geometry: 8 rows of a 16-row farm
    (on the Pallas lane in interpret mode, as the dense twin's)."""
    D, T, K = 16, 8, 4
    fn = _tile_step_for(K, use_pallas=use_pallas,
                        pallas_interpret=use_pallas)

    def example():
        S = 16
        state = jax.vmap(lambda _: DocState.empty(S))(jnp.arange(D))
        rows = jnp.arange(1, D, 2, dtype=jnp.int32)
        wave16 = jnp.zeros((T, K, OP_FIELDS), jnp.int16)
        bases = jnp.zeros((T, 2), jnp.int32)
        return (state, rows, wave16, bases), {}

    return fn, example


# contract: the tile step is the packed step on T gathered rows, under
# the same wire rules; its gathers are zamboni's repack (10) plus one
# row gather per DocState field (12), its scatters one row scatter per
# field back into the farm (12), all once per wave
for _name, _pallas in (("service.tile_step_packed", False),
                       ("service.tile_step_packed_pallas", True)):
    register_kernel_contract(
        _name,
        build=partial(_contract_build_tile, use_pallas=_pallas),
        max_gathers=22,
        max_scatters=12,
        no_int16_arithmetic=True,
        single_jit=True,
        notes="int16 packed wave on a gathered tile of farm rows: row "
              "gather + apply + fused zamboni + row scatter-back",
    )


def channel_stream(server, tenant_id: str, document_id: str,
                   ds_id: str, channel_id: str, from_seq: int = 0):
    """Extract one channel's merge-tree messages from the document's
    sequenced op log (scriptorium) — the applier's replay source and the
    scribe-replay entry point (BASELINE config 5).

    Truncated logs raise (scriptorium.LogTruncatedError): with log
    retention active, a from-zero replay would silently rebuild WRONG
    state once ops behind an acked summary have been dropped — such
    deployments must give the applier a summary-aware replay source.
    Reads go straight through a stateless ScriptoriumLambda over the db
    so inspecting a doc never lazily constructs its whole pipeline."""
    from .scriptorium import ScriptoriumLambda

    for m in ScriptoriumLambda(server.db).get_deltas(
            tenant_id, document_id, from_seq, 10**9):
        if m.type != MessageType.OPERATION:
            continue
        env = m.contents
        if not isinstance(env, dict) or env.get("kind") != "chanop":
            continue
        if env["address"] != ds_id:
            continue
        inner = env["contents"]
        if inner.get("address") != channel_id or "attach" in inner:
            continue
        yield replace(m, contents=inner["contents"])


class TpuDocumentApplier:
    """Maintains [D, S] device doc states fed by sequenced op streams."""

    #: chaos seam (fluidframework_tpu/chaos): forced device escalations —
    #: the int32 wide dispatch path and the overflow-to-host flip — so the
    #: rare lanes run under the soak, not only when a doc organically
    #: exceeds int16 / device capacity. None = disarmed, one branch.
    fault_plane = None

    def __init__(
        self,
        max_docs: Optional[int] = None,
        max_slots: Optional[int] = None,
        ops_per_dispatch: Optional[int] = None,
        mesh=None,
        overflow_check_every: Optional[int] = None,
        pallas_interpret: bool = False,
        kernel: Optional[str] = None,
        long_docs: Optional[int] = None,
        long_slots: Optional[int] = None,
        grids: Optional[int] = None,
        grid_handles: Optional[int] = None,
    ):
        from ..config import DEFAULT as _CFG

        # geometry defaults come from the unified config registry
        max_docs = max_docs if max_docs is not None else _CFG.applier_max_docs
        max_slots = (max_slots if max_slots is not None
                     else _CFG.applier_max_slots)
        long_docs = (long_docs if long_docs is not None
                     else _CFG.applier_long_docs)
        long_slots = (long_slots if long_slots is not None
                      else _CFG.applier_long_slots)
        grids = grids if grids is not None else _CFG.applier_grids
        grid_handles = (grid_handles if grid_handles is not None
                        else _CFG.applier_grid_handles)
        ops_per_dispatch = (ops_per_dispatch if ops_per_dispatch is not None
                            else _CFG.applier_ops_per_dispatch)
        overflow_check_every = (
            overflow_check_every if overflow_check_every is not None
            else _CFG.applier_overflow_check_every)
        self.max_docs = max_docs
        self.max_slots = max_slots
        self.K = ops_per_dispatch
        # overflow flags live on-device; reading them is a host sync that
        # stalls the whole dispatch pipeline (the host waits for every
        # queued wave), so flush() only polls every N dispatches.
        # Deferral is safe: the flag is sticky (ops/apply.py ORs into it)
        # and escalation replays the doc from its authoritative log, so
        # late detection loses nothing. Queries and finalize() always
        # check before exposing state.
        self.overflow_check_every = overflow_check_every
        self._dispatches_since_check = 0
        # an int mesh is shorthand for a docs-only axis of that many
        # shards — callers above the parallel layer (chaos soak) can ask
        # for a mesh without importing mesh construction themselves
        if isinstance(mesh, int):
            from ..parallel.mesh import make_mesh

            mesh = make_mesh(mesh, seg_shards=1)
        # the doc→shard routing table (partition-router role). In mesh
        # mode each 'docs'-axis device owns a contiguous block of state
        # rows (NamedSharding splits axis 0 in mesh order), so placement
        # shard s IS device s and the global row is shard*slots + slot.
        if mesh is not None:
            n_shards = mesh.shape["docs"]
            if max_docs % n_shards:
                raise ValueError(
                    f"max_docs={max_docs} not divisible by the mesh's "
                    f"docs axis ({n_shards})")
            self.placement = DocPlacement(
                n_shards=n_shards, slots_per_shard=max_docs // n_shards)
        else:
            self.placement = DocPlacement(n_shards=1,
                                          slots_per_shard=max_docs)
        self.state: DocState = jax.vmap(lambda _: DocState.empty(max_slots))(
            jnp.arange(max_docs)
        )
        self.arenas: list[TextArena] = [TextArena() for _ in range(max_docs)]
        self.prop_table = PropTable()  # shared across docs; ids are dense
        # per-doc dense client interning — collision-free by construction
        # (the round-1 truncated-hash scheme could merge two clients'
        # own-op visibility at the 24-bit birthday bound)
        self._client_ids: dict[int, dict[str, int]] = {}
        # staged device ops as 12-tuples in ops/apply field order; one
        # np.array() per slot per flush instead of one per op
        # staged device ops per slot, as a list of int32 [n, OP_FIELDS]
        # CHUNKS (one per ingested batch — the array lane appends its
        # vectorized rows directly; the dict lane converts its tuple
        # batch once); _staged_ops tracks the total row count
        self._staged: dict[int, list] = {}
        self._staged_ops = 0
        self._host_docs: dict[int, MergeTreeClient] = {}  # escalated docs
        self._doc_keys: dict[int, tuple[str, str]] = {}
        # what changed since the last checkpoint: a delta checkpoint
        # writes only these slots and what the shared tables grew by
        self._marks = _CheckpointMarks(max_docs)
        self._mesh = mesh
        # mesh-lane staging-cost counters (the multichip smoke and
        # bench_multichip read these: per-wave staged bytes must scale
        # with ACTIVE shards, never with max_docs)
        self.mesh_waves = 0
        self.mesh_active_shards = 0
        self.mesh_staged_bytes = 0
        self.mesh_stage_seconds = 0.0
        # ---- overlap-staged dispatch (stage/execute split) ----
        # Two rotating host staging buffer sets: wave N+1 scatters into
        # one set while the other set's device_put (wave N) may still be
        # copying — _rotate_stage_buffers fences a set's previous
        # transfers before handing it out again, so async H2D and state
        # donation stay sound even when the backend copies lazily.
        self._stage_pool: tuple = ({}, {})
        self._stage_inflight: list = [None, None]
        self._stage_flip = 0
        # the last dispatched step's output: is_ready() is the
        # non-blocking "device still executing" probe the overlap-ratio
        # accounting keys on; _drain_device() fences it at seams
        self._exec_marker = None
        # host-stage vs device-execute split, BOTH lanes (the pre-overlap
        # code only took t0 in mesh mode, so the dense lane reported zero
        # staging cost and the kernel-plateau analysis had no split)
        self.stage_seconds = 0.0
        self.stage_overlap_seconds = 0.0
        self.stage_bytes = 0
        # the execute half's wall time: an asynchronous enqueue of the
        # step, not its device time (that is in the profiler's trace)
        self.dispatch_seconds = 0.0
        self.waves_staged = 0
        # the local farm's waves by step: the tile step's or the full
        # one's, the rows they occupied and the rows the steps covered
        self.waves_tiled = self.waves_full = 0
        self.wave_rows = self.wave_rows_padded = 0
        self._tiles: tuple = ()
        self._registry = None
        # contract-kernel selection: auto = Pallas on real TPU, XLA scan
        # elsewhere; dense lane's tile is max_docs (= slots_per_shard of
        # the 1-shard placement), mesh lane's is slots-per-shard
        use_pallas = _resolve_kernel(kernel, _CFG,
                                     self.placement.slots_per_shard)
        self.kernel_lane = "pallas" if use_pallas else "xla"
        if mesh is not None:
            from ..parallel.sharded_apply import (
                doc_sharding, make_sharded_packed_step, shard_state)

            self.state = shard_state(self.state, mesh)
            # the mesh twin of _dense_step_for: same int16 packed wave,
            # unpacked per shard inside shard_map, state donated, stats
            # psum'd — the dispatch path below is otherwise identical to
            # the local dense lane (force_wide included)
            self._sharded_step = make_sharded_packed_step(
                mesh, use_pallas=use_pallas,
                pallas_interpret=pallas_interpret,
                trace_hook=_count_trace)
            self._mesh_sharding = doc_sharding(mesh)
            sps = self.placement.slots_per_shard
            # device → docs-shard map for pre-partitioned wave assembly:
            # P("docs") splits axis 0 into contiguous blocks in mesh
            # order, so the device whose block starts at shard*sps IS
            # that placement shard (with a 'seg' axis, its replicas too)
            by_shard: dict[int, list] = {}
            for dev, idx in self._mesh_sharding.devices_indices_map(
                    (max_docs,)).items():
                by_shard.setdefault((idx[0].start or 0) // sps,
                                    []).append(dev)
            self._shard_devices = [by_shard[s]
                                   for s in range(self.placement.n_shards)]
            # per-device resident zero shards, reused every wave for
            # INACTIVE shards (no host alloc, no transfer)
            self._zero_shards: dict = {}
        else:
            # dense dispatch: ship the padded [D, K, F] wave packed to
            # int16 deltas (see _dense_step_for for the wire format), or,
            # where it occupies at most a tile's rows, only those rows
            self._dense_step = _dense_step_for(
                max_docs, self.K, use_pallas=use_pallas,
                pallas_interpret=pallas_interpret)
            self._tiles = _tile_sizes(max_docs)
            self._tile_step = _tile_step_for(
                self.K, use_pallas=use_pallas,
                pallas_interpret=pallas_interpret)
        self._init_long_tier(long_docs, long_slots, use_pallas,
                             pallas_interpret)
        self._init_grids(grids, grid_handles)
        self.dispatches = 0
        self.ops_applied = 0
        self.host_escalations = 0
        # coverage tracking for summary writers (service_summarizer.py):
        # _applied_seq = highest ingested seq per slot (tail-lag check);
        # _first_seq = first ingested seq per slot; _anchored = slots whose
        # state provably covers the doc's WHOLE history (checkpoint
        # restore, authoritative escalation replay, or a summarizer gate
        # pass over an untruncated log). max-seq alone cannot prove an
        # applier fed only the post-truncation tail covers the prefix.
        self._applied_seq: dict[int, int] = {}
        self._first_seq: dict[int, int] = {}
        self._anchored: set[int] = set()
        # checkpoint-restore bookkeeping: ops sequenced while the process
        # was down are not in the restored state, so the summarizer must
        # verify the feed resumed without skipping any (see restore_gap)
        self._restore_applied: dict[int, int] = {}
        self._post_restore_first: dict[int, int] = {}

    # ---------------------------------------------------------- long tier
    #
    # A document that outgrows the farm's S slots moves, on the device, to
    # one of L rows of S_long slots beside the farm (``_promote``); it
    # keeps its farm slot as its key (arena, client table, applied seq,
    # placement) and ``_long_rows`` maps that slot to its long row. The
    # tier has its own int32 wave and step in the same turn, so a book's
    # positions never widen the farm's int16 wave. Host escalation stays
    # for a long row past S_long and for the causes other than slots.

    def _init_long_tier(self, L: int, SL: int, use_pallas: bool,
                        pallas_interpret: bool) -> None:
        self.long_docs, self.long_slots = L, SL
        self.long_state: Optional[DocState] = None
        self.long_kernel_lane: Optional[str] = None
        self._long_rows: dict[int, int] = {}  # farm slot -> long row
        self._long_free: list[int] = []
        self.promotions = 0
        self.long_dispatches = 0
        self.long_slots_peak = 0
        self._long_marker = None
        self._long_since_check = 0
        if not L:
            return
        if self._mesh is not None:
            raise ValueError("the long tier runs beside a local farm, "
                             "not a doc mesh")
        if L % 8 or SL & (SL - 1) or SL <= self.max_slots \
                or 2 * self.K >= self.max_slots:
            raise ValueError(
                f"long tier {L} x {SL}: L must be a multiple of 8, S_long "
                f"a power of two above max_slots={self.max_slots}, and "
                f"2 x ops_per_dispatch={self.K} below max_slots (a doc "
                "moves at a count above max_slots - 2K)")
        self.long_state = jax.vmap(lambda _: DocState.empty(SL))(
            jnp.arange(L))
        self._long_free = list(range(L - 1, -1, -1))
        # host upper bound on each farm row's count: set at every fence,
        # and raised by 2 per op dispatched since (no op adds more slots)
        self._count_hi = np.zeros(self.max_docs, np.int64)
        self.long_kernel_lane = "pallas" if use_pallas else "xla"
        self._long_step = _long_step_for(L, self.K, use_pallas,
                                         pallas_interpret)

    def warm_long_tier(self) -> None:
        """Compile the tier's step and the promotion on throwaway state
        of the same shapes, so that nothing compiles when a doc first
        grows past the farm."""
        if self.long_state is None:
            return
        L, D, K = self.long_docs, self.max_docs, self.K
        farm = jax.vmap(lambda _: DocState.empty(self.max_slots))(
            jnp.arange(D))
        long = jax.vmap(lambda _: DocState.empty(self.long_slots))(
            jnp.arange(L))
        farm, long = _promote_fn()(farm, long, 0, 0)
        long = self._long_step(
            long, jax.device_put(np.zeros((L, K, OP_FIELDS), np.int32)))
        jax.block_until_ready((farm, long))

    def warm_farm(self) -> None:
        """Compile the local farm's packed step and its tile steps, so
        that no wave compiles later, whatever rows it occupies. Each runs
        an all-NOOP wave on the farm itself, which leaves every row
        bit-identical (as a row no op touches comes out of a step), so no
        second farm is held on the device."""
        if self._mesh is not None:
            return
        D, K = self.max_docs, self.K
        self._drain_device()
        self.state, _ = self._dense_step[0](
            self.state,
            jax.device_put(np.zeros((D, K, OP_FIELDS), np.int16)),
            jax.device_put(np.zeros((D, 2), np.int32)))
        for T in self._tiles:
            self.state, _ = self._tile_step(
                self.state, jax.device_put(np.arange(T, dtype=np.int32)),
                jax.device_put(np.zeros((T, K, OP_FIELDS), np.int16)),
                jax.device_put(np.zeros((T, 2), np.int32)))
        self._stage_inflight = [None, None]
        self._exec_marker = self.state.count
        jax.block_until_ready(self.state)

    def _route_parts(self, parts):
        """Split one wave's parts into (farm, long). A farm doc moves to
        the long tier first when this wave could pass its S slots (by the
        host's bound; a fence confirms the count, and it moves above
        S - 2K), or when one of its ops names a position past int16,
        which would widen the farm's whole wave."""
        rows, S = self._long_rows, self.max_slots
        due, check = [], []
        for slot, chunks, count in parts:
            if not count or slot in rows:
                continue
            if any(int(c[:, F_POS].max()) > _INT16_MAX
                   or int(c[:, F_END].max()) > _INT16_MAX for c in chunks):
                due.append(slot)
            elif self._count_hi[slot] + 2 * count > S:
                check.append(slot)
        if check:
            counts = np.asarray(self.state.count)  # a fence of the farm
            self._count_hi[:] = counts
            due += [s for s in check if counts[s] > S - 2 * self.K]
        for slot in due[:len(self._long_free)]:
            self._promote(slot)
        farm, long = [], []
        for p in parts:
            (long if p[0] in rows else farm).append(p)
        return farm, long

    def _promote(self, slot: int) -> None:
        """Move farm doc ``slot`` to a free long row, on the device."""
        with span("applier.promote"):
            # the move donates both states: fence the waves in flight, so
            # that no staging set's marker names a buffer it frees (a
            # rotation would block on a donated buffer and fail)
            self._drain_device()
            self._stage_inflight = [None, None]
            row = self._long_free.pop()
            self.state, self.long_state = _promote_fn()(
                self.state, self.long_state, slot, row)
            self._exec_marker = self.state.count
            self._long_marker = self.long_state.count
            self._long_rows[slot] = row
            self._count_hi[slot] = 0
            self._marks.dirty.add(slot)
            self._marks.promoted.add(slot)
        self.promotions += 1

    def _dispatch_long(self, parts) -> int:
        """Stage and execute one long-tier wave: the tier's docs' op rows
        at int32 in a fresh [L, K, F] buffer, then its step."""
        with span("applier.long_stage_wave"):
            parts = [p for p in parts if p[2]]
            if not parts:
                return 0
            chunks = [c for _, cs, _ in parts for c in cs]
            flat = chunks[0] if len(chunks) == 1 else np.concatenate(chunks)
            rows_a = np.array([self._long_rows[p[0]] for p in parts],
                              np.int64)
            lens_a = np.array([p[2] for p in parts])
            starts = np.cumsum(lens_a) - lens_a
            wave = np.zeros((self.long_docs, self.K, OP_FIELDS), np.int32)
            wave[np.repeat(rows_a, lens_a),
                 np.arange(len(flat)) - np.repeat(starts, lens_a)] = flat
            wave = jax.device_put(wave)
        with span("applier.long_dispatch"):
            self.long_state = self._long_step(self.long_state, wave)
            self._long_marker = self.long_state.count
        self.long_dispatches += 1
        self._long_since_check += 1
        self._dispatches_since_check += 1
        return len(flat)

    def _check_long(self) -> None:
        """The long tier's half of the fence: counts (and the peak) and
        overflow; a long row past S_long escalates to the host."""
        self._long_since_check = 0
        flags, counts = jax.device_get((self.long_state.overflow,
                                        self.long_state.count))
        used = {row: slot for slot, row in self._long_rows.items()}
        if used:
            self.long_slots_peak = max(self.long_slots_peak,
                                       int(counts[list(used)].max()))
        for row in np.nonzero(flags)[0]:
            slot = used.get(int(row))
            if slot is not None and slot not in self._host_docs:
                self._escalate(slot, None, None)

    # -------------------------------------------------------------- grids
    #
    # A SharedMatrix document (a grid, dds/matrix.py) holds two merge-tree
    # permutation vectors and a cell map. Grid g's row and column vectors
    # are the farm rows of its doc's id plus ROWS_SUFFIX and COLS_SUFFIX,
    # both applied by the farm's wave, so a doc that holds text beside
    # its grid keeps its own text row; its cells are ``grid_cells[g]``,
    # an [H, H] int32 plane indexed by (row handle, column handle), with
    # ``grid_written[g]`` marking the cells ever written, both written
    # by ops/matrix_cells.py. Handles are allocated in insert order, one
    # arena character each, so a vector slot's text_start is its first
    # handle. A grid's staged ops stay in seq order in ``_grid_q``; each
    # wave takes a prefix of them of the form setCells, vector ops,
    # setCells (``_take_grid_wave``): the first setCells run before the
    # farm's step and the others after it, so that a setCell sees every
    # vector op of lower seq and none of higher, and a wave splits only
    # where a vector op follows. A grid whose inserts would pass H, whose
    # vector row overflows S, or whose op the device does not model (a
    # cell value that is not an int32) escalates to a host SharedMatrix
    # replayed from its log; so does a grid past the G the store holds.

    def _init_grids(self, G: int, H: int) -> None:
        self.grids, self.grid_handles = G, H
        self.grid_cells = self.grid_written = None
        self._vec_grid: dict[int, int] = {}  # either vector's slot -> grid
        self._grid_slots: list[tuple[int, int]] = []  # (row, col) slots
        self._grid_next: list[list[int]] = []  # handles allocated [r, c]
        self._grid_q: dict[int, list] = {}    # staged items, seq order
        self._host_grids: dict[int, SharedMatrix] = {}
        self._host_grid_seq: dict[int, int] = {}
        # each dispatch's (written index, written value, dead writes) on
        # the device, then the turn's writes on the host for the delta
        self._grid_pending: list = []
        self._grid_turn: list = []
        self._grid_slots_dev = None
        self._grid_marker = None
        self.grid_dispatches = self.grid_cells_set = 0
        self.grid_dead_writes = self.grid_handles_peak = 0
        self.grid_escalations = 0
        self.grid_cells_held = 0  # cells written, as the last base read
        if not G:
            return
        if self._mesh is not None or self.long_state is not None:
            raise ValueError("grids run beside a local farm without the "
                             "long tier")
        if G * H * H >= 1 << 31:
            raise ValueError(f"grid store {G} x {H} x {H}: a cell's flat "
                             "index must fit int32")
        self.grid_cells = jnp.zeros((G, H, H), jnp.int32)
        self.grid_written = jnp.zeros((G, H, H), jnp.int8)

    def warm_grids(self) -> None:
        """Compile the cells step (an empty wave on the store) and the
        base's page read, so that nothing compiles when the first setCell
        arrives or a base is taken."""
        if self.grid_cells is None:
            return
        empty = np.zeros((self.grids, self.K, CELL_FIELDS), np.int32)
        slots = jax.device_put(np.zeros((2, self.grids), np.int32))
        self.grid_cells, self.grid_written, *_ = _cells_fn()(
            self.grid_cells, self.grid_written, self.state, slots[0],
            slots[1], jax.device_put(empty))
        jax.block_until_ready(_written_fn()(
            self.grid_cells, self.grid_written,
            jnp.zeros(self.grids, jnp.int32)))

    def attach_grid(self, tenant_id: str, document_id: str) -> int:
        """Make the doc a grid (its attach names a SharedMatrix): farm
        rows for its two vectors, apart from the doc's text row, and the
        next grid index; past the store's G, a host SharedMatrix.
        Returns the grid's index."""
        rs = self.slot_of(tenant_id, document_id + ROWS_SUFFIX)
        if rs in self._vec_grid:
            return self._vec_grid[rs]
        cs = self.slot_of(tenant_id, document_id + COLS_SUFFIX)
        g = len(self._grid_slots)
        self._grid_slots.append((rs, cs))
        self._grid_next.append([0, 0])
        self._vec_grid[rs] = self._vec_grid[cs] = g
        self._grid_slots_dev = None
        self._marks.grids.add(g)
        self._marks.dirty.update((rs, cs))
        if g >= self.grids:
            self._escalate_grid(g)
        return g

    def _grid_client(self, g: int, client_id: Optional[str]) -> int:
        """A client's id, the same in both vectors' tables."""
        if client_id is None:
            return SYSTEM_CLIENT
        rs, cs = self._grid_slots[g]
        table = self._client_ids.setdefault(rs, {})
        cid = table.get(client_id)
        if cid is None:
            cid = table[client_id] = len(table)
            self._client_ids.setdefault(cs, {})[client_id] = cid
            self._marks.dirty.add(cs)
        return cid

    def ingest_grid_batch(self, tenant_id: str, document_id: str,
                          pairs: list) -> None:
        """Stage a batch of sequenced SharedMatrix wire ops of one grid
        (dds/matrix.py's wire) in seq order."""
        g = self.attach_grid(tenant_id, document_id)
        rs = self._grid_slots[g][0]
        self._marks.dirty.add(rs)
        if pairs:
            self._applied_seq[rs] = max(self._applied_seq.get(rs, 0),
                                        pairs[-1][0].sequence_number)
            self._first_seq.setdefault(rs, pairs[0][0].sequence_number)
            if rs in self._restore_applied:
                self._post_restore_first.setdefault(
                    rs, pairs[0][0].sequence_number)
        if g in self._host_grids:
            for msg, op in pairs:
                self._apply_host_grid(g, msg, op)
            return
        q = self._grid_q.setdefault(g, [])
        for i, (msg, op) in enumerate(pairs):
            item = self._grid_item(g, msg, op)
            if item is None:
                # the replay reads this batch from the log
                self._escalate_grid(g)
                for msg2, op2 in pairs[i + 1:]:
                    self._apply_host_grid(g, msg2, op2)
                return
            q.append(item)
        if not q:
            del self._grid_q[g]

    def _grid_item(self, g: int, msg, op) -> Optional[tuple]:
        """A staged item: ("c", setCell row) or ("v", axis, farm op row);
        None for an op the device does not model."""
        if type(op) is not dict:
            return None
        kind = op.get("op")
        seq, ref = msg.sequence_number, msg.reference_sequence_number
        client = self._grid_client(g, msg.client_id)
        if kind == "setCell":
            v = op.get("value")
            if type(v) is not int or not -(1 << 31) <= v < 1 << 31 \
                    or type(op.get("row")) is not int \
                    or type(op.get("col")) is not int:
                return None
            # ops/matrix_cells.py's row: VALID, ROW, COL, REF, CLIENT, VALUE
            return ("c", (1, op["row"], op["col"], ref, client, v))
        axis, insert = _VECTOR_OPS.get(kind, (None, None))
        count = op.get("count")
        if axis is None or type(count) is not int or count < 1 \
                or type(op.get("pos")) is not int:
            return None
        slot = self._grid_slots[g][axis]
        self._marks.dirty.add(slot)
        pos, msn = op["pos"], msg.minimum_sequence_number
        if not insert:
            return ("v", axis, (OP_REMOVE, pos, pos + count, seq, ref,
                                client, 0, 0, msn, 0, 0, 0))
        nxt = self._grid_next[g]
        h0 = nxt[axis]
        if h0 + count > self.grid_handles:
            return None
        nxt[axis] = h0 + count
        self.grid_handles_peak = max(self.grid_handles_peak, h0 + count)
        self._marks.grids.add(g)
        # the arena holds only handles, so the run starts at handle h0
        start = self.arenas[slot].append(
            "".join(map(chr, range(HANDLE_BASE + h0,
                                   HANDLE_BASE + h0 + count))))
        return ("v", axis, (OP_INSERT, pos, 0, seq, ref, client, count,
                            start, msn, 0, 0, 0))

    def _take_grid_wave(self):
        """Pop each staged grid's next wave: up to K setCells that come
        before its vector ops, up to K vector ops a vector, then up to K
        setCells, stopping at a vector op that follows those. Returns
        (farm parts, setCells before the farm's step, setCells after),
        the setCells as [(grid, rows)]."""
        K = self.K
        parts, pre, post = [], [], []
        for g in list(self._grid_q):
            q = self._grid_q[g]
            vec = ([], [])
            cells = ([], [])  # before, after the vector ops
            n = 0
            for item in q:
                if item[0] == "c":
                    rows = cells[any(vec)]
                    if len(rows) == K:
                        break
                    rows.append(item[1])
                elif cells[1] or len(vec[item[1]]) == K:
                    break
                else:
                    vec[item[1]].append(item[2])
                n += 1
            del q[:n]
            if not q:
                del self._grid_q[g]
            for axis, ops in enumerate(vec):
                if ops:
                    parts.append((self._grid_slots[g][axis],
                                  [np.asarray(ops, np.int32)], len(ops)))
            for out, rows in zip((pre, post), cells):
                if rows:
                    out.append((g, rows))
        return parts, pre, post

    def _dispatch_cells(self, cells) -> int:
        """Stage and execute one wave of setCells: [G, K] rows, then the
        cells step on the farm as the wave's step left it."""
        with span("applier.grid_stage_wave"):
            wave = np.zeros((self.grids, self.K, CELL_FIELDS), np.int32)
            n = 0
            for g, rows in cells:
                wave[g, :len(rows)] = rows
                n += len(rows)
            if self._grid_slots_dev is None:
                slots = np.zeros((2, self.grids), np.int32)
                for g, pair in enumerate(self._grid_slots[:self.grids]):
                    slots[:, g] = pair
                self._grid_slots_dev = (jax.device_put(slots[0]),
                                        jax.device_put(slots[1]))
            wave = jax.device_put(wave)
        with span("applier.grid_dispatch"):
            self.grid_cells, self.grid_written, idx, val, dead = _cells_fn()(
                self.grid_cells, self.grid_written, self.state,
                *self._grid_slots_dev, wave)
            self._grid_pending.append((idx, val, dead))
            self._grid_marker = dead
        self.grid_dispatches += 1
        return n

    def _collect_grid_writes(self) -> None:
        """Bring the dispatched waves' writes to the host: the turn's list
        for the delta, the counters."""
        if not self._grid_pending:
            return
        got = jax.device_get(self._grid_pending)
        self._grid_pending = []
        idx = np.concatenate([i.ravel() for i, _v, _d in got])
        val = np.concatenate([v.ravel() for _i, v, _d in got])
        self.grid_dead_writes += int(sum(d for _i, _v, d in got))
        keep = idx >= 0
        idx, val = idx[keep].astype(np.int64), val[keep]
        self.grid_cells_set += len(idx)
        self._grid_turn.append((idx, val))

    def _take_grid_turn(self) -> tuple[np.ndarray, np.ndarray]:
        """(flat index, value) of each cell written since the last call,
        its last write only."""
        self._collect_grid_writes()
        turn, self._grid_turn = self._grid_turn, []
        if not turn:
            return np.zeros(0, np.int64), np.zeros(0, np.int32)
        return _last_writes(np.concatenate([i for i, _v in turn]),
                            np.concatenate([v for _i, v in turn]))

    def _grid_store_values(self) -> tuple[np.ndarray, np.ndarray]:
        """(flat index, value) of every cell ever written, read back from
        the device store a page of GRID_PAGE cells a grid at a time:
        one dispatch per page of the fullest grid, and the host holds
        only what it reads."""
        start = np.zeros(self.grids, np.int32)
        idx, val = [], []
        while True:
            i, v, n = jax.device_get(_written_fn()(
                self.grid_cells, self.grid_written, jax.device_put(start)))
            keep = i >= 0
            idx.append(i[keep].astype(np.int64))
            val.append(v[keep])
            start += GRID_PAGE
            if (start >= n).all():
                self.grid_cells_held = int(n.sum())
                return np.concatenate(idx), np.concatenate(val)

    def _live_handles(self, slot: int) -> list[int]:
        """A vector row's handles in order, removed items left out."""
        row = self._device_slot(slot)
        out = []
        for i in range(int(row.count)):
            if row.rem_seq[i] == NO_SEQ:
                ts = int(row.text_start[i])
                out.extend(range(ts, ts + int(row.length[i])))
        return out

    def get_grid(self, tenant_id: str, document_id: str) -> dict:
        """{(row, col): value} of the grid's set cells at their current
        positions (a query: fences the device)."""
        g = self._vec_grid[self._placed_row(tenant_id,
                                            document_id + ROWS_SUFFIX)]
        rs, cs = self._grid_slots[g]
        self.finalize()
        if g in self._host_grids:
            lists = self._host_grids[g].to_lists()
            return {(r, c): v for r, vals in enumerate(lists)
                    for c, v in enumerate(vals) if v is not None}
        self._collect_grid_writes()  # the counters, as of this query
        rpos = {h: i for i, h in enumerate(self._live_handles(rs))}
        cpos = {h: i for i, h in enumerate(self._live_handles(cs))}
        plane = np.asarray(self.grid_cells[g])
        return {(rpos[rh], cpos[ch]): int(plane[rh, ch])
                for rh, ch in zip(*np.nonzero(np.asarray(
                    self.grid_written[g])))
                if rh in rpos and ch in cpos}

    def _escalate_grid(self, g: int) -> None:
        """Move grid ``g`` to a host SharedMatrix, replayed from its log
        when it was on the device."""
        rs, _cs = self._grid_slots[g]
        tenant_id, name = self._doc_keys[rs]
        document_id = name.removesuffix(ROWS_SUFFIX)
        self._drain_device()
        on_device = g < self.grids
        if on_device and self._grid_replay_log is None:
            raise RuntimeError(
                f"grid {tenant_id}/{document_id} needs host escalation but "
                "no grid replay source is configured")
        self.grid_escalations += 1
        replica = SharedMatrix(GRID_CHANNEL)
        self._host_grids[g] = replica
        self._host_grid_seq[g] = 0
        self._grid_q.pop(g, None)
        self._marks.grids.add(g)
        self._marks.dirty.add(rs)
        if on_device:
            for m in self._grid_replay_log(tenant_id, document_id):
                self._apply_host_grid(g, m, m.contents)

    def _apply_host_grid(self, g: int, msg, op) -> None:
        if msg.sequence_number <= self._host_grid_seq[g]:
            return  # covered by the escalation replay
        self._host_grids[g].process(replace(msg, contents=op), local=False)
        self._host_grid_seq[g] = msg.sequence_number

    _grid_replay_log = None

    def set_grid_replay_source(self, fn) -> None:
        """fn(tenant, doc) -> the grid channel's sequenced messages."""
        self._grid_replay_log = fn

    # ------------------------------------------------------------- ingest

    def slot_of(self, tenant_id: str, document_id: str) -> int:
        """Global state row for a doc: the placement's (shard, slot)
        flattened shard-major, so rows route to their owning device."""
        shard, slot = self.placement.place(tenant_id, document_id)
        row = shard * self.placement.slots_per_shard + slot
        if row not in self._doc_keys:
            self._doc_keys[row] = (tenant_id, document_id)
            self._marks.dirty.add(row)
        return row

    def _placed_row(self, tenant_id: str, document_id: str
                    ) -> Optional[int]:
        """A doc's state row if it has one; places nothing."""
        at = self.placement.lookup(tenant_id, document_id)
        return (None if at is None
                else at[0] * self.placement.slots_per_shard + at[1])

    def _intern_client(self, slot: int, client_id: Optional[str]) -> int:
        if client_id is None:
            return SYSTEM_CLIENT
        table = self._client_ids.setdefault(slot, {})
        cid = table.get(client_id)
        if cid is None:
            cid = len(table)
            table[client_id] = cid
        return cid

    def ingest(
        self,
        tenant_id: str,
        document_id: str,
        msg: SequencedDocumentMessage,
        wire_op: dict,
    ) -> None:
        """Stage one sequenced merge-tree wire op for batched apply."""
        self.ingest_batch(tenant_id, document_id, [(msg, wire_op)])

    def ingest_batch(
        self,
        tenant_id: str,
        document_id: str,
        pairs: list[tuple[SequencedDocumentMessage, dict]],
    ) -> None:
        """Stage a broadcast batch of sequenced wire ops in one call —
        the deli-tpu marshal's per-boxcar entry point. Staging is plain
        tuple appends; device encoding happens once per flush."""
        slot = self.slot_of(tenant_id, document_id)
        self._marks.dirty.add(slot)
        if pairs:
            # sequenced stream ⇒ pairs arrive in seq order; the last is max
            self._applied_seq[slot] = max(
                self._applied_seq.get(slot, 0),
                pairs[-1][0].sequence_number)
            self._first_seq.setdefault(slot, pairs[0][0].sequence_number)
            if slot in self._restore_applied:
                self._post_restore_first.setdefault(
                    slot, pairs[0][0].sequence_number)
        if self.fault_plane is not None and slot not in self._host_docs:
            if self.fault_plane("applier.ingest", slot=slot) \
                    == "escalate_host":
                # forced overflow-to-host flip: same path a doc takes
                # when it outgrows device capacity — replays the
                # authoritative log into a host replica, then applies
                # this batch host-side below
                self._escalate(slot, None, None)
        if slot in self._host_docs:
            for msg, wire_op in pairs:
                self._apply_host(slot, msg, wire_op)
            return
        # stage into a local tuple list; one np conversion per batch at
        # the end (the chunk) — per-op tuple appends beat per-op numpy
        # row writes, and the wave builder concatenates chunks
        staged = []
        table = self._client_ids.setdefault(slot, {})
        arena = self.arenas[slot]
        # hot-loop locals: plain inserts/removes (the overwhelming bulk of
        # real traffic) stage inline without the _stage_op dispatch
        append = staged.append
        arena_append = arena.append
        table_get = table.get
        for i, (msg, wire_op) in enumerate(pairs):
            if type(wire_op) is not dict:
                ok = False
            else:
                cid = msg.client_id
                if cid is None:
                    client = SYSTEM_CLIENT
                else:
                    client = table_get(cid)
                    if client is None:
                        client = len(table)
                        table[cid] = client
                t = wire_op.get("type")
                if t == 0 and "marker" not in wire_op \
                        and not wire_op.get("props"):
                    text = wire_op.get("text") or ""
                    append((OP_INSERT, wire_op["pos"], 0,
                            msg.sequence_number,
                            msg.reference_sequence_number, client,
                            len(text), arena_append(text),
                            msg.minimum_sequence_number, 0, 0, 0))
                    continue
                if t == 1:
                    append((OP_REMOVE, wire_op["start"], wire_op["end"],
                            msg.sequence_number,
                            msg.reference_sequence_number, client, 0, 0,
                            msg.minimum_sequence_number, 0, 0, 0))
                    continue
                ok = self._stage_op(
                    staged, arena, wire_op, msg.sequence_number,
                    msg.reference_sequence_number, client,
                    msg.minimum_sequence_number)
            if not ok:
                # escalation replays the authoritative log (which already
                # holds this batch) and discards partial staging
                self._escalate(slot, msg, wire_op)
                for msg2, wire_op2 in pairs[i + 1:]:
                    self._apply_host(slot, msg2, wire_op2)
                return
        if staged:
            self._push_chunk(slot, np.asarray(staged, np.int32))

    def ingest_array_batch(self, tenant_id: str, document_id: str,
                           batch) -> None:
        """Stage a SequencedArrayBatch (service/array_batch.py) as ONE
        vectorized chunk — the deli-tpu marshal's device on-ramp: no
        per-op dicts, tuples, or message objects. Inserts land in the
        arena as a single concatenated append; annotate rows (the rare
        kind) fill their key/val ids in a small loop over just the
        annotate indices."""
        slot = self.slot_of(tenant_id, document_id)
        box = batch.boxcar
        n = box.n
        if n == 0:
            return
        self._marks.dirty.add(slot)
        self._applied_seq[slot] = max(self._applied_seq.get(slot, 0),
                                      batch.last_seq)
        self._first_seq.setdefault(slot, batch.base_seq)
        if slot in self._restore_applied:
            self._post_restore_first.setdefault(slot, batch.base_seq)
        if slot in self._host_docs:
            for i in range(n):
                self._apply_host(slot, batch.message(i), box.wire_op(i))
            return
        table = self._client_ids.setdefault(slot, {})
        client = table.get(box.client_id)
        if client is None:
            client = len(table)
            table[box.client_id] = client
        kind = box.kind
        is_ann = kind == 2  # wire kind 2 = annotate (array_batch.py)
        ann_idx = np.nonzero(is_ann)[0] if is_ann.any() else ()
        # annotates expand to one row PER PROP KEY; with single-key props
        # (the overwhelming case) the chunk stays one row per op; empty
        # or multi-key props take the materialized slow path
        if len(ann_idx) and (
                box.props is None
                or any(len(box.props[int(i)] or {}) != 1 for i in ann_idx)):
            pairs = [(batch.message(i), box.wire_op(i)) for i in range(n)]
            self.ingest_batch(tenant_id, document_id, pairs)
            return
        chunk = np.zeros((n, OP_FIELDS), np.int32)
        # wire kinds (0 ins, 1 rem, 2 ann) → device op codes (1, 2, 3)
        chunk[:, F_TYPE] = kind.astype(np.int32) + 1
        chunk[:, F_POS] = box.a
        chunk[:, F_END] = box.b
        seqs = batch.base_seq + np.arange(n, dtype=np.int64)
        chunk[:, F_SEQ] = seqs
        chunk[:, F_REFSEQ] = box.rseq
        chunk[:, F_CLIENT] = client
        chunk[:, F_MSN] = batch.msns
        arena_start = self.arenas[slot].append(box.text)
        chunk[:, F_TLEN] = np.diff(box.text_off)
        chunk[:, F_TSTART] = arena_start + box.text_off[:-1]
        for i in ann_idx:
            (k, v), = box.props[int(i)].items()
            chunk[i, F_KEY] = self.prop_table.intern_key(k)
            chunk[i, F_VAL] = (NO_VAL if v is None
                               else self.prop_table.intern_val(v))
        self._push_chunk(slot, chunk)

    def _push_chunk(self, slot: int, chunk: np.ndarray) -> None:
        """Append a staged [n, OP_FIELDS] chunk (the ONLY staging-count
        mutation point besides _take_wave/_drop_staged)."""
        self._staged.setdefault(slot, []).append(chunk)
        self._staged_ops += len(chunk)

    def _drop_staged(self, slot: int) -> None:
        """Discard a slot's staged chunks (escalation path), keeping the
        staged-op count consistent."""
        dropped = self._staged.pop(slot, None)
        if dropped:
            self._staged_ops -= sum(len(c) for c in dropped)

    def _stage_op(self, staged, arena, w, seq, ref, client, msn) -> bool:
        """Append a wire op's device tuples (ops/apply field order).
        Returns False when the kernel does not model the op."""
        t = w.get("type")
        if t == 0:  # insert
            pos = w["pos"]
            marker = w.get("marker")
            if marker is not None:
                start = arena.append(MARKER_GLYPH)
                tlen = 1
                staged.append((OP_INSERT, pos, 0, seq, ref, client,
                               1, start, msn, FLAG_MARKER, 0, 0))
            else:
                text = w.get("text") or ""
                start = arena.append(text)
                tlen = len(text)
                staged.append((OP_INSERT, pos, 0, seq, ref, client,
                               tlen, start, msn, 0, 0, 0))
            props = w.get("props")
            if props:
                # insert-with-props (oracle attaches props to the new
                # segment): at the insert's OWN perspective the visible
                # span [pos, pos+len) is exactly the new slot, so
                # follow-up annotates stamp precisely it
                self._stage_annotate(
                    staged, pos, pos + tlen, props, seq, ref, client, msn)
            return True
        if t == 1:  # remove
            staged.append((OP_REMOVE, w["start"], w["end"], seq, ref, client,
                           0, 0, msn, 0, 0, 0))
            return True
        if t == 2:  # annotate
            self._stage_annotate(staged, w["start"], w["end"], w["props"],
                                 seq, ref, client, msn)
            return True
        if t == 3:  # group: all-or-nothing (partial staging is discarded
            # by _escalate if a sub-op is unsupported)
            return all(
                self._stage_op(staged, arena, sub, seq, ref, client, msn)
                for sub in w["ops"]
            )
        if t == "interval":
            return True  # interval metadata: no effect on text content
        return False

    def _stage_annotate(self, staged, start, end, props, seq, ref, client,
                        msn) -> None:
        # one device op per key; in-order apply gives per-key LWW
        intern_key = self.prop_table.intern_key
        intern_val = self.prop_table.intern_val
        for k, v in props.items():
            staged.append((OP_ANNOTATE, start, end, seq, ref, client, 0, 0,
                           msn, 0, intern_key(k),
                           NO_VAL if v is None else intern_val(v)))

    # -------------------------------------------------------------- flush

    def flush(self) -> int:
        """Dispatch all staged ops to the device in [D, K] waves, inline.
        Device execution is only fenced by the periodic overflow poll
        (every ``overflow_check_every`` dispatches) or by
        ``finalize()``/queries."""
        total = 0
        while self._staged or self._grid_q:
            # one dispatch path for both lanes: _dispatch_wave routes the
            # packed wave to the local dense step or the mesh's sharded
            # step (per-shard staging + pre-partitioned transfer); a
            # grid's vector ops ride the farm's wave, its setCells follow
            parts, pre, post = self._take_wave() or [], None, None
            if self._grid_q:
                vec, pre, post = self._take_grid_wave()
                parts += vec
            if pre:  # setCells of lower seq than the wave's vector ops
                total += self._dispatch_cells(pre)
            total += self._dispatch_wave(parts)
            if post:
                total += self._dispatch_cells(post)
        self.ops_applied += total
        if self._dispatches_since_check >= self.overflow_check_every:
            self._check_overflow()
        return total

    def _take_wave(self):
        """Pop up to K staged op ROWS per doc.

        Returns [(slot, chunk_list, row_count)]; an overflowing chunk is
        split by an array view, so staging order is preserved."""
        if not self._staged:
            return None
        parts = []
        drained = []
        K = self.K
        for slot, chunks in self._staged.items():
            take, rest, count = [], None, 0
            for ci, ch in enumerate(chunks):
                n = len(ch)
                if count + n <= K:
                    take.append(ch)
                    count += n
                else:
                    room = K - count
                    if room > 0:
                        take.append(ch[:room])
                        count = K
                        rest = [ch[room:]] + chunks[ci + 1:]
                    else:
                        rest = chunks[ci:]
                    break
            parts.append((slot, take, count))
            self._staged_ops -= count
            if rest is None:
                drained.append(slot)
            else:
                self._staged[slot] = rest
        for slot in drained:
            del self._staged[slot]
        return parts

    def _dispatch_wave(self, parts) -> int:
        """Stage then execute one wave. Overlap comes from the execute
        half being an async dispatch, so flush()'s NEXT iteration's stage
        half runs on the host while this wave executes on device. With
        the long tier, the wave's long docs go to the tier's own wave
        after the farm's."""
        long_parts = None
        if self.long_state is not None and parts:
            parts, long_parts = self._route_parts(parts)
        n = 0
        if parts or long_parts is None:
            with span("applier.stage_wave"):
                staged = self._stage_wave(parts)
            if staged is not None:
                with span("applier.dispatch"):
                    n = self._execute_wave(staged)
        if long_parts:
            n += self._dispatch_long(long_parts)
        return n

    # --------------------------------------------- stage / execute halves

    def _metrics(self):
        if self._registry is None:
            from ..obs import get_registry

            self._registry = get_registry()
        return self._registry

    @blocking("may block_until_ready the execution that last consumed the target buffer set — the PR 11 rotation fence")
    def _rotate_stage_buffers(self) -> None:
        """Flip to the other staging buffer set, fencing the EXECUTION
        that last consumed it (``jax.device_put`` may alias the host
        buffer rather than copy — readiness of the input array proves
        nothing, only step completion makes the memory reusable). By
        rotation the fenced wave is two dispatches old, so with the
        pipeline one wave deep the block is a no-op — it only waits when
        the device has fallen a full double-buffer behind."""
        self._stage_flip ^= 1
        pending = self._stage_inflight[self._stage_flip]
        if pending is not None:
            jax.block_until_ready(pending)
            self._stage_inflight[self._stage_flip] = None

    def _stage_buffer(self, shape: tuple, dtype) -> np.ndarray:
        """A zeroed host staging buffer from the CURRENT rotation set
        (callers run _rotate_stage_buffers once per wave first)."""
        pool = self._stage_pool[self._stage_flip]
        key = (shape, np.dtype(dtype).str)
        buf = pool.get(key)
        if buf is None:
            buf = np.zeros(shape, dtype)
            pool[key] = buf
        else:
            buf.fill(0)
        return buf

    @blocking("block_until_ready on the in-flight wave — the strict-wave-order fence at checkpoint/escalation seams")
    def _drain_device(self) -> None:
        """Fence the in-flight wave. Checkpoint/restore, escalation,
        force_wide, and state queries must never act on a farm with a
        wave still executing — strict wave order at every seam."""
        if self._exec_marker is not None:
            jax.block_until_ready(self._exec_marker)
        if self._long_marker is not None:
            jax.block_until_ready(self._long_marker)
        if self._grid_marker is not None:
            jax.block_until_ready(self._grid_marker)

    def _stage_wave(self, parts) -> Optional[_StagedWave]:
        """The HOST half of a dispatch: concat chunks → pack_wave_rows →
        scatter into rotating staging buffers → device_put. No device
        compute is issued; the returned wave holds resident buffers only
        (ops/apply.py's packed-wave section documents the int16-delta
        wire format).

        One vectorized fancy-index write places every occupied row; the
        flat rows build as ONE ``np.array`` over the concatenated tuple
        list (per-doc conversions were the dominant host cost at high doc
        counts). ``_take_wave`` caps each doc at K ops, so a wave
        always fits. In mesh mode the scatter targets compact per-shard
        buffers for ACTIVE shards only (_stage_wave_mesh) — never an
        O(max_docs) dense host array."""
        if parts is None:
            return None
        t0 = time.perf_counter()
        all_chunks: list = []
        slots: list[int] = []
        lens: list[int] = []
        for slot, chunks, count in parts:
            if count == 0:  # interval-only batches stage nothing
                continue
            all_chunks.extend(chunks)
            slots.append(slot)
            lens.append(count)
        if not all_chunks:
            return None
        K = self.K
        flat = (all_chunks[0] if len(all_chunks) == 1
                else np.concatenate(all_chunks))
        n = len(flat)
        lens_a = np.array(lens)
        starts = np.cumsum(lens_a) - lens_a
        slots_a = np.array(slots, np.int64)
        doc_idx = np.repeat(slots_a, lens_a)
        pos_idx = np.arange(n, dtype=np.int64) - np.repeat(starts, lens_a)

        packed, seq_base, text_base = pack_wave_rows(flat, starts, lens_a)
        if self.long_state is not None:
            self._count_hi[slots_a] += 2 * lens_a

        force_wide = (
            self.fault_plane is not None
            and self.fault_plane("applier.dispatch", ops=n) == "force_wide")
        if force_wide:
            # the forced int32 lane is a different program: drain the
            # pipeline so the width flip never reorders around an
            # in-flight packed wave
            self._drain_device()
        fits16 = (not force_wide
                  and packed.min() >= -32768 and packed.max() <= 32767)
        self._rotate_stage_buffers()
        if self._mesh is not None:
            staged = self._stage_wave_mesh(
                flat, packed if fits16 else None, doc_idx, pos_idx,
                slots_a, seq_base, text_base, n)
        elif fits16:
            # the smallest tile that holds the wave's rows, else all D
            T = next((t for t in self._tiles if t >= len(slots_a)), 0)
            rows = _tile_rows(slots_a, T) if T else None
            at = slots_a if rows is None else np.searchsorted(rows, slots_a)
            wave16 = self._stage_buffer((T or self.max_docs, K, OP_FIELDS),
                                        np.int16)
            wave16[np.repeat(at, lens_a), pos_idx] = packed.astype(np.int16)
            bases = self._stage_buffer((T or self.max_docs, 2), np.int32)
            bases[at, 0] = seq_base
            bases[at, 1] = text_base
            arrays = (jax.device_put(wave16), jax.device_put(bases))
            nbytes = wave16.nbytes + bases.nbytes
            if rows is None:
                staged = _StagedWave("dense", False, arrays, n, nbytes)
            else:
                staged = _StagedWave("tile", False,
                                     (jax.device_put(rows),) + arrays, n,
                                     nbytes + rows.nbytes)
            self._count_wave(len(slots_a), T)
        else:
            # a field escaped int16 (giant doc, huge window): ship the
            # wave at full width — rare, pays a 2x transfer + one extra
            # compile the first time it happens
            wave = self._stage_buffer((self.max_docs, K, OP_FIELDS),
                                      np.int32)
            wave[doc_idx, pos_idx] = flat
            staged = _StagedWave("dense", True, (jax.device_put(wave),),
                                 n, wave.nbytes)
            self._count_wave(len(slots_a), 0)
        staged.flip = self._stage_flip
        dt = time.perf_counter() - t0
        # overlap accounting: this stage half counts as HIDDEN time when
        # a previously dispatched wave is still executing (is_ready is a
        # non-blocking completion probe, so the measurement never
        # perturbs the pipeline it measures)
        overlapped = (self._exec_marker is not None
                      and not self._exec_marker.is_ready())
        self.waves_staged += 1
        self.stage_seconds += dt
        self.stage_bytes += staged.nbytes
        if overlapped:
            self.stage_overlap_seconds += dt
        if self._mesh is not None:
            self.mesh_stage_seconds += dt
        reg = self._metrics()
        reg.inc("applier.stage.seconds", dt, lane=staged.lane)
        reg.inc("applier.stage.bytes", staged.nbytes, lane=staged.lane)
        reg.set_gauge("applier.stage.overlap_ratio",
                      self.stage_overlap_seconds / self.stage_seconds,
                      lane=staged.lane)
        if self.fault_plane is not None:
            # chaos seam: wave N+1 staged (popped from the staging dict,
            # device buffers resident) but NOT yet executed — a crash
            # here must lose nothing: restore replays it from the log
            self.fault_plane("applier.stage.staged", ops=n)
        return staged

    def _count_wave(self, rows: int, tile: int) -> None:
        """Count one local farm wave: ``rows`` occupied, stepped by the
        tile step of ``tile`` rows, or by the full step where 0."""
        reg = self._metrics()
        if tile:
            self.waves_tiled += 1
            reg.inc("applier.wave.tiled")
        else:
            self.waves_full += 1
            reg.inc("applier.wave.full")
        covered = tile or self.max_docs
        self.wave_rows += rows
        self.wave_rows_padded += covered
        reg.inc("applier.wave.rows", rows)
        reg.inc("applier.wave.rows_padded", covered)

    def _execute_wave(self, staged: _StagedWave) -> int:
        """The DEVICE half: one jitted-step dispatch on already-resident
        buffers. The dispatch is asynchronous — the caller's next stage
        half runs while the device executes."""
        t0 = time.perf_counter()
        if staged.lane == "tile":
            fn = self._tile_step
        else:
            packed_fn, wide_fn = (self._sharded_step
                                  if staged.lane == "mesh"
                                  else self._dense_step)
            fn = wide_fn if staged.wide else packed_fn
        self.state, _ = fn(self.state, *staged.arrays)
        self._exec_marker = self.state.count
        # the wave's staging buffers may be reused (and on CPU, where
        # device_put can alias host memory, even READ) only after this
        # execution completes — record its marker against the buffer set
        # the wave staged from, for _rotate_stage_buffers to fence on
        self._stage_inflight[staged.flip] = self._exec_marker
        dt = time.perf_counter() - t0
        self.dispatch_seconds += dt
        self._metrics().inc("applier.dispatch.seconds", dt,
                            lane=staged.lane)
        self.dispatches += 1
        self._dispatches_since_check += 1
        if self.fault_plane is not None:
            # chaos seam: the wave is IN FLIGHT on device and the next
            # wave is not yet staged — the other overlap-window order
            self.fault_plane("applier.stage.inflight", ops=staged.n)
        return staged.n

    def stage_overlap_ratio(self) -> float:
        """staged-while-executing seconds / total stage seconds."""
        return (self.stage_overlap_seconds / self.stage_seconds
                if self.stage_seconds else 0.0)

    def _stage_wave_mesh(self, flat, packed, doc_idx, pos_idx, slots_a,
                         seq_base, text_base, n: int) -> _StagedWave:
        """Mesh-lane stage: scatter the wave into per-ACTIVE-shard
        buffers and hand each mesh device its own addressable shard, so
        host staging cost and transferred bytes are O(active shards · K),
        never O(max_docs), and the jitted step sees inputs already in its
        layout — no host-side global materialization, no XLA resharding.

        The wave's rows are sorted by shard ONCE (each shard's rows
        become a contiguous slice — the pre-overlap per-shard boolean
        masks rescanned the whole wave per shard, the linear host cost
        MULTICHIP_r06 measured), then the per-shard scatter+transfer jobs
        run on a small thread pool: the numpy fancy-index write and
        device_put both release the GIL, so active shards stage
        concurrently on multi-core hosts. ``packed=None`` ships the int32
        wide wave (int16 range escape / chaos force_wide)."""
        sps = self.placement.slots_per_shard
        K = self.K
        row_shard, local_doc = self.placement.split_rows(doc_idx)
        order = np.argsort(row_shard, kind="stable")
        sorted_shard = row_shard[order]
        active = np.unique(sorted_shard)
        n_active = len(active)
        lo = np.searchsorted(sorted_shard, active, side="left")
        hi = np.searchsorted(sorted_shard, active, side="right")
        ld, pi = local_doc[order], pos_idx[order]
        wide = packed is None
        dtype = np.int32 if wide else np.int16
        rows = (flat if wide else packed.astype(np.int16))[order]
        W = self._stage_buffer((n_active, sps, K, OP_FIELDS), dtype)
        if wide:
            B = dlo = dhi = ls = sb = tb = None
        else:
            B = self._stage_buffer((n_active, sps, 2), np.int32)
            doc_shard, local_slot = self.placement.split_rows(slots_a)
            dorder = np.argsort(doc_shard, kind="stable")
            sorted_doc_shard = doc_shard[dorder]
            dlo = np.searchsorted(sorted_doc_shard, active, side="left")
            dhi = np.searchsorted(sorted_doc_shard, active, side="right")
            ls = local_slot[dorder]
            sb, tb = seq_base[dorder], text_base[dorder]

        def job(i: int):
            a, b = lo[i], hi[i]
            W[i][ld[a:b], pi[a:b]] = rows[a:b]
            if B is not None:
                da, db = dlo[i], dhi[i]
                B[i][ls[da:db], 0] = sb[da:db]
                B[i][ls[da:db], 1] = tb[da:db]

        if n_active > 1:
            list(_stage_executor().map(job, range(n_active)))
        else:
            job(0)
        shard_waves = {int(s): W[i] for i, s in enumerate(active)}
        arrays = (self._mesh_assemble(shard_waves, (K, OP_FIELDS), dtype),)
        staged_bytes = n_active * sps * K * OP_FIELDS * W.itemsize
        if not wide:
            shard_bases = {int(s): B[i] for i, s in enumerate(active)}
            arrays += (self._mesh_assemble(shard_bases, (2,), np.int32),)
            staged_bytes += n_active * sps * 2 * 4
        self.mesh_waves += 1
        self.mesh_active_shards += n_active
        self.mesh_staged_bytes += staged_bytes
        return _StagedWave("mesh", wide, arrays, n, staged_bytes)

    def _mesh_assemble(self, shard_bufs: dict, tail: tuple,
                       dtype) -> jax.Array:
        """A global [max_docs, *tail] device array assembled from per-
        shard host buffers via ``jax.make_array_from_single_device_
        arrays``: every mesh device receives ITS row block directly (one
        device_put of the compact per-shard buffer; 'seg' replicas share
        the same buffer), and INACTIVE shards reuse a zero shard already
        resident on their device — no transfer at all."""
        key = (np.dtype(dtype).str,) + tail
        zeros = self._zero_shards.get(key)
        if zeros is None:
            z = np.zeros((self.placement.slots_per_shard,) + tail, dtype)
            zeros = {dev: jax.device_put(z, dev)
                     for devs in self._shard_devices for dev in devs}
            self._zero_shards[key] = zeros
        arrays = []
        for s, devs in enumerate(self._shard_devices):
            buf = shard_bufs.get(s)
            for dev in devs:
                arrays.append(zeros[dev] if buf is None
                              else jax.device_put(buf, dev))
        return jax.make_array_from_single_device_arrays(
            (self.max_docs,) + tail, self._mesh_sharding, arrays)

    def finalize(self) -> None:
        """Flush staged ops and fence the device: after this, every doc's
        state (or its host escalation) reflects everything ingested."""
        with span("applier.fence"):
            self._finalize()

    def _finalize(self) -> None:
        self.flush()
        self._drain_device()
        if self._dispatches_since_check:
            self._check_overflow()

    def _check_overflow(self) -> None:
        self._dispatches_since_check = 0
        if self.long_state is None:
            flags = np.asarray(self.state.overflow)  # host sync point
            for slot in np.nonzero(flags)[0]:
                if int(slot) not in self._host_docs:
                    self._escalate(int(slot), None, None)
            return
        flags, counts = jax.device_get((self.state.overflow,
                                        self.state.count))
        self._count_hi[:] = counts
        for slot in np.nonzero(flags)[0]:
            if int(slot) not in self._host_docs \
                    and int(slot) not in self._long_rows:
                self._escalate(int(slot), None, None)
        if self._long_since_check:
            self._check_long()
        # the fence's promotions: farm docs the next wave could overflow
        for slot in np.nonzero(counts > self.max_slots - 2 * self.K)[0]:
            if not self._long_free:
                break
            if int(slot) not in self._host_docs \
                    and int(slot) not in self._long_rows:
                self._promote(int(slot))

    # ------------------------------------------------------------- queries

    def slot_count(self, tenant_id: str, document_id: str) -> int:
        """Live device slots for a doc (bounded under churn by zamboni)."""
        slot = self.slot_of(tenant_id, document_id)
        row = self._long_rows.get(slot)
        if row is not None:
            return int(np.asarray(self.long_state.count)[row])
        return int(np.asarray(self.state.count)[slot])

    def _device_slot(self, slot: int) -> DocState:
        """The doc's row of whichever tier holds it, on the host."""
        row = self._long_rows.get(slot)
        if row is not None:
            return jax.tree.map(lambda a: np.asarray(a)[row],
                                self.long_state)
        return jax.tree.map(lambda a: np.asarray(a)[slot], self.state)

    def _sync(self, slot: int) -> None:
        """Flush + overflow-check before exposing a doc's state."""
        if self._staged.get(slot):
            self.flush()
        self._drain_device()
        if self._dispatches_since_check:
            self._check_overflow()

    def get_text(self, tenant_id: str, document_id: str) -> str:
        slot = self.slot_of(tenant_id, document_id)
        self._sync(slot)
        if slot in self._host_docs:
            return self._host_docs[slot].get_text()
        single = self._device_slot(slot)
        out, arena = [], self.arenas[slot]
        for i in range(int(single.count)):
            if single.rem_seq[i] != -1:
                continue
            if single.flags[i] & FLAG_MARKER:
                continue  # markers contribute length, not text
            out.append(arena.slice(int(single.text_start[i]), int(single.length[i])))
        return "".join(out)

    def get_tree(self, tenant_id: str, document_id: str) -> "MergeTreeClient":
        """Decode the doc to an oracle tree (summaries / inspection)."""
        slot = self.slot_of(tenant_id, document_id)
        self._sync(slot)
        if slot in self._host_docs:
            return self._host_docs[slot]
        tree = decode_state(self._device_slot(slot), self.arenas[slot],
                            self.prop_table)
        # flat replica: decode_state produces the flat oracle tree, so
        # don't build (then discard) the client's default blocked one
        replica = MergeTreeClient(f"tpu-applier/{tenant_id}/{document_id}",
                                  blocked=False)
        replica.tree = tree
        # carry the interning table: in-window stamps must translate back
        # to wire client ids when this replica snapshots (service
        # summaries would otherwise lose attribution)
        replica._ids.update(self._client_ids.get(slot, {}))
        return replica

    def applied_seq(self, tenant_id: str, document_id: str) -> int:
        """Highest sequence number ingested for the doc, its text and its
        grid (0 if none). Summary writers compare this against the
        stream's last channel op to refuse writing a summary from lagging
        device state. Without grids a doc takes its row here, at the
        stage's first sight of it; with grids it takes its rows as its
        text or its grid arrives (a grid alone holds no text row)."""
        if not self.grids:
            return self._applied_seq.get(
                self.slot_of(tenant_id, document_id), 0)
        return max(self._applied_seq.get(self._placed_row(
            tenant_id, name), 0) for name in (document_id,
                                              document_id + ROWS_SUFFIX))

    def first_seq(self, tenant_id: str, document_id: str) -> int:
        """First sequence number ever ingested for the doc (0 if none)."""
        return self._first_seq.get(
            self.slot_of(tenant_id, document_id), 0)

    def is_anchored(self, tenant_id: str, document_id: str) -> bool:
        """True when the slot's state provably covers the doc's whole
        history (see the coverage-tracking comment in __init__)."""
        return self.slot_of(tenant_id, document_id) in self._anchored

    def mark_anchored(self, tenant_id: str, document_id: str) -> None:
        """Record a coverage proof established by the caller (the
        summarizer's gate pass). Also discharges any pending
        restore-window condition — the proof subsumes it."""
        slot = self.slot_of(tenant_id, document_id)
        self._marks.dirty.add(slot)
        self._anchored.add(slot)
        self._restore_applied.pop(slot, None)
        self._post_restore_first.pop(slot, None)

    def restore_gap(self, tenant_id: str, document_id: str
                    ) -> Optional[tuple[int, Optional[int]]]:
        """(applied seq at checkpoint restore, first seq ingested since)
        for a restored slot, else None. Ops sequenced in between were
        never ingested — the summarizer refuses if the stream shows any."""
        slot = self.slot_of(tenant_id, document_id)
        if slot not in self._restore_applied:
            return None
        return (self._restore_applied[slot],
                self._post_restore_first.get(slot))

    def get_properties_at(self, tenant_id: str, document_id: str,
                          pos: int) -> dict:
        """Properties of the visible character at ``pos`` (final
        perspective) — the annotate-path query surface."""
        slot = self.slot_of(tenant_id, document_id)
        self._sync(slot)
        if slot in self._host_docs:
            return self._host_docs[slot].get_properties_at(pos)
        single = self._device_slot(slot)
        cum = 0
        for i in range(int(single.count)):
            if single.rem_seq[i] != -1:
                continue
            if cum <= pos < cum + int(single.length[i]):
                props = {}
                for p in range(single.prop_key.shape[-1]):
                    kid = int(single.prop_key[i, p])
                    if kid != -1:
                        props[self.prop_table.key(kid)] = self.prop_table.val(
                            int(single.prop_val[i, p]))
                return props
            cum += int(single.length[i])
        raise IndexError(pos)

    # ---------------------------------------------------- host escalation

    def _escalate(self, slot: int, msg, wire_op) -> None:
        """Rebuild the doc on the scalar oracle from its authoritative op
        log and continue host-side (SURVEY §7(e) escape hatch)."""
        g = self._vec_grid.get(slot)
        if g is not None:  # a grid's vector row: the whole grid goes
            if g not in self._host_grids:
                self._escalate_grid(g)
            return
        tenant_id, document_id = self._doc_keys[slot]
        # strict wave order at the escalation seam: the doc leaves the
        # device farm only after its last in-flight wave lands
        self._drain_device()
        if self._replay_log is None:
            # degrading to an empty replica would silently lose the doc
            raise RuntimeError(
                f"doc {tenant_id}/{document_id} needs host escalation but no "
                "replay source is configured (set_replay_source)")
        self.host_escalations += 1
        self._marks.dirty.add(slot)
        row = self._long_rows.pop(slot, None)
        if row is not None:
            self._long_free.append(row)
        replica = MergeTreeClient(f"tpu-applier/{tenant_id}/{document_id}")
        self._host_docs[slot] = replica
        self._drop_staged(slot)
        for m in self._replay_log(tenant_id, document_id):
            if m.type == MessageType.OPERATION:
                replica.apply_msg(m, local=False)
        self._applied_seq[slot] = max(self._applied_seq.get(slot, 0),
                                      replica.tree.current_seq)
        # deliberately NOT anchored: the applier cannot verify the replay
        # source yielded the doc's whole history (a summary-aware source
        # starts at the summary) — the summarizer gate must re-prove
        # coverage before trusting this replica for a service summary
        self._anchored.discard(slot)
        if msg is not None:
            self._apply_host(slot, msg, wire_op)

    def _apply_host(self, slot: int, msg, wire_op) -> None:
        replica = self._host_docs[slot]
        if msg.sequence_number <= replica.tree.current_seq:
            return  # already covered by the escalation replay
        replica.apply_msg(replace(msg, contents=wire_op), local=False)

    # the host replay source: fn(tenant, doc) -> [SequencedDocumentMessage]
    # of CHANNEL-LEVEL merge-tree messages; wired by the service host
    _replay_log = None

    def set_replay_source(self, fn) -> None:
        self._replay_log = fn


# ----------------------------------------------------------- checkpointing
#
# A checkpoint at ``path`` is a BASE and the DELTAS written after it.
#
# - The base: ``path.json``, the sidecar (arenas, tables, placement, host
#   docs; ``gen`` names the arrays' file and ``delta_seq`` the last delta
#   the base covers), beside ``path.g{gen}.npz``, every [D, S] state array
#   compressed. ``save_applier_checkpoint`` writes one: the arrays to the
#   other generation's file, then the sidecar renamed into place. The
#   rename is the commit point; the previous pair survives until then.
# - A delta: one framed record (``u32 length | u32 crc32 | payload``, the
#   framing of protocol/refgraph.py) appended to ``path.delta.{first}``,
#   where ``first`` numbers the file's first record. It holds the state
#   rows of the slots that changed since the previous checkpoint, what
#   the sidecar holds for each of them, and what the property table grew
#   by: its cost follows the docs that moved, not the farm.
#
# The long tier rides beside the farm: its [L, S_long] arrays as
# ``long_<field>`` in the base's ``.npz``, its rows in a delta after the
# farm's, and ``long`` in the sidecar ({docs, slots, rows: {row: [slot,
# tenant, doc]}}). A promoted doc leaves the sidecar's ``doc_keys``; the
# farm's arrays and ``doc_keys`` keep the layout a farm-only reader
# expects.
#
# ``load_applier_checkpoint`` loads the base, then replays the deltas
# numbered past its ``delta_seq``, in order, up to the first one missing
# or torn. A torn tail is a delta that never committed; the stage writes
# the offsets it covers only after the append. ``ApplierCheckpointer``
# (the applier stage's) writes a base at start and stop, and once the
# deltas since the newest base outgrow the state's raw bytes; that one
# is snapshotted on the caller's thread and compressed, written and
# committed on its own, and only then are the delta files it covers
# removed. Whichever base is on disk, the deltas past it are there.

_STATE_FIELDS = ("length", "text_start", "flags", "ins_seq", "ins_client",
                 "rem_seq", "rem_client_a", "rem_client_b", "prop_key",
                 "prop_val", "count", "overflow")

#: per-doc fields; the others are per slot, and a slot not in use holds
#: ``_SLOT_FILL`` (0 where not named: DocState.empty's values)
_DOC_FIELDS = ("count", "overflow")
_SLOT_FILL = {"ins_client": NO_CLIENT, "rem_seq": NO_SEQ,
              "rem_client_a": NO_CLIENT, "rem_client_b": NO_CLIENT,
              "prop_key": NO_KEY}

#: the sidecar's per-slot tables a delta carries an entry of for each
#: slot it holds (None: the slot has none)
_SLOT_TABLES = ("client_ids", "applied_seq", "first_seq", "restore_applied")

_DELTA_HEAD = struct.Struct(">I")  # payload: header length, JSON, rows


class _CheckpointMarks:
    """What changed in an applier since its last checkpoint: the slots
    touched (ingest, escalation, placement, anchoring), each arena's
    length and the property table's size when it was taken, and the
    number of the last delta its state reflects."""

    __slots__ = ("dirty", "promoted", "grids", "arena_len", "props", "seq")

    def __init__(self, max_docs: int):
        self.dirty: set[int] = set()
        # grids whose sidecar changed: added, handles allocated, escalated
        self.grids: set[int] = set()
        # moved to the long tier since: the delta carries their emptied
        # farm rows once
        self.promoted: set[int] = set()
        self.arena_len = [0] * max_docs
        self.props = (0, 0)
        self.seq = 0

    def reset(self, applier: "TpuDocumentApplier") -> None:
        """Everything the applier holds is saved."""
        self.dirty.clear()
        self.promoted.clear()
        self.grids.clear()
        self.arena_len = [len(a) for a in applier.arenas]
        snap = applier.prop_table.snapshot()
        self.props = (len(snap["keys"]), len(snap["vals"]))


#: key prefix of the long tier's arrays in a base's ``.npz``
LONG_PREFIX = "long_"
#: a base's grid cells, sparse: ``grid_idx`` (flat index
#: ``(g * H + rh) * H + ch``, int64) and ``grid_val`` (int32)
GRID_PREFIX = "grid_"


def _last_writes(idx: np.ndarray, val: np.ndarray
                 ) -> tuple[np.ndarray, np.ndarray]:
    """Of cell writes (flat index, value) in the order made, the last
    write of each cell."""
    _, last = np.unique(idx[::-1], return_index=True)
    pick = len(idx) - 1 - last
    return idx[pick], val[pick]


def _grid_entry(applier: "TpuDocumentApplier", g: int) -> list:
    """A grid's sidecar: [row slot, col slot, row handles, col handles,
    [host snapshot, host seq] or None]."""
    host = applier._host_grids.get(g)
    return [*applier._grid_slots[g], *applier._grid_next[g],
            None if host is None else [host.snapshot(),
                                       applier._host_grid_seq[g]]]


def _grid_base(applier: "TpuDocumentApplier") -> tuple[dict, int]:
    """A base's grid arrays (every cell written, read back from the
    store) and their bytes; nothing without grids."""
    if applier.grid_cells is None:
        return {}, 0
    idx, val = applier._grid_store_values()
    return ({GRID_PREFIX + "idx": idx, GRID_PREFIX + "val": val},
            idx.nbytes + val.nbytes)


def _device_get(applier: "TpuDocumentApplier", long: bool = True) -> dict:
    """Every state array on the host, in one fixed-shape transfer; with
    ``long``, the long tier's too, under ``LONG_PREFIX``."""
    tree = {f: getattr(applier.state, f) for f in _STATE_FIELDS}
    if long and applier.long_state is not None:
        tree.update({LONG_PREFIX + f: getattr(applier.long_state, f)
                     for f in _STATE_FIELDS})
    return jax.device_get(tree)


def _long_nbytes(arrays: dict) -> int:
    return sum(a.nbytes for k, a in arrays.items()
               if k.startswith(LONG_PREFIX))


def save_applier_checkpoint(applier: "TpuDocumentApplier",
                            path: str) -> int:
    """Persist the applier's device-resident farm to disk as a base: the
    [D, S] state arrays plus the host sidecars (text arenas, property
    interning, client tables, placement). A warm restart loads this instead of
    replaying every doc's op log through escalation — at 10k docs that is
    the difference between milliseconds and minutes (the applier analog
    of deli's Mongo checkpoint, SURVEY §5.4).

    Fences the state first (``finalize()``); host-mode docs are
    serialized as their oracle snapshots. Returns the bytes written
    (the arrays' file and the JSON sidecar).
    """
    with span("checkpoint.save"):
        applier.finalize()
        with span("checkpoint.device_get"):
            arrays = _device_get(applier)
            arrays.update(_grid_base(applier)[0])
        return _write_base(arrays, _base_meta(applier), path)


def _base_meta(applier: "TpuDocumentApplier") -> dict:
    """A base's sidecar, copied out of the applier on its own thread: a
    worker may write it later, while the applier moves on. ``delta_seq``
    is the last delta taken; a save changes nothing of the applier."""
    meta = {
        "max_docs": applier.max_docs,
        "max_slots": applier.max_slots,
        "arenas": [a.text() for a in applier.arenas],
        "prop_table": applier.prop_table.snapshot(),
        "client_ids": {str(k): dict(v)
                       for k, v in applier._client_ids.items()},
        "doc_keys": {str(k): list(v) for k, v in applier._doc_keys.items()
                     if k not in applier._long_rows},
        "placement": applier.placement.snapshot(),
        "host_docs": {str(k): replica.snapshot()
                      for k, replica in applier._host_docs.items()},
        "host_doc_names": {str(k): list(applier._doc_keys[k])
                           for k in applier._host_docs},
        "applied_seq": {str(k): v
                        for k, v in applier._applied_seq.items()},
        "first_seq": {str(k): v for k, v in applier._first_seq.items()},
        "anchored": sorted(applier._anchored),
        # a still-PENDING restart window must survive the save: without
        # it, a save/load cycle would silently discharge an unverified
        # window (load resets gap_lo to the current applied seq, hiding
        # any downtime ops below it from the summarizer's gate)
        "restore_applied": {str(k): v
                            for k, v in applier._restore_applied.items()},
        "delta_seq": applier._marks.seq,
    }
    if applier.long_state is not None:
        meta["long"] = {
            "docs": applier.long_docs, "slots": applier.long_slots,
            "rows": {str(row): [slot, *applier._doc_keys[slot]]
                     for slot, row in applier._long_rows.items()}}
    if applier._grid_slots:
        meta["grids"] = {
            "grids": applier.grids, "handles": applier.grid_handles,
            "docs": {str(g): _grid_entry(applier, g)
                     for g in range(len(applier._grid_slots))}}
    return meta


def _write_base(arrays: dict, meta: dict, path: str) -> int:
    """Write and commit a base (thread-agnostic: it reads nothing of the
    applier); returns the bytes written."""
    # crash-atomic commit: a saver can be SIGKILLed mid-write, and a torn
    # .npz must never be what a restart loads. The arrays go to an
    # alternating generation file; the .json (which NAMES the generation)
    # is renamed into place last.
    gen = 0
    try:
        with open(path + ".json") as f:
            gen = 1 - int(json.load(f).get("gen", 0))
    except (OSError, ValueError):
        pass
    npz_path = f"{path}.g{gen}.npz"
    with span("checkpoint.write_arrays"):
        with open(npz_path + ".tmp", "wb") as f:
            np.savez_compressed(f, **arrays)
        os.replace(npz_path + ".tmp", npz_path)
    with span("checkpoint.write_meta"):
        with open(path + ".json.tmp", "w") as f:
            json.dump({**meta, "gen": gen}, f)
        size = os.path.getsize(path + ".json.tmp")
        os.replace(path + ".json.tmp", path + ".json")
    return os.path.getsize(npz_path) + size


def _delta_payload(applier: "TpuDocumentApplier", arrays: dict
                   ) -> tuple[bytes, int, int, int]:
    """One delta's payload: the rows of the slots marked since the last
    checkpoint, taken from the host copy ``arrays``, and their sidecar;
    marks them saved. A doc on the long tier ships its long row (after
    the farm's rows), and its farm row only in the delta that follows
    its promotion. A grid's cells written since ship as (flat index,
    value) rows after those, and its changed sidecar in the header.
    Returns it, the number of slots, the long rows' bytes and the grid
    cells' bytes."""
    m = applier._marks
    slots = sorted(m.dirty)
    on_long = applier._long_rows
    farm_slots = [s for s in slots if s not in on_long or s in m.promoted]
    long_slots = [s for s in slots if s in on_long]
    snap = applier.prop_table.snapshot()
    keys0, vals0 = m.props
    sidecar = []
    for s in slots:
        arena = applier.arenas[s]
        host = applier._host_docs.get(s)
        key = applier._doc_keys.get(s)
        sidecar.append({
            "arena": [m.arena_len[s], arena.tail(m.arena_len[s])],
            "doc_key": list(key) if key and s not in on_long else None,
            "long_row": on_long.get(s),
            "long_key": list(key) if s in on_long else None,
            "host_doc": host.snapshot() if host is not None else None,
            "anchored": s in applier._anchored,
            "client_ids": applier._client_ids.get(s),
            "applied_seq": applier._applied_seq.get(s),
            "first_seq": applier._first_seq.get(s),
            "restore_applied": applier._restore_applied.get(s)})
        m.arena_len[s] = len(arena)
    idx = np.asarray(farm_slots, np.int64)
    rows = [np.ascontiguousarray(arrays[f][idx]) for f in _STATE_FIELDS]
    long_bytes = width = 0
    if long_slots:
        lidx = np.asarray([on_long[s] for s in long_slots], np.int64)
        # a row's slots from its count on hold the empty fill (every step
        # ends in zamboni's repack): ship the used prefix of the rows
        width = int(arrays[LONG_PREFIX + "count"][lidx].max())
        long_rows = [np.ascontiguousarray(
            arrays[LONG_PREFIX + f][lidx] if f in _DOC_FIELDS
            else arrays[LONG_PREFIX + f][lidx, :width])
            for f in _STATE_FIELDS]
        long_bytes = sum(r.nbytes for r in long_rows)
        rows += long_rows
    grid_bytes = 0
    if applier.grid_cells is not None:
        cells = applier._take_grid_turn()
        if len(cells[0]):
            rows += list(cells)
            grid_bytes = cells[0].nbytes + cells[1].nbytes
    grids = {str(g): _grid_entry(applier, g) for g in sorted(m.grids)}
    m.seq += 1
    m.dirty.clear()
    m.promoted.clear()
    m.grids.clear()
    m.props = (len(snap["keys"]), len(snap["vals"]))
    head = {
        "seq": m.seq, "slots": slots, "sidecar": sidecar,
        "props": [keys0, vals0, snap["keys"][keys0:], snap["vals"][vals0:]],
        "rows": [[r.dtype.str, list(r.shape)] for r in rows]}
    if farm_slots != slots:
        head["farm_slots"] = farm_slots
    if long_slots:
        head["long_slots"], head["long_width"] = long_slots, width
    if grids:
        head["grids"] = grids
        head["grid_meta"] = [applier.grids, applier.grid_handles]
    if grid_bytes:
        head["grid_cells"] = len(rows[-1])
    head = json.dumps(head).encode()
    return (b"".join([_DELTA_HEAD.pack(len(head)), head]
                     + [r.tobytes() for r in rows]), len(slots), long_bytes,
            grid_bytes)


def _decode_delta(payload: bytes) -> tuple[dict, list]:
    """(header, rows) of one delta's payload; raises on a malformed one."""
    (hlen,) = _DELTA_HEAD.unpack_from(payload)
    off = _DELTA_HEAD.size + hlen
    head = json.loads(payload[_DELTA_HEAD.size:off])
    rows = []
    for dtype, shape in head["rows"]:
        dtype = np.dtype(dtype)
        n = int(np.prod(shape))
        rows.append(np.frombuffer(payload, dtype, n, off).reshape(shape))
        off += n * dtype.itemsize
    tiers = 2 if head.get("long_slots") else 1
    grid = 2 if head.get("grid_cells") else 0
    if off != len(payload) or len(rows) != tiers * len(_STATE_FIELDS) + grid:
        raise ValueError("delta rows do not fill the record")
    return head, rows


def _delta_files(path: str) -> list[tuple[int, str]]:
    """(first delta number, file) of every delta file of ``path``, in
    order."""
    directory = os.path.dirname(path)
    prefix = os.path.basename(path) + ".delta."
    out = []
    for name in os.listdir(directory or "."):
        if name.startswith(prefix) and name[len(prefix):].isdigit():
            out.append((int(name[len(prefix):]),
                        os.path.join(directory, name)))
    return sorted(out)


def _deltas_after(path: str, seq: int) -> list[tuple[dict, list]]:
    """The intact deltas numbered past ``seq``, in order, up to the first
    one missing or torn."""
    found = {}
    for _first, name in _delta_files(path):
        with open(name, "rb") as f:
            records, _clean = scan_records(f.read(), decode=_decode_delta)
        for head, rows in records:
            found[head["seq"]] = (head, rows)
    out = []
    while seq + 1 in found:
        seq += 1
        out.append(found[seq])
    return out


def _put(table: dict, key: str, value) -> None:
    if value is None:
        table.pop(key, None)
    else:
        table[key] = value


def _replay_delta(meta: dict, arrays: dict, head: dict, rows: list) -> None:
    """Apply one delta to a base's sidecar (in its JSON form) and to its
    host arrays."""
    table = meta["prop_table"]
    keys0, vals0, keys, vals = head["props"]
    if (len(table["keys"]), len(table["vals"])) != (keys0, vals0):
        raise ValueError(f"delta {head['seq']} does not follow the "
                         "checkpoint it is loaded onto")
    table["keys"] += keys
    table["vals"] += vals
    idx = np.asarray(head.get("farm_slots", head["slots"]), np.int64)
    for f, r in zip(_STATE_FIELDS, rows):
        arrays[f][idx] = r
    side_of = dict(zip(head["slots"], head["sidecar"]))
    if head.get("long_slots"):
        lidx = np.asarray([side_of[s]["long_row"]
                           for s in head["long_slots"]], np.int64)
        width = head["long_width"]
        for f, r in zip(_STATE_FIELDS, rows[len(_STATE_FIELDS):]):
            a = arrays[LONG_PREFIX + f]
            if f in _DOC_FIELDS:
                a[lidx] = r
            else:
                a[lidx, :width] = r
                a[lidx, width:] = _SLOT_FILL.get(f, 0)
    if head.get("grids"):
        G, H = head["grid_meta"]
        meta.setdefault("grids", {"grids": G, "handles": H, "docs": {}})[
            "docs"].update(head["grids"])
    if head.get("grid_cells"):
        idx, val = rows[-2:]
        arrays[GRID_PREFIX + "idx"] = np.concatenate(
            [arrays.get(GRID_PREFIX + "idx", np.zeros(0, np.int64)), idx])
        arrays[GRID_PREFIX + "val"] = np.concatenate(
            [arrays.get(GRID_PREFIX + "val", np.zeros(0, np.int32)), val])
    long_rows = meta["long"]["rows"] if "long" in meta else {}
    sps = meta["placement"]["slots_per_shard"]
    anchored = set(meta.get("anchored", ()))
    for slot, side in side_of.items():
        k = str(slot)
        start, tail = side["arena"]
        if len(meta["arenas"][slot]) != start:
            raise ValueError(f"delta {head['seq']}: arena {slot} holds "
                             f"{len(meta['arenas'][slot])} chars, not "
                             f"{start}")
        meta["arenas"][slot] += tail
        # a doc on the long tier is named by its long row, not doc_keys
        long_key = side.get("long_key")
        key = side["doc_key"] or long_key
        if key is not None:
            meta["placement"]["map"][DocPlacement.key(*key)] = [
                slot // sps, slot % sps]
        _put(meta["doc_keys"], k, side["doc_key"])
        for row in [r for r, v in long_rows.items() if v[0] == slot]:
            del long_rows[row]
        if long_key is not None:
            long_rows[str(side["long_row"])] = [slot, *long_key]
        _put(meta["host_docs"], k, side["host_doc"])
        _put(meta["host_doc_names"], k,
             key if side["host_doc"] is not None else None)
        for name in _SLOT_TABLES:
            _put(meta.setdefault(name, {}), k, side[name])
        (anchored.add if side["anchored"] else anchored.discard)(slot)
    meta["anchored"] = sorted(anchored)


class CheckpointWrite(NamedTuple):
    """What one call of :class:`ApplierCheckpointer` wrote."""

    nbytes: int  # the delta's or base's, plus each base committed since
    docs: int    # slots the delta holds
    bases: int   # bases committed by the call, or since the last call
    long_bytes: int = 0  # the long tier's rows in it (raw, in a base)
    grid_bytes: int = 0  # the grid cells in it (sparse, in a base too)


class ApplierCheckpointer:
    """The base + delta checkpoint of one applier at ``path`` (the
    section comment above): ``base()`` at start and stop, ``delta()``
    after each turn that moved docs. A base that the deltas' growth makes
    due is written on the ``applier-base`` thread, off the caller's
    turn."""

    def __init__(self, applier: "TpuDocumentApplier", path: str):
        self.applier, self.path = applier, path
        #: a base is due once the deltas written since the newest one
        #: hold more than the state itself: its raw bytes, a geometry
        self.limit = sum(a.nbytes for a in jax.tree.leaves(applier.state))
        #: the long tier's raw bytes: its share of each base
        self.long_raw = sum(a.nbytes for a in
                            jax.tree.leaves(applier.long_state))
        self.limit += self.long_raw
        self._since_base = 0
        self._file: Optional[str] = None  # the delta file appended to
        # the applier-base thread while a base is in flight, and where it
        # leaves the base's bytes or what it raised
        self._writer: Optional[threading.Thread] = None
        self._ended: queue.SimpleQueue = queue.SimpleQueue()

    def base(self) -> CheckpointWrite:
        """Write a base now, on this thread, after any in flight; every
        delta file is then covered, and removed."""
        nbytes, bases = self._collect(wait=True)
        nbytes += save_applier_checkpoint(self.applier, self.path)
        # the deltas after it start from here (a save of the applier
        # elsewhere leaves the marks to their owner, this chain)
        self.applier._marks.reset(self.applier)
        self.applier._grid_turn = []  # the base holds every cell written
        self._file, self._since_base = None, 0
        self._remove_deltas(None)
        # int64 + int32 a cell the base read back
        grid_bytes = 12 * self.applier.grid_cells_held
        return CheckpointWrite(nbytes, 0, bases + 1,
                               (bases + 1) * self.long_raw, grid_bytes)

    def delta(self) -> CheckpointWrite:
        """Append one delta of the slots changed since the last
        checkpoint (nothing when none did). Call on the applier's
        thread."""
        nbytes, bases = self._collect(wait=False)
        a = self.applier
        a.finalize()
        if not a._marks.dirty and not a._grid_pending \
                and not a._grid_turn:
            return CheckpointWrite(nbytes, 0, bases, bases * self.long_raw)
        with span("checkpoint.delta"):
            with span("checkpoint.delta.device_get"):
                # the long tier's arrays only when one of its docs moved
                arrays = _device_get(a, long=not a._marks.dirty.isdisjoint(
                    a._long_rows))
            payload, docs, long_bytes, grid_bytes = _delta_payload(a, arrays)
            with span("checkpoint.delta.write"):
                frame = frame_record(payload)
                mode = "ab"
                if self._file is None:
                    # a new file starts at this delta; "wb" drops a torn
                    # file a crash may have left under the same name
                    self._file, mode = f"{self.path}.delta.{a._marks.seq}", "wb"
                with open(self._file, mode) as f:
                    f.write(frame)
        self._since_base += len(frame)
        if self._since_base > self.limit and self._writer is None:
            if a.long_state is not None and not _long_nbytes(arrays):
                arrays.update(_device_get(a, long=True))
            cells, nbytes_grid = _grid_base(a)
            arrays.update(cells)
            grid_bytes += nbytes_grid
            self._start_base(arrays)
        return CheckpointWrite(nbytes + len(frame), docs, bases,
                               long_bytes + bases * self.long_raw,
                               grid_bytes)

    def _start_base(self, arrays: dict) -> None:
        """Snapshot a base here (the host arrays the delta just took, and
        copies of the sidecar: it covers that delta, which left nothing
        marked); compress, write and commit it on the applier-base
        thread. Later deltas go to a new file."""
        meta = _base_meta(self.applier)
        self._file, self._since_base = None, 0
        self._writer = threading.Thread(
            target=self._base_worker, args=(arrays, meta),
            name="applier-base", daemon=True)
        self._writer.start()

    @blocking("compresses and writes a whole base (~0.2-0.4 s at 1k x 256): the applier-base thread's work, off the stage's turn")
    def _base_worker(self, arrays: dict, meta: dict) -> None:
        try:
            with span("checkpoint.save"):
                nbytes = _write_base(arrays, meta, self.path)
            self._remove_deltas(meta["delta_seq"])
        except Exception as e:  # handed to the caller's next checkpoint
            self._ended.put(e)
        else:
            self._ended.put(nbytes)

    def _collect(self, wait: bool) -> tuple[int, int]:
        """(bytes, 1) of the base the applier-base thread has ended, and
        (0, 0) when none; with ``wait`` it waits for one in flight.
        Raises what the thread raised."""
        if self._writer is None or (not wait and self._writer.is_alive()):
            return 0, 0
        self._writer.join()
        self._writer = None
        out = self._ended.get()
        if isinstance(out, BaseException):
            raise out
        return out, 1

    def _remove_deltas(self, upto: Optional[int]) -> None:
        """Remove the delta files whose records are all numbered up to
        ``upto`` (every file when None): a committed base covers them."""
        for first, name in _delta_files(self.path):
            if upto is None or first <= upto:
                os.remove(name)


def load_applier_checkpoint(path: str, **applier_kwargs
                            ) -> "TpuDocumentApplier":
    """Rebuild a fenced applier from its base and the deltas after it."""
    from ..ops.doc_state import DocState as _DS

    with open(path + ".json") as f:
        meta = json.load(f)
    # generation-named arrays (crash-atomic saver); plain ".npz" is the
    # legacy single-generation layout (tests/golden pins it loadable)
    npz_path = (f"{path}.g{meta['gen']}.npz" if "gen" in meta
                else path + ".npz")
    with np.load(npz_path) as data:
        arrays = {k: data[k] for k in data.files}
    seq = meta.get("delta_seq", 0)
    for head, rows in _deltas_after(path, seq):
        _replay_delta(meta, arrays, head, rows)
        seq = head["seq"]
    long = meta.get("long")
    if long is not None:
        applier_kwargs = {**applier_kwargs, "long_docs": long["docs"],
                          "long_slots": long["slots"]}
    grids = meta.get("grids")
    if grids is not None:
        applier_kwargs = {**applier_kwargs, "grids": grids["grids"],
                          "grid_handles": grids["handles"]}
    applier = TpuDocumentApplier(max_docs=meta["max_docs"],
                                 max_slots=meta["max_slots"],
                                 **applier_kwargs)
    applier.state = _DS(**{k: jnp.asarray(v) for k, v in arrays.items()
                           if not k.startswith((LONG_PREFIX, GRID_PREFIX))})
    if long is not None:
        applier.long_state = _DS(**{
            k[len(LONG_PREFIX):]: jnp.asarray(v) for k, v in arrays.items()
            if k.startswith(LONG_PREFIX)})
        for row, (slot, *key) in long["rows"].items():
            applier._long_rows[slot] = int(row)
            applier._doc_keys[slot] = tuple(key)
        applier._long_free = [r for r in range(long["docs"] - 1, -1, -1)
                              if r not in applier._long_rows.values()]
    if applier.long_state is not None:
        applier._count_hi[:] = arrays["count"]
    if applier._mesh is not None:
        # a mesh applier's step requires state committed per P("docs");
        # without this re-shard the first dispatch would silently pay an
        # XLA relayout of every state array (or fail under shard_map)
        from ..parallel.sharded_apply import shard_state

        applier.state = shard_state(applier.state, applier._mesh)
    for slot, text in enumerate(meta["arenas"]):
        arena = TextArena()
        if text:
            arena.append(text)
        applier.arenas[slot] = arena
    applier.prop_table = PropTable.load(meta["prop_table"])
    applier._client_ids = {int(k): dict(v)
                           for k, v in meta["client_ids"].items()}
    applier._doc_keys.update({int(k): tuple(v)
                              for k, v in meta["doc_keys"].items()})
    placement = DocPlacement.load(meta["placement"])
    if applier._mesh is not None and \
            placement.n_shards != applier.placement.n_shards:
        # the row↔device mapping is shard-major: restoring a checkpoint
        # onto a mesh with a different docs axis would route every doc
        # to the wrong device's rows
        raise ValueError(
            f"checkpoint placement has {placement.n_shards} shards but "
            f"the mesh's docs axis is {applier.placement.n_shards}")
    applier.placement = placement
    for k, snap in meta["host_docs"].items():
        tenant_id, document_id = meta["host_doc_names"][k]
        applier._host_docs[int(k)] = MergeTreeClient.load(
            f"tpu-applier/{tenant_id}/{document_id}", snap)
    applier._applied_seq = {int(k): v for k, v in
                            meta.get("applied_seq", {}).items()}
    applier._first_seq = {int(k): v for k, v in
                          meta.get("first_seq", {}).items()}
    # a checkpoint written by pre-coverage-tracking code carries no
    # anchor set; such slots restore UNANCHORED and the summarizer
    # refuses until coverage is re-proven — safe, never lossy
    applier._anchored = set(meta.get("anchored", []))
    # restored anchors are conditional: the summarizer additionally
    # verifies no ops were sequenced in the restart window (restore_gap)
    applier._restore_applied = dict(applier._applied_seq)
    # compose with any window the CHECKPOINT itself left unverified:
    # keep the older low bound, so the gate inspects the union
    # (old_lo, new_hi) — conservative (may refuse ops the saved state
    # actually covers, between the old resume point and the save), but
    # never discharges a real hole
    for k, v in meta.get("restore_applied", {}).items():
        slot = int(k)
        applier._restore_applied[slot] = min(
            v, applier._restore_applied.get(slot, v))
    if grids is not None:
        _restore_grids(applier, grids, arrays)
    applier._marks.reset(applier)
    applier._marks.seq = seq
    return applier


def _restore_grids(applier: "TpuDocumentApplier", grids: dict,
                   arrays: dict) -> None:
    """The grids of a loaded checkpoint: their slots, handle counts and
    host replicas, and the device store rebuilt from the cells written
    (the last write of each)."""
    for g in range(len(grids["docs"])):
        rs, cs, nr, nc, host = grids["docs"][str(g)]
        applier._grid_slots.append((rs, cs))
        applier._grid_next.append([nr, nc])
        applier._vec_grid[rs] = applier._vec_grid[cs] = g
        applier.grid_handles_peak = max(applier.grid_handles_peak, nr, nc)
        if host is not None:
            replica = SharedMatrix(GRID_CHANNEL)
            replica.load_core(host[0])
            applier._host_grids[g] = replica
            applier._host_grid_seq[g] = host[1]
    idx = arrays.get(GRID_PREFIX + "idx")
    if idx is None or not len(idx):
        return
    idx, val = _last_writes(idx, arrays[GRID_PREFIX + "val"])
    H = applier.grid_handles
    at = (idx // (H * H), (idx // H) % H, idx % H)
    applier.grid_cells = applier.grid_cells.at[at].set(val)
    applier.grid_written = applier.grid_written.at[at].set(1)
