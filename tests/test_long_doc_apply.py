"""Segment-sharded giant-doc APPLY (SURVEY §5.7 SP analog): the composed
sharded apply must match the single-chip kernel op-for-op on a fuzzed
stream — same content, same stamps, same zamboni result.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fluidframework_tpu.ops.apply import (
    apply_ops_scan,
    compact,
    wave_min_seq,
)
from fluidframework_tpu.ops.doc_state import DocState
from fluidframework_tpu.ops.opgen import generate_batch_ops
from fluidframework_tpu.parallel.long_doc import sharded_apply_ops
from fluidframework_tpu.parallel.mesh import make_mesh

N_SHARDS = 8
S_LOCAL = 64
S_GLOBAL = N_SHARDS * S_LOCAL

SLOT_FIELDS = ("length", "text_start", "flags", "ins_seq", "ins_client",
               "rem_seq", "rem_client_a", "rem_client_b")


def _live_rows(arrs, counts):
    """Ordered logical segment rows (shard-major, used slots only)."""
    rows = []
    if np.isscalar(counts) or counts.ndim == 0:
        counts = np.array([counts])
        arrs = {k: v[None, :] for k, v in arrs.items()}
    for s in range(len(counts)):
        for i in range(int(counts[s])):
            rows.append(tuple(int(arrs[f][s, i]) for f in SLOT_FIELDS))
    return rows


def _run_pair(seed, n_ops, remove_fraction=0.3, annotate_fraction=0.1):
    rng = np.random.default_rng(seed)
    ops = jnp.asarray(generate_batch_ops(
        rng, 1, n_ops, remove_fraction=remove_fraction,
        annotate_fraction=annotate_fraction, max_insert=6)[0])

    # --- single-chip reference
    ref = DocState.empty(S_GLOBAL)
    ref = jax.tree.map(jnp.asarray, ref)
    ref = apply_ops_scan(ref, ops)
    ref = compact(ref, wave_min_seq(ops))
    assert not bool(ref.overflow), "reference overflowed; enlarge slots"

    # --- sharded: per-shard [S_LOCAL] arrays + per-shard counts
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh(N_SHARDS, seg_shards=N_SHARDS)
    empty = DocState.empty(S_LOCAL)
    sharded = DocState(
        **{f: jnp.tile(getattr(empty, f), (N_SHARDS,) +
                       (1,) * getattr(empty, f).ndim)
           for f in SLOT_FIELDS + ("prop_key", "prop_val")},
        count=jnp.zeros(N_SHARDS, jnp.int32),
        overflow=jnp.zeros(N_SHARDS, bool),
    )
    seg = P("seg")
    specs = DocState(
        **{f: seg for f in SLOT_FIELDS + ("prop_key", "prop_val")},
        count=seg, overflow=seg,
    )

    def body(st, ops):
        # strip the leading length-1 shard axis shard_map hands us
        local = jax.tree.map(lambda a: a[0], st)
        out = sharded_apply_ops(local, ops, axis="seg")
        return jax.tree.map(lambda a: a[None], out)

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(specs, P()), out_specs=specs,
        check_vma=False))
    out = fn(sharded, ops)
    counts = np.asarray(out.count)
    assert not np.asarray(out.overflow).any(), "sharded path overflowed"

    ref_rows = _live_rows(
        {f: np.asarray(getattr(ref, f)) for f in SLOT_FIELDS},
        np.asarray(ref.count))
    out_rows = _live_rows(
        {f: np.asarray(getattr(out, f)) for f in SLOT_FIELDS}, counts)
    return ref_rows, out_rows, counts


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_sharded_apply_matches_single_chip(seed):
    ref_rows, out_rows, _ = _run_pair(seed, n_ops=48)
    assert out_rows == ref_rows


def _run_chunked_with_rebalancing(ops_all, chunk=8):
    """Chunked sharded apply with watermark rebalancing between waves,
    against the single-chip reference. Returns (ref_rows, out_rows,
    counts, rebalances)."""
    from jax.sharding import PartitionSpec as P

    from fluidframework_tpu.parallel.long_doc import rebalance_shards

    n_ops = int(ops_all.shape[0])
    # an op can add up to 3 slots, so the rebalance watermark must leave
    # a full chunk's worst-case growth of headroom
    watermark = S_LOCAL - 3 * chunk

    ref = jax.tree.map(jnp.asarray, DocState.empty(S_GLOBAL))
    ref = apply_ops_scan(ref, ops_all)
    ref = compact(ref, wave_min_seq(ops_all))
    assert not bool(ref.overflow)

    mesh = make_mesh(N_SHARDS, seg_shards=N_SHARDS)
    empty = DocState.empty(S_LOCAL)
    all_fields = SLOT_FIELDS + ("prop_key", "prop_val")
    state = DocState(
        **{f: jnp.tile(getattr(empty, f), (N_SHARDS,) +
                       (1,) * getattr(empty, f).ndim) for f in all_fields},
        count=jnp.zeros(N_SHARDS, jnp.int32),
        overflow=jnp.zeros(N_SHARDS, bool),
    )
    seg = P("seg")
    specs = DocState(**{f: seg for f in all_fields}, count=seg, overflow=seg)

    def body(st, ops):
        local = jax.tree.map(lambda a: a[0], st)
        out = sharded_apply_ops(local, ops, axis="seg")
        return jax.tree.map(lambda a: a[None], out)

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(specs, P()), out_specs=specs,
        check_vma=False))

    rebalances = 0
    for i in range(0, n_ops, chunk):
        state = fn(state, ops_all[i:i + chunk])
        assert not np.asarray(state.overflow).any(), f"overflow at op {i}"
        counts = np.asarray(state.count)
        if counts.max() > watermark:
            arrays = {f: np.asarray(getattr(state, f)) for f in all_fields}
            arrays, new_counts = rebalance_shards(arrays, counts)
            state = DocState(
                **{f: jnp.asarray(a) for f, a in arrays.items()},
                count=jnp.asarray(new_counts),
                overflow=jnp.zeros(N_SHARDS, bool),
            )
            rebalances += 1

    counts = np.asarray(state.count)
    ref_rows = _live_rows(
        {f: np.asarray(getattr(ref, f)) for f in SLOT_FIELDS},
        np.asarray(ref.count))
    out_rows = _live_rows(
        {f: np.asarray(getattr(state, f)) for f in SLOT_FIELDS}, counts)
    return ref_rows, out_rows, counts, rebalances


def test_heavy_stream_with_watermark_rebalancing():
    """Mid-doc inserts pile onto the boundary-owning shard; a long
    insert-heavy stream therefore needs host rebalancing between waves
    (the bulk analog of B-tree node splits). Chunked apply with a 75%
    watermark must track the single-chip kernel exactly."""
    rng = np.random.default_rng(7)
    ops_all = jnp.asarray(generate_batch_ops(
        rng, 1, 96, remove_fraction=0.15, annotate_fraction=0.05,
        max_insert=6)[0])
    ref_rows, out_rows, counts, rebalances = \
        _run_chunked_with_rebalancing(ops_all)
    assert out_rows == ref_rows
    assert rebalances >= 1          # the stream really needed it
    assert (counts > 0).sum() > 1   # content spans shards afterwards


@pytest.mark.parametrize("seed", [0, 7, 42])
def test_giant_doc_exceeds_single_shard_budget(seed):
    """Adversarial giant doc (ISSUE 9): ONE doc whose live segment count
    exceeds a single shard's S_LOCAL budget — impossible to hold on one
    seg shard, so the stream only survives via cross-shard rebalancing —
    must still match the single-chip reference row-for-row."""
    rng = np.random.default_rng(seed)
    ops_all = jnp.asarray(generate_batch_ops(
        rng, 1, 128, remove_fraction=0.08, annotate_fraction=0.05,
        max_insert=6)[0])
    ref_rows, out_rows, counts, rebalances = \
        _run_chunked_with_rebalancing(ops_all)
    assert len(ref_rows) > S_LOCAL  # the doc genuinely outgrew one shard
    assert out_rows == ref_rows
    assert rebalances >= 1
    assert counts.max() <= S_LOCAL  # no shard holds more than its budget


def test_rebalance_refuses_when_doc_outgrows_whole_mesh():
    """Past total capacity an even spread no longer fits; silent
    out-of-bounds packing would corrupt shard-major order, so
    rebalance_shards must refuse loudly."""
    from fluidframework_tpu.parallel.long_doc import rebalance_shards

    arrays = {"length": np.ones((2, 4), np.int32)}
    with pytest.raises(ValueError, match="cannot fit"):
        rebalance_shards(arrays, np.array([5, 5], np.int32))
