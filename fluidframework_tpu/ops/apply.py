"""Batched merge-tree delta-apply: the kernel the whole project exists for.

``apply_op`` applies ONE sequenced op to ONE document as pure array math —
masked prefix-sum position resolution at the op's (refSeq, client)
perspective, then a static-shape gather rebuild. ``vmap`` lifts it across
thousands of documents; ``lax.scan`` chains K ops per doc per dispatch.

Server-side invariants that make this simple (see ops/__init__ docstring):
ops arrive in sequence order, so every existing stamp is below the incoming
seq — the concurrent-insert tie-break ("higher seq leftward",
mergeTree.ts:2281 breakTie) reduces to inserting at the EARLIEST boundary,
and overlapping removes keep the earliest stamp automatically. Annotate
LWW-per-key (segmentPropertiesManager.ts) likewise reduces to in-order
overwrite of the per-slot property table.

Every op carries the msn deli stamped on its sequenced message (F_MSN), so
zamboni compaction can run fused after each wave with the exact per-doc
collaboration-window floor — no host-side msn bookkeeping.

Oracle parity is enforced by tests/test_kernel_vs_oracle.py on fuzzed op
streams (the TPU-build analog of PartialSequenceLengths.options.verify,
partialLengths.ts:63).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..utils.contracts import kernel_contract
from .doc_state import NO_KEY, NO_SEQ, DocState

NO_CLIENT = -1
NO_VAL = -1  # annotate value id meaning "delete this key"

# op vector layout (int32[OP_FIELDS])
OP_NOOP = 0
OP_INSERT = 1
OP_REMOVE = 2
OP_ANNOTATE = 3
(
    F_TYPE,
    F_POS,
    F_END,
    F_SEQ,
    F_REFSEQ,
    F_CLIENT,
    F_TLEN,
    F_TSTART,
    F_MSN,
    F_FLAGS,
    F_KEY,
    F_VAL,
) = range(12)
OP_FIELDS = 12


def make_op(
    type: int,
    pos: int = 0,
    end: int = 0,
    seq: int = 0,
    ref_seq: int = 0,
    client: int = 0,
    text_len: int = 0,
    text_start: int = 0,
    msn: int = 0,
    flags: int = 0,
    key: int = 0,
    val: int = 0,
) -> np.ndarray:
    v = np.zeros(OP_FIELDS, np.int32)
    v[F_TYPE], v[F_POS], v[F_END] = type, pos, end
    v[F_SEQ], v[F_REFSEQ], v[F_CLIENT] = seq, ref_seq, client
    v[F_TLEN], v[F_TSTART] = text_len, text_start
    v[F_MSN], v[F_FLAGS] = msn, flags
    v[F_KEY], v[F_VAL] = key, val
    return v


def _visibility(state: DocState, ref_seq, client, count=None):
    """Per-slot visibility at the op's perspective → (vis, vlen, cum).

    The branch-free twin of Segment.visible_in / Perspective (all stamps
    assigned on the server path). ``cum`` is the exclusive prefix sum of
    visible lengths — the masked-prefix-sum replacement for the reference's
    PartialSequenceLengths queries (partialLengths.ts:432).

    ``count`` overrides ``state.count`` for callers whose slot arrays are a
    shard of a larger doc (parallel/long_doc.py passes the local count).
    """
    if count is None:
        count = state.count
    idx = jnp.arange(state.length.shape[-1], dtype=jnp.int32)
    in_use = idx < count
    ins_seen = (state.ins_client == client) | (state.ins_seq <= ref_seq)
    removed = (state.rem_seq != NO_SEQ) & (
        (state.rem_client_a == client)
        | (state.rem_client_b == client)
        | (state.rem_seq <= ref_seq)
    )
    vis = in_use & ins_seen & ~removed
    vlen = jnp.where(vis, state.length, 0)
    cum = jnp.cumsum(vlen) - vlen
    return vis, vlen, cum


_SLOT_FIELDS = (
    "length",
    "text_start",
    "flags",
    "ins_seq",
    "ins_client",
    "rem_seq",
    "rem_client_a",
    "rem_client_b",
    "prop_key",
    "prop_val",
)


def _shift1(a):
    """out[i] = a[i-1] (out[0] is never selected by callers)."""
    return jnp.roll(a, 1, axis=0)


def _shift2(a):
    """out[i] = a[i-2] (out[0..1] are never selected by callers)."""
    return jnp.roll(a, 2, axis=0)


def _fieldwise(state: DocState, fn, count, overflow) -> DocState:
    return DocState(
        **{name: fn(name, getattr(state, name)) for name in _SLOT_FIELDS},
        count=count,
        overflow=overflow,
    )


def _apply_unified(state: DocState, op) -> DocState:
    """One shared path for insert/remove/annotate (noop passes through),
    FUSED: a single visibility/prefix-sum pass and a single roll+select
    rebuild cover both potential splits and the insert shift.

    An op creates at most two new slots — the tail halves of up to two
    splits, or one tail half plus the inserted segment — so every output
    slot is one of {a[o], a[o-1], a[o-2]} (gather-free: static rolls and
    selects vectorize onto the VPU; computed-index gathers are the TPU
    slow path), plus point patches at the split/insert indices. The
    sequential form (split(p1) → split(p2) → insert-shift, each with its
    own visibility recompute) cost 4 prefix-sum passes and 3 full-state
    rebuilds per op; fused it is 1 and 1, which is what sets the K-step
    scan's per-op device cost.

    Semantics (unchanged, fuzz-checked against the scalar oracle):
    - insert lands at the EARLIEST boundary reaching pos (before
      tombstone runs, matching MergeTree.resolve + breakTie);
    - remove mask-stamps covered slots (overlap keeps the earliest
      stamp, later removers recorded as extra remove clients);
    - annotate is LWW per key into per-slot property tables.
    """
    vis, vlen, cum = _visibility(state, op[F_REFSEQ], op[F_CLIENT])
    return _apply_core(state, op, vis, vlen, cum, jnp.sum(vlen))


def _apply_core(
    state: DocState,
    op,
    vis,
    vlen,
    cum,
    total,
    insert_here=True,
    reduce_any=None,
):
    """The unified apply body over PRECOMPUTED visibility.

    Single-doc callers pass locally-computed (vis, vlen, cum, total).
    The segment-sharded giant-doc path (parallel/long_doc.py) passes a
    GLOBAL prefix (local cum + shard offset, global total), masks the
    insert to the boundary-owning shard via ``insert_here``, and supplies
    ``reduce_any`` (a pmax over the 'seg' axis) so a capacity/shape
    problem on ANY shard aborts the op on EVERY shard — the op either
    applies everywhere or flags overflow everywhere.
    """
    if reduce_any is None:
        def reduce_any(x):
            return x
    S = state.max_slots
    typ = op[F_TYPE]
    is_ins = typ == OP_INSERT
    is_rem = typ == OP_REMOVE
    is_ann = typ == OP_ANNOTATE
    active = is_ins | is_rem | is_ann
    pos, end = op[F_POS], op[F_END]
    seq, client = op[F_SEQ], op[F_CLIENT]
    p2 = jnp.where(is_ins, pos, end)

    bad_shape = jnp.where(is_ins, pos > total, (end > total) | (end <= pos))
    inc = cum + vlen

    # split demand: a split happens iff the position falls STRICTLY
    # inside a visible segment (exact on the pre-split state: adding the
    # p1 boundary cannot move p2 strictly into/out of a segment)
    inside1 = vis & (cum < pos) & (pos < inc)
    inside2 = vis & (cum < p2) & (p2 < inc)
    s1_raw = jnp.any(inside1)
    s2_raw = (~is_ins) & jnp.any(inside2)
    needed = (
        s1_raw.astype(jnp.int32)
        + s2_raw.astype(jnp.int32)
        + (is_ins & insert_here).astype(jnp.int32)
    )
    bad = active & reduce_any(bad_shape | (state.count + needed > S))
    ok = active & ~bad
    insert_ok = ok & insert_here
    s1 = s1_raw & ok
    s2 = s2_raw & ok
    do_ins = is_ins & insert_ok

    j1 = jnp.argmax(inside1)
    j2 = jnp.argmax(inside2)
    # split-segment field extracts as one-hot masked sums, NOT a[j]:
    # inside1/inside2 are one-hot (positions strictly inside a visible
    # segment match at most one slot), and a[j] with a batched j lowers
    # to lax.gather under vmap — the computed-index path the kernel
    # contract forbids (tools/fluidlint jaxpr pass, no_gather)
    c1 = jnp.sum(jnp.where(inside1, cum, 0))
    c2 = jnp.sum(jnp.where(inside2, cum, 0))
    o1 = pos - c1
    o2 = p2 - c2
    l1 = jnp.sum(jnp.where(inside1, state.length, 0))
    ts1 = jnp.sum(jnp.where(inside1, state.text_start, 0))
    l2 = jnp.sum(jnp.where(inside2, state.length, 0))
    ts2 = jnp.sum(jnp.where(inside2, state.text_start, 0))
    same = s1 & s2 & (j1 == j2)  # both splits inside one segment

    # output indices of the new/patched slots
    s1i = s1.astype(jnp.int32)
    idx0 = jnp.argmax(cum >= pos)  # earliest boundary (unused slots keep
    # cum == total, so append-at-end resolves to the first free slot)
    p_ins = jnp.where(s1, j1 + 1, idx0)  # new insert slot
    p_n1 = jnp.where(do_ins, p_ins + 1, j1 + 1)  # tail half of split 1
    p_h2 = j2 + s1i  # original j2 (head half of split 2), shifted past n1
    p_n2 = j2 + 1 + s1i  # tail half of split 2

    i = jnp.arange(S, dtype=jnp.int32)
    # shift = how many new slots sit at/before each output index
    delta = (
        (s1 & (i >= p_n1)).astype(jnp.int32)
        + (s2 & (i >= p_n2)).astype(jnp.int32)
        + (do_ins & (i >= p_ins)).astype(jnp.int32)
    )
    d1 = delta == 1
    d2 = delta == 2
    head1_at = s1 & (i == j1)
    n1_at = s1 & (i == p_n1)
    h2_at = s2 & ~same & (i == p_h2)
    n2_at = s2 & (i == p_n2)
    new_at = do_ins & (i == p_ins)

    tlen, tstart = op[F_TLEN], op[F_TSTART]
    new_vals = {
        "length": jnp.where(tlen > 0, tlen, 1),
        "text_start": tstart,
        "flags": op[F_FLAGS],
        "ins_seq": seq,
        "ins_client": client,
        "rem_seq": NO_SEQ,
        "rem_client_a": NO_CLIENT,
        "rem_client_b": NO_CLIENT,
    }
    # length/text_start patches for the four split-derived slots
    n1_len = jnp.where(same, o2 - o1, l1 - o1)
    patch_len = [(head1_at, o1), (n1_at, n1_len), (h2_at, o2),
                 (n2_at, l2 - o2)]
    patch_ts = [(n1_at, ts1 + o1), (n2_at, ts2 + o2)]

    def rebuild(name, a):
        if a.ndim == 2:  # prop tables: roll rows, new insert slot empty
            fill = NO_KEY if name == "prop_key" else 0
            out = jnp.where(d1[:, None], _shift1(a),
                            jnp.where(d2[:, None], _shift2(a), a))
            return jnp.where(new_at[:, None], fill, out)
        out = jnp.where(d1, _shift1(a), jnp.where(d2, _shift2(a), a))
        if name == "length":
            for mask, val in patch_len:
                out = jnp.where(mask, val, out)
        elif name == "text_start":
            for mask, val in patch_ts:
                out = jnp.where(mask, val, out)
        return jnp.where(new_at, new_vals[name], out) if name in new_vals \
            else out

    st = _fieldwise(
        state,
        rebuild,
        count=state.count + s1i + s2.astype(jnp.int32)
        + do_ins.astype(jnp.int32),
        overflow=state.overflow,
    )

    # ---- remove/annotate target mask, on ROLLED perspective arrays (no
    # second prefix pass). The insert slot never matters here: do_ins
    # excludes is_rem/is_ann, so the mask is dead in that case.
    vis_out = jnp.where(d1, _shift1(vis), jnp.where(d2, _shift2(vis), vis))
    cum_out = jnp.where(d1, _shift1(cum), jnp.where(d2, _shift2(cum), cum))
    cum_out = jnp.where(n1_at, c1 + o1, cum_out)
    cum_out = jnp.where(n2_at, c2 + o2, cum_out)
    vlen_out = jnp.where(vis_out, st.length, 0)
    covered = vis_out & (cum_out >= pos) & (cum_out + vlen_out <= end)
    rm = is_rem & ~bad & covered
    fresh = rm & (st.rem_seq == NO_SEQ)
    # overlap: ops apply in seq order so the existing stamp is the
    # earliest; just record this client as an additional remover
    over = rm & (st.rem_seq != NO_SEQ)
    add_b = over & (st.rem_client_a != client) & (st.rem_client_b == NO_CLIENT)
    third = over & (st.rem_client_a != client) & (st.rem_client_b != client) & (
        st.rem_client_b != NO_CLIENT
    )

    # ---- annotate: per-key LWW write (val == NO_VAL deletes the key)
    key, val = op[F_KEY], op[F_VAL]
    P = state.max_props
    an = is_ann & ~bad & covered
    match = st.prop_key == key  # [S, P]
    has_key = jnp.any(match, axis=-1)
    empty = st.prop_key == NO_KEY
    has_empty = jnp.any(empty, axis=-1)
    tgt = jnp.where(has_key, jnp.argmax(match, axis=-1), jnp.argmax(empty, axis=-1))
    is_delete = val == NO_VAL
    do_write = an & (has_key | (~is_delete & has_empty))
    onehot = (jnp.arange(P, dtype=jnp.int32)[None, :] == tgt[:, None]) & do_write[
        :, None
    ]
    # a slot that needs a (P+1)th distinct key cannot hold it → escalate
    table_full = jnp.any(an & ~has_key & ~has_empty & ~is_delete)

    return DocState(
        length=st.length,
        text_start=st.text_start,
        flags=st.flags,
        ins_seq=st.ins_seq,
        ins_client=st.ins_client,
        rem_seq=jnp.where(fresh, seq, st.rem_seq),
        rem_client_a=jnp.where(fresh, client, st.rem_client_a),
        rem_client_b=jnp.where(add_b, client, st.rem_client_b),
        prop_key=jnp.where(onehot, jnp.where(is_delete, NO_KEY, key), st.prop_key),
        prop_val=jnp.where(onehot, jnp.where(is_delete, 0, val), st.prop_val),
        count=st.count,
        overflow=st.overflow | jnp.any(third) | table_full | bad,
    )


def apply_op(state: DocState, op) -> DocState:
    """Apply one sequenced op vector (int32[OP_FIELDS]) to one doc."""
    return _apply_unified(state, op)


# [D docs] × one op each
apply_op_batch = jax.vmap(apply_op)


def apply_ops_scan(state: DocState, ops) -> DocState:
    """Apply K sequenced ops (int32[K, OP_FIELDS]) to one doc, in order."""

    def step(s, op):
        return apply_op(s, op), None

    out, _ = lax.scan(step, state, ops)
    return out


def _contract_example():
    """Small representative wave: [D=8 docs, K=4 ops, S=16 slots]."""
    D, S, K = 8, 16, 4
    state = jax.vmap(lambda _: DocState.empty(S))(jnp.arange(D))
    ops = jnp.zeros((D, K, OP_FIELDS), jnp.int32)
    return (state, ops), {}


# [D docs] × [K ops each]: the batched hot loop. The contract IS the
# ARCHITECTURE.md claim: the K-amplified apply is strictly rolls +
# selects — zero computed-index gathers/scatters, zero dynamic slices,
# one compile per wave shape (enforced by tools/fluidlint).
apply_ops_batch = kernel_contract(
    "ops.apply_ops_batch",
    example=_contract_example,
    no_gather=True,
    no_scatter=True,
    max_dynamic_slices=0,
    single_jit=True,
    notes="batched merge-tree apply: the K-amplified hot path",
)(jax.vmap(apply_ops_scan))


def wave_min_seq(ops) -> jax.Array:
    """Per-doc zamboni floor for a [D, K, OP_FIELDS] wave: the msn of the
    LAST real op applied to each doc. msn is monotone per doc and NOOP
    padding carries msn 0, so this is simply the max over the wave. Using
    the wave's own msn (not a later one) is what keeps compaction safe
    while later-sequenced ops are still staged on the host: deli
    guarantees every future op's refSeq ≥ the msn it stamped HERE, not
    the msn it stamped afterwards."""
    return jnp.max(ops[..., F_MSN], axis=-1)


# ------------------------------------------------------ packed wave format
#
# Host→device bytes are the op path's per-wave cost that grows with
# the wave (the padded [D, K] wave dwarfs its bases), so the dense
# [D, K] wave ships as int16 DELTAS plus int32 per-doc bases and is
# widened back to the int32 field layout on device. The format lives
# here so both dense lanes — the single-device step
# (service/tpu_applier._dense_step_for) and the doc-sharded mesh step
# (parallel/sharded_apply.make_sharded_packed_step) — encode and decode
# the exact same wire layout. Deltas keep every field in int16 range:
# seq/text_start are per-doc monotone (delta from the wave's first row),
# ref/msn trail seq by at most the collaboration window; the host checks
# the ranges and falls back to the int32 wave when any field escapes.

#: interned id for server/system-originated stamps (never collides with
#: the dense per-doc client table, which grows upward from 0)
SYSTEM_CLIENT = (1 << 30) - 1

#: int16 packed-wave sentinel standing in for SYSTEM_CLIENT on the wire
PACK_SYSTEM = 32767


def unpack_wave16(wave16, bases):
    """Widen a packed int16 [D, K, F] delta wave plus its int32 [D, 2]
    (seq_base, text_base) to the kernel's int32 field layout, on device.

    Gather-free by construction: ``bases[:, :1]`` is a pure slice (a
    None-mixed static index would lower to lax.gather, and the kernel
    contracts budget gathers to compaction only). NOOP padding must not
    lift the per-doc zamboni floor (wave_min_seq is a max), so its msn
    is parked far below any real one."""
    w = wave16.astype(jnp.int32)
    typ = w[..., F_TYPE]
    seq = bases[:, :1] + w[..., F_SEQ]
    ref = seq - w[..., F_REFSEQ]
    msn = jnp.where(typ == OP_NOOP, -(1 << 20), seq - w[..., F_MSN])
    client = w[..., F_CLIENT]
    client = jnp.where(client == PACK_SYSTEM, SYSTEM_CLIENT, client)
    tstart = bases[:, 1:] + w[..., F_TSTART]
    return jnp.stack(
        [typ, w[..., F_POS], w[..., F_END], seq, ref, client,
         w[..., F_TLEN], tstart, msn, w[..., F_FLAGS],
         w[..., F_KEY], w[..., F_VAL]], axis=-1)


def pack_wave_rows(flat, starts, lens_a):
    """Host-side twin of ``unpack_wave16`` over concatenated staged rows.

    ``flat`` is int32 [n, OP_FIELDS] (all docs' rows back to back),
    ``starts``/``lens_a`` delimit each doc's run. Returns
    ``(packed int64 [n, F], seq_base [m], text_base [m])``; the caller
    checks the int16 range and scatters ``packed`` into its wave
    buffers. Bases: seq of the doc's first row; min text_start over its
    insert rows (text_start of non-inserts is unused — packed 0)."""
    seq_base = flat[starts, F_SEQ]
    is_ins = flat[:, F_TYPE] == OP_INSERT
    tstart_or_inf = np.where(is_ins, flat[:, F_TSTART], np.int64(2 ** 62))
    text_base = np.minimum.reduceat(tstart_or_inf, starts)
    text_base = np.where(text_base == 2 ** 62, 0, text_base).astype(np.int64)

    n = len(flat)
    seq = flat[:, F_SEQ].astype(np.int64)
    seq_base_row = np.repeat(seq_base.astype(np.int64), lens_a)
    text_base_row = np.repeat(text_base, lens_a)
    packed = np.empty((n, OP_FIELDS), np.int64)
    packed[:, F_TYPE] = flat[:, F_TYPE]
    packed[:, F_POS] = flat[:, F_POS]
    packed[:, F_END] = flat[:, F_END]
    packed[:, F_SEQ] = seq - seq_base_row
    packed[:, F_REFSEQ] = seq - flat[:, F_REFSEQ]
    client = flat[:, F_CLIENT]
    # a REAL interned id of 32767 would collide with the sentinel and be
    # silently re-attributed to the system client on unpack: force it
    # (vanishingly rare: 32768 distinct clients in one doc) onto the
    # wide path via an out-of-range value
    packed[:, F_CLIENT] = np.where(
        client == SYSTEM_CLIENT, PACK_SYSTEM,
        np.where(client == PACK_SYSTEM, np.int64(1) << 40, client))
    packed[:, F_TLEN] = flat[:, F_TLEN]
    packed[:, F_TSTART] = np.where(
        is_ins, flat[:, F_TSTART] - text_base_row, 0)
    packed[:, F_MSN] = seq - flat[:, F_MSN]
    packed[:, F_FLAGS] = flat[:, F_FLAGS]
    packed[:, F_KEY] = flat[:, F_KEY]
    packed[:, F_VAL] = flat[:, F_VAL]
    return packed, seq_base, text_base


def compact(state: DocState, min_seq) -> DocState:
    """Zamboni, device-side: drop slots whose remove seq ≤ minSeq (no future
    perspective can see them; ref mergeTree.ts:1455) and re-pack in order."""
    S = state.max_slots
    i = jnp.arange(S, dtype=jnp.int32)
    in_use = i < state.count
    drop = in_use & (state.rem_seq != NO_SEQ) & (state.rem_seq <= min_seq)
    keep = in_use & ~drop
    order = jnp.argsort(jnp.where(keep, i, S + i))  # kept first, stable
    new_count = jnp.sum(keep.astype(jnp.int32))
    live = jnp.arange(S, dtype=jnp.int32) < new_count

    def g(a, fill):
        gathered = a[order]
        mask = live if a.ndim == 1 else live[:, None]
        return jnp.where(mask, gathered, fill)

    return DocState(
        length=g(state.length, 0),
        text_start=g(state.text_start, 0),
        flags=g(state.flags, 0),
        ins_seq=g(state.ins_seq, 0),
        ins_client=g(state.ins_client, NO_CLIENT),
        rem_seq=g(state.rem_seq, NO_SEQ),
        rem_client_a=g(state.rem_client_a, NO_CLIENT),
        rem_client_b=g(state.rem_client_b, NO_CLIENT),
        prop_key=g(state.prop_key, NO_KEY),
        prop_val=g(state.prop_val, 0),
        count=new_count,
        overflow=state.overflow,
    )


compact_batch = jax.vmap(compact)
