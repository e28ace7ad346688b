"""Pallas TPU kernel: VMEM-resident batched merge-tree apply.

The XLA scan (`ops/apply.apply_ops_batch`) re-reads and re-writes the
[D, S] doc state from HBM across the K scan steps. This kernel grids
over tiles of R=8 docs (a full VPU sublane tile), loads each tile's slot
arrays into VMEM ONCE, applies all K ops with a `fori_loop` carrying the
state in registers/VMEM, and writes back once — state traffic drops from
O(K·|state|) to O(|state|) per wave. Measured on the v5e chip: ~8%
faster than the XLA scan at K=64-128 (1.56M vs 1.45M ops/s at K=128) —
the apply turns out to be closer to compute-bound than HBM-bound once
XLA's own fusion is accounted for, so residency buys the margin, not a
multiple.

The op semantics are a line-for-line 2D port of `apply._apply_core`
(leading dim R, slot axis last; per-doc scalars as [R, 1] columns;
dynamic extracts as masked row-sums — TPU-safe forms per the Pallas
guide). Parity with the XLA kernel (and through it the scalar oracle) is
enforced by tests/test_pallas_apply.py on fuzzed streams.

Zamboni compaction stays in XLA (`apply.compact_batch`): it runs once
per wave, not per op, so it is not on the K-amplified path.

Mosaic lowering constraints found by bisection on this toolchain (and
baked into the shapes here): bool and 3-D arrays crash the compiler when
loop-carried, and jnp.cumsum / value-level dynamic_slice / argmax do not
lower — hence int32 overflow, prop tables carried as P separate 2-D
planes (statically unrolled), the blocked segmented lane scan (see
_cumsum_lanes), ref-level pl.ds reads, and masked-min first-True
selection.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .apply import (
    F_CLIENT,
    F_END,
    F_FLAGS,
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
)
from ..utils.contracts import kernel_contract
from .doc_state import NO_KEY, NO_SEQ, DocState

R = 8  # docs per grid instance: one full VPU sublane tile

_FIELDS_1D = ("length", "text_start", "flags", "ins_seq", "ins_client",
              "rem_seq", "rem_client_a", "rem_client_b")


def _rowtake(col, a, j):
    """a[row, j[row]] as a masked row-sum ([R, S] × [R, 1] → [R, 1])."""
    return jnp.sum(jnp.where(col == j, a, 0), axis=1, keepdims=True)


#: segmented-scan block width: one vector register row of lanes. Strides
#: below this stay inside a single register rotate on the VPU; strides at
#: or above it cross register boundaries and pay a real shuffle.
SCAN_BLOCK = 128


def _cumsum_lanes(x, col, S, block=None):
    """Inclusive prefix sum along the lane axis as a BLOCKED segmented
    scan (SURVEY §5.7: per-block partial sums are a segmented
    prefix-sum), replacing the flat Hillis-Steele lane scan.

    Three phases over blocks of B = min(block, S) lanes:

    1. within-block inclusive scan — log2(B) Hillis-Steele rounds whose
       mask confines every roll to its own block (``lane >= n`` kills
       both the circular wrap and cross-block bleed);
    2. block partial sums (the §5.7 partial-lengths table) live at each
       block's last lane; an inter-block Hillis-Steele at strides
       B..S/2 turns them into an inclusive scan of block totals —
       block-end lanes map to block-end lanes under multiples of B, so
       non-end lanes only ever accumulate rolled zeros;
    3. each block j>0 picks up block j-1's scanned total (roll by 1
       lands it on the block's first lane) and broadcasts it across the
       block with one more masked prefix pass.

    Round count is 2·log2(B) + log2(S/B) + 1 vs the flat scan's
    log2(S) — MORE rounds, but all except the log2(S/B) carry rounds
    run at stride < B, i.e. inside one vector register row; the flat
    scan's large-stride rolls (up to S/2 lanes) are the ones that cost
    cross-register shuffles on real TPUs. Off-TPU (interpret mode) the
    two are numerically identical; parity with jnp.cumsum is pinned by
    tests/test_pallas_apply.py. jnp.cumsum itself does not lower in
    Pallas TPU, hence the roll+mask formulation throughout."""
    B = min(block or SCAN_BLOCK, S)
    assert S % B == 0, (S, B)
    lane = col % B  # col is an iota, so this is plain int arithmetic
    n = 1
    while n < B:
        x = x + jnp.where(lane >= n, pltpu.roll(x, n, 1), 0)
        n *= 2
    if B == S:
        return x
    # phase 2: scan the per-block totals (resident at block-end lanes)
    tot = jnp.where(lane == B - 1, x, 0)
    n = B
    while n < S:
        tot = tot + jnp.where(col >= n, pltpu.roll(tot, n, 1), 0)
        n *= 2
    # phase 3: block j's carry = scanned total through block j-1; the
    # col >= B mask keeps block 0 carry-free (roll is circular)
    carry = jnp.where((lane == 0) & (col >= B), pltpu.roll(tot, 1, 1), 0)
    n = 1
    while n < B:
        carry = carry + jnp.where(lane >= n, pltpu.roll(carry, n, 1), 0)
        n *= 2
    return x + carry


def _apply_one(carry, op_row, S):
    """One op across the R-doc tile; mirrors apply._apply_core in 2D."""
    (length, tstart, flags, iseq, icl, rseq, rca, rcb, pk, pv,
     count, ovf) = carry
    col = lax.broadcasted_iota(jnp.int32, (R, S), 1)

    def f(i):
        return op_row[:, i][:, None]  # [R, 1]

    typ = f(F_TYPE)
    is_ins = typ == OP_INSERT
    is_rem = typ == OP_REMOVE
    is_ann = typ == OP_ANNOTATE
    active = is_ins | is_rem | is_ann
    pos, end = f(F_POS), f(F_END)
    seq, ref, client = f(F_SEQ), f(F_REFSEQ), f(F_CLIENT)
    p2 = jnp.where(is_ins, pos, end)

    # visibility at the op's perspective (apply._visibility, 2D)
    in_use = col < count
    ins_seen = (icl == client) | (iseq <= ref)
    removed = (rseq != NO_SEQ) & (
        (rca == client) | (rcb == client) | (rseq <= ref))
    vis = in_use & ins_seen & ~removed
    vlen = jnp.where(vis, length, 0)
    cum = _cumsum_lanes(vlen, col, S) - vlen
    total = jnp.sum(vlen, axis=1, keepdims=True)
    inc = cum + vlen

    # pure logic form: jnp.where with BOOL branches crashes this Mosaic
    # toolchain (as does pltpu.roll on bools — see vis_r below)
    bad_shape = (is_ins & (pos > total)) | (
        ~is_ins & ((end > total) | (end <= pos)))
    inside1 = vis & (cum < pos) & (pos < inc)
    inside2 = vis & (cum < p2) & (p2 < inc)
    s1_raw = jnp.any(inside1, axis=1, keepdims=True)
    s2_raw = (~is_ins) & jnp.any(inside2, axis=1, keepdims=True)
    needed = (s1_raw.astype(jnp.int32) + s2_raw.astype(jnp.int32)
              + is_ins.astype(jnp.int32))
    bad = active & (bad_shape | (count + needed > S))
    ok = active & ~bad
    s1 = s1_raw & ok
    s2 = s2_raw & ok
    do_ins = is_ins & ok

    # first-True via masked min (argmax-free: reliably lowers on TPU);
    # the no-match sentinel S is safe — every use is gated on s1/s2/ok
    j1 = jnp.min(jnp.where(inside1, col, S), axis=1, keepdims=True)
    j2 = jnp.min(jnp.where(inside2, col, S), axis=1, keepdims=True)
    o1 = pos - _rowtake(col, cum, j1)
    o2 = p2 - _rowtake(col, cum, j2)
    l1 = _rowtake(col, length, j1)
    ts1 = _rowtake(col, tstart, j1)
    l2 = _rowtake(col, length, j2)
    ts2 = _rowtake(col, tstart, j2)
    same = s1 & s2 & (j1 == j2)

    s1i = s1.astype(jnp.int32)
    idx0 = jnp.min(jnp.where(cum >= pos, col, S), axis=1, keepdims=True)
    p_ins = jnp.where(s1, j1 + 1, idx0)
    p_n1 = jnp.where(do_ins, p_ins + 1, j1 + 1)
    p_h2 = j2 + s1i
    p_n2 = j2 + 1 + s1i

    delta = ((s1 & (col >= p_n1)).astype(jnp.int32)
             + (s2 & (col >= p_n2)).astype(jnp.int32)
             + (do_ins & (col >= p_ins)).astype(jnp.int32))
    d1 = delta == 1
    d2 = delta == 2
    head1_at = s1 & (col == j1)
    n1_at = s1 & (col == p_n1)
    h2_at = s2 & ~same & (col == p_h2)
    n2_at = s2 & (col == p_n2)
    new_at = do_ins & (col == p_ins)

    tlen, tst = f(F_TLEN), f(F_TSTART)
    new_len = jnp.where(tlen > 0, tlen, 1)
    n1_len = jnp.where(same, o2 - o1, l1 - o1)

    def sh1(a):
        return pltpu.roll(a, 1, 1)

    def sh2(a):
        return pltpu.roll(a, 2, 1)

    def rebuild(a, new_val=None, patches=()):
        out = jnp.where(d1, sh1(a), jnp.where(d2, sh2(a), a))
        for mask, val in patches:
            out = jnp.where(mask, val, out)
        if new_val is not None:
            out = jnp.where(new_at, new_val, out)
        return out

    length_o = rebuild(length, new_len,
                       [(head1_at, o1), (n1_at, n1_len), (h2_at, o2),
                        (n2_at, l2 - o2)])
    tstart_o = rebuild(tstart, tst, [(n1_at, ts1 + o1), (n2_at, ts2 + o2)])
    flags_o = rebuild(flags, f(F_FLAGS))
    iseq_o = rebuild(iseq, seq)
    icl_o = rebuild(icl, client)
    rseq_o = rebuild(rseq, NO_SEQ)
    rca_o = rebuild(rca, NO_CLIENT)
    rcb_o = rebuild(rcb, NO_CLIENT)
    # prop tables ride as P separate [R, S] planes (3-D loop carries
    # crash Mosaic); the lane axis unrolls statically
    pk_o = tuple(
        jnp.where(new_at, NO_KEY,
                  jnp.where(d1, sh1(a), jnp.where(d2, sh2(a), a)))
        for a in pk)
    pv_o = tuple(
        jnp.where(new_at, 0,
                  jnp.where(d1, sh1(a), jnp.where(d2, sh2(a), a)))
        for a in pv)
    count_o = count + s1i + s2.astype(jnp.int32) + do_ins.astype(jnp.int32)

    # remove/annotate coverage on the ROLLED perspective arrays; vis
    # rides as an int mask (bool rolls crash Mosaic here)
    vism = vis.astype(jnp.int32)
    vis_r = jnp.where(d1, sh1(vism), jnp.where(d2, sh2(vism), vism)) > 0
    cum_r = jnp.where(d1, sh1(cum), jnp.where(d2, sh2(cum), cum))
    cum_r = jnp.where(n1_at, _rowtake(col, cum, j1) + o1, cum_r)
    cum_r = jnp.where(n2_at, _rowtake(col, cum, j2) + o2, cum_r)
    vlen_r = jnp.where(vis_r, length_o, 0)
    covered = vis_r & (cum_r >= pos) & (cum_r + vlen_r <= end)
    rm = is_rem & ~bad & covered
    fresh = rm & (rseq_o == NO_SEQ)
    over = rm & (rseq_o != NO_SEQ)
    add_b = over & (rca_o != client) & (rcb_o == NO_CLIENT)
    third = over & (rca_o != client) & (rcb_o != client) & \
        (rcb_o != NO_CLIENT)

    key, val = f(F_KEY), f(F_VAL)
    an = is_ann & ~bad & covered
    P_ = len(pk_o)
    match = [a == key for a in pk_o]
    empty = [a == NO_KEY for a in pk_o]
    has_key = functools.reduce(jnp.logical_or, match)
    has_empty = functools.reduce(jnp.logical_or, empty)
    # first matching (else first empty) lane, as a static priority walk
    big = jnp.int32(P_)
    tgt_m = big
    tgt_e = big
    for lane in range(P_ - 1, -1, -1):
        tgt_m = jnp.where(match[lane], lane, tgt_m)
        tgt_e = jnp.where(empty[lane], lane, tgt_e)
    tgt = jnp.where(has_key, tgt_m, tgt_e)
    is_delete = val == NO_VAL
    do_write = an & (has_key | (~is_delete & has_empty))
    table_full = jnp.any(an & ~has_key & ~has_empty & ~is_delete,
                         axis=1, keepdims=True)
    pk_o = tuple(
        jnp.where(do_write & (tgt == lane),
                  jnp.where(is_delete, NO_KEY, key), a)
        for lane, a in enumerate(pk_o))
    pv_o = tuple(
        jnp.where(do_write & (tgt == lane),
                  jnp.where(is_delete, 0, val), a)
        for lane, a in enumerate(pv_o))

    # overflow rides as int32: a bool loop carry crashed the Mosaic
    # compiler when this kernel was written
    ovf_o = ovf | (jnp.any(third, axis=1, keepdims=True)
                   | table_full | bad).astype(jnp.int32)

    return (length_o, tstart_o, flags_o, iseq_o, icl_o,
            jnp.where(fresh, seq, rseq_o),
            jnp.where(fresh, client, rca_o),
            jnp.where(add_b, client, rcb_o),
            pk_o, pv_o, count_o, ovf_o)


def _kernel(ops_ref, length, tstart, flags, iseq, icl, rseq, rca, rcb,
            pk, pv, count, ovf,
            o_length, o_tstart, o_flags, o_iseq, o_icl, o_rseq, o_rca,
            o_rcb, o_pk, o_pv, o_count, o_ovf, *, S, K):
    P = pk.shape[-1]
    carry = (length[:, :], tstart[:, :], flags[:, :], iseq[:, :],
             icl[:, :], rseq[:, :], rca[:, :], rcb[:, :],
             tuple(pk[:, :, p] for p in range(P)),
             tuple(pv[:, :, p] for p in range(P)),
             count[:, :], ovf[:, :])
    def body(k, carry):
        # dynamic-sliced REF read (value-level dynamic_slice does not
        # lower in Pallas TPU)
        op_row = ops_ref[:, pl.ds(k, 1), :][:, 0, :]  # [R, F]
        return _apply_one(carry, op_row, S)

    out = lax.fori_loop(0, K, body, carry)
    for ref, arr in zip(
        (o_length, o_tstart, o_flags, o_iseq, o_icl, o_rseq, o_rca,
         o_rcb), out[:8]):
        ref[...] = arr
    for p in range(P):
        o_pk[:, :, p] = out[8][p]
        o_pv[:, :, p] = out[9][p]
    o_count[...] = out[10]
    o_ovf[...] = out[11]


def _kernel_long(ops_ref, length, tstart, flags, iseq, icl, rseq, rca, rcb,
                 pk, pv, count, ovf,
                 o_length, o_tstart, o_flags, o_iseq, o_icl, o_rseq, o_rca,
                 o_rcb, o_pk, o_pv, o_count, o_ovf, *, S, K):
    """The long tier's layout: prop planes slot-last ([P, R, S] blocks,
    each plane a whole [R, S] tile with the slots in the lane dimension;
    the farm's [R, S, P] puts P = 8 in the lanes, which Mosaic pads to
    128), and the state carried through the ``fori_loop`` in the OUTPUT
    refs, read and written back once an op: at S = 4096 one plane is 32
    vector registers, so a value carry of all 24 planes could never stay
    in registers."""
    P = pk.shape[0]
    planes = (o_length, o_tstart, o_flags, o_iseq, o_icl, o_rseq, o_rca,
              o_rcb)
    for src, dst in zip((length, tstart, flags, iseq, icl, rseq, rca, rcb),
                        planes):
        dst[...] = src[...]
    o_pk[...] = pk[...]
    o_pv[...] = pv[...]
    o_count[...] = count[...]
    o_ovf[...] = ovf[...]

    def body(k, _):
        op_row = ops_ref[:, pl.ds(k, 1), :][:, 0, :]  # [R, F]
        carry = (*(r[:, :] for r in planes),
                 tuple(o_pk[p] for p in range(P)),
                 tuple(o_pv[p] for p in range(P)),
                 o_count[:, :], o_ovf[:, :])
        out = _apply_one(carry, op_row, S)
        for r, arr in zip(planes, out[:8]):
            r[...] = arr
        for p in range(P):
            o_pk[p] = out[8][p]
            o_pv[p] = out[9][p]
        o_count[...] = out[10]
        o_ovf[...] = out[11]
        return 0

    lax.fori_loop(0, K, body, 0)


def _contract_example():
    """One R-tile wave in interpret mode (the checker runs on CPU)."""
    D, S, K = R, 16, 4
    state = jax.vmap(lambda _: DocState.empty(S))(jnp.arange(D))
    ops = jnp.zeros((D, K, OP_FIELDS), jnp.int32)
    return (state, ops), {"interpret": True}


# contract: the VMEM-resident apply must stay roll/select like its XLA
# twin — the checker walks INTO the pallas_call kernel jaxpr, so a
# gather smuggled into the Mosaic body fails the same way
@kernel_contract(
    "ops.pallas_apply_ops_batch",
    example=_contract_example,
    no_gather=True,
    no_scatter=True,
    no_int16_arithmetic=True,
    single_jit=True,
    notes="Pallas VMEM-resident apply (tile of R docs, blocked "
          "segmented lane scan)",
)
@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_apply_ops_batch(state: DocState, ops: jax.Array,
                           interpret: bool = False) -> DocState:
    """Drop-in twin of ``apply.apply_ops_batch`` (no compact): applies a
    NOOP-padded [D, K, F] wave with doc state resident in VMEM."""
    D = state.length.shape[0]
    S = state.length.shape[1]
    P = state.prop_key.shape[-1]
    K = ops.shape[1]
    assert D % R == 0, f"doc count {D} must be a multiple of {R}"
    count2 = state.count.astype(jnp.int32).reshape(D, 1)
    ovf2 = state.overflow.astype(jnp.int32).reshape(D, 1)

    grid = (D // R,)
    row = pl.BlockSpec((R, S), lambda i: (i, 0))
    rowp = pl.BlockSpec((R, S, P), lambda i: (i, 0, 0))
    row1 = pl.BlockSpec((R, 1), lambda i: (i, 0))
    opspec = pl.BlockSpec((R, K, OP_FIELDS), lambda i: (i, 0, 0))

    shapes = (
        [jax.ShapeDtypeStruct((D, S), jnp.int32)] * 8
        + [jax.ShapeDtypeStruct((D, S, P), jnp.int32)] * 2
        + [jax.ShapeDtypeStruct((D, 1), jnp.int32),
           jax.ShapeDtypeStruct((D, 1), jnp.int32)]
    )
    outs = pl.pallas_call(
        functools.partial(_kernel, S=S, K=K),
        grid=grid,
        in_specs=[opspec] + [row] * 8 + [rowp] * 2 + [row1, row1],
        out_specs=[row] * 8 + [rowp] * 2 + [row1, row1],
        out_shape=shapes,
        interpret=interpret,
    )(ops, *(getattr(state, f) for f in _FIELDS_1D),
      state.prop_key, state.prop_val, count2, ovf2)

    return DocState(
        **dict(zip(_FIELDS_1D, outs[:8])),
        prop_key=outs[8], prop_val=outs[9],
        count=outs[10].reshape(D),
        overflow=outs[11].reshape(D).astype(bool),
    )


#: the long tier's kernel, by this name in the device trace
LONG_KERNEL_NAME = "long_apply"
#: the long kernel's scoped VMEM limit
LONG_VMEM_LIMIT = 64 * 1024 * 1024


def _contract_example_long():
    D, S, K = R, 256, 4
    state = jax.vmap(lambda _: DocState.empty(S))(jnp.arange(D))
    ops = jnp.zeros((D, K, OP_FIELDS), jnp.int32)
    return (state, ops), {"interpret": True}


@kernel_contract(
    "ops.pallas_apply_ops_batch_long",
    example=_contract_example_long,
    no_gather=True,
    no_scatter=True,
    no_int16_arithmetic=True,
    single_jit=True,
    notes="Pallas apply at the long tier's layout (prop planes slot-last, "
          "state carried in VMEM refs)",
)
@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_apply_ops_batch_long(state: DocState, ops: jax.Array,
                                interpret: bool = False) -> DocState:
    """``pallas_apply_ops_batch`` for the long tier's [L, S_long] rows:
    the prop tables go to the kernel as [P, L, S] planes (a transpose
    either side of the call, in XLA) so that a block stays within VMEM
    at S = 8192, and the kernel is named ``LONG_KERNEL_NAME``."""
    D = state.length.shape[0]
    S = state.length.shape[1]
    P = state.prop_key.shape[-1]
    K = ops.shape[1]
    assert D % R == 0, f"doc count {D} must be a multiple of {R}"
    count2 = state.count.astype(jnp.int32).reshape(D, 1)
    ovf2 = state.overflow.astype(jnp.int32).reshape(D, 1)

    row = pl.BlockSpec((R, S), lambda i: (i, 0))
    planes = pl.BlockSpec((P, R, S), lambda i: (0, i, 0))
    row1 = pl.BlockSpec((R, 1), lambda i: (i, 0))
    opspec = pl.BlockSpec((R, K, OP_FIELDS), lambda i: (i, 0, 0))
    shapes = (
        [jax.ShapeDtypeStruct((D, S), jnp.int32)] * 8
        + [jax.ShapeDtypeStruct((P, D, S), jnp.int32)] * 2
        + [jax.ShapeDtypeStruct((D, 1), jnp.int32)] * 2
    )
    outs = pl.pallas_call(
        functools.partial(_kernel_long, S=S, K=K),
        grid=(D // R,),
        in_specs=[opspec] + [row] * 8 + [planes] * 2 + [row1, row1],
        out_specs=[row] * 8 + [planes] * 2 + [row1, row1],
        out_shape=shapes,
        interpret=interpret,
        name=LONG_KERNEL_NAME,
        # double-buffered in and out blocks of 24 [8, S] planes plus the
        # body's temporaries pass the 16 MB default scope (17.1 MB at
        # S = 4096); 64 MB holds S = 8192, of the v5e core's 128 MiB
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=LONG_VMEM_LIMIT),
    )(ops, *(getattr(state, f) for f in _FIELDS_1D),
      jnp.transpose(state.prop_key, (2, 0, 1)),
      jnp.transpose(state.prop_val, (2, 0, 1)), count2, ovf2)

    return DocState(
        **dict(zip(_FIELDS_1D, outs[:8])),
        prop_key=jnp.transpose(outs[8], (1, 2, 0)),
        prop_val=jnp.transpose(outs[9], (1, 2, 0)),
        count=outs[10].reshape(D),
        overflow=outs[11].reshape(D).astype(bool),
    )
