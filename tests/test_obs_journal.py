"""Control-plane audit journal + fleet observability (ISSUE 14).

Five planes under test:

- the :class:`~fluidframework_tpu.obs.journal.Journal` codec — fuzzed
  labels survive a write/read round trip, torn tails and garbage lines
  are skipped, rotation keeps the tail and a restart recovers the seq
  from the file (ids are never reused);
- cause links — ``causal_chain`` walks root-first, terminates on
  opaque causes, and cuts cycles;
- the fleet merge — ``(epoch, ts, core, seq)`` ordering keeps
  cross-core causality correct under deliberate wall-clock skew;
- the metrics history ring — retired buckets survive past the live
  window, the horizon prunes, and ``window_history`` label-filters;
- the appended hop catalog e2e — ``relay_to_relay`` from a real
  2-level relay tree (core ← mid ← leaf, separate processes), plus
  the full forced-migration journal chain on in-proc shard hosts.
"""

from __future__ import annotations

import json
import os
import random
import socket
import string
import subprocess
import sys
import time

import pytest

from fluidframework_tpu.obs.journal import (
    KINDS,
    Journal,
    arm_journal,
    causal_chain,
    filter_entries,
    get_journal,
    merge_entries,
    read_journal,
    reset_journal,
)
from fluidframework_tpu.obs.metrics import MetricsRegistry, WindowedSeries
from fluidframework_tpu.protocol import binwire
from fluidframework_tpu.service.front_end import ShardHost
from fluidframework_tpu.service.placement_plane import MigrationEngine
from fluidframework_tpu.utils.telemetry import (
    HOP_ACK,
    HOP_ADMIT,
    HOP_DELI,
    HOP_EXECUTE,
    HOP_FANOUT,
    HOP_RELAY,
    HOP_SHED,
    HOP_STAGE,
    HOP_SUBMIT,
    count_unknown_hops,
    hop_pairs,
)
from tests.test_columnar import _rand_cols_ops


def wait_for(pred, timeout: float = 20.0) -> bool:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.005)
    return bool(pred())


# ------------------------------------------------------------ codec basics


def test_emit_roundtrip_disarmed_noop_and_kind_guard(tmp_path):
    path = str(tmp_path / "j" / "core.jsonl")
    jr = Journal(path, core="c0", epoch_fn=lambda: 7)
    eid = jr.emit("core.start", owner="c0", shards=2)
    assert eid == "c0:1"
    e2 = jr.emit("lease.claim", cause=eid, part=3)
    assert e2 == "c0:2"
    entries = read_journal(path)
    assert [e["id"] for e in entries] == ["c0:1", "c0:2"]
    assert entries[0]["kind"] == "core.start"
    assert entries[0]["epoch"] == 7
    assert entries[0]["labels"] == {"owner": "c0", "shards": 2}
    assert entries[1]["cause"] == eid
    # an undeclared kind must explode at emit time on an ARMED journal
    with pytest.raises(ValueError):
        jr.emit("migration.sealed", part=3)
    jr.close()
    # disarmed: emit is a no-op returning None (the bench A/B contract)
    off = Journal()
    assert not off.armed
    assert off.emit("core.start") is None
    assert off.emit("not.even.a.kind") is None  # no validation when free


def test_codec_fuzz_and_torn_tail(tmp_path):
    """200 fuzzed entries round-trip; garbage and a torn final line are
    skipped without poisoning the earlier reads."""
    rng = random.Random(14)
    path = str(tmp_path / "fuzz.jsonl")
    jr = Journal(path, core="fz")
    kinds = sorted(KINDS)
    emitted = []
    for i in range(200):
        labels = {
            "s": "".join(rng.choices(string.printable, k=rng.randrange(20))),
            "u": "μ→漢 \x00" * rng.randrange(3),
            "n": rng.choice([None, rng.random(), rng.randrange(1 << 40)]),
            "nest": {"a": [1, {"b": rng.random()}]},
        }
        kind = rng.choice(kinds)
        emitted.append((kind, jr.emit(kind, **labels), labels))
    jr.close()
    # torn tail: a crash mid-write leaves a partial line; plus junk
    with open(path, "a", encoding="utf-8", errors="surrogateescape") as f:
        f.write("not json at all\n")
        f.write('{"noise": true}\n')      # wrong shape (no kind)
        f.write('[1, 2, 3]\n')            # not an object
        f.write('{"id":"fz:999","seq":999,"kind":"core.st')  # torn
    entries = read_journal(path)
    assert len(entries) == 200
    for (kind, eid, labels), e in zip(emitted, entries):
        assert e["kind"] == kind and e["id"] == eid
        assert e["labels"]["nest"] == labels["nest"]
    assert [e["seq"] for e in entries] == list(range(1, 201))


def test_rotation_and_seq_recovery_across_restart(tmp_path):
    """Rotation keeps one prior generation; a re-armed journal recovers
    the seq from the tail so restarted cores never reuse ids."""
    path = str(tmp_path / "rot.jsonl")
    jr = Journal(path, core="r0", max_bytes=2048)
    n = 0
    while not os.path.exists(path + ".1") and n < 500:
        n += 1
        jr.emit("epoch.bump", epoch=n, part=n % 4)
    assert os.path.exists(path + ".1"), "rotation never happened"
    for _ in range(5):  # land entries in the fresh generation too
        n += 1
        jr.emit("epoch.bump", epoch=n)
    jr.close()
    entries = read_journal(path)  # rotated generation first
    seqs = [e["seq"] for e in entries]
    assert seqs == sorted(seqs)
    assert seqs[-1] == n
    # restart: a new instance on the same path continues the id space
    jr2 = Journal(path, core="r0")
    assert jr2.seq == n
    assert jr2.emit("core.recover", owner="r0") == f"r0:{n + 1}"
    jr2.close()


def test_tail_filters_kind_prefix_doc_and_part(tmp_path):
    path = str(tmp_path / "t.jsonl")
    jr = Journal(path, core="t0")
    jr.emit("migration.seal", part=1, doc="d1")
    jr.emit("migration.commit", part=1)
    jr.emit("lease.claim", part=2)
    jr.emit("summary.commit", doc="d1", tenant="t")
    assert [e["kind"] for e in jr.tail(kind="migration.")] == [
        "migration.seal", "migration.commit"]
    assert [e["kind"] for e in jr.tail(part=2)] == ["lease.claim"]
    assert [e["kind"] for e in jr.tail(doc="d1")] == [
        "migration.seal", "summary.commit"]
    assert len(jr.tail(n=2)) == 2
    jr.close()
    # the same filters over raw entry lists (the admin --fleet path)
    entries = read_journal(path)
    assert len(filter_entries(entries, kind="migration.", part=1)) == 2


# ------------------------------------------------------------- cause links


def _entry(core, seq, kind, cause=None, epoch=None, ts=0.0, **labels):
    return {"id": f"{core}:{seq}", "seq": seq, "ts": ts, "core": core,
            "epoch": epoch, "kind": kind, "cause": cause, "labels": labels}


def test_causal_chain_root_first_opaque_and_cycles():
    entries = [
        _entry("a", 1, "operator.command"),
        _entry("a", 2, "migration.seal", cause="a:1"),
        _entry("b", 1, "migration.adopt", cause="a:2"),
        _entry("a", 3, "migration.commit", cause="b:1"),
        # opaque cause (a flight-dump path) terminates the walk cleanly
        _entry("a", 4, "flight.dump", cause="/var/dumps/x.json"),
        # a cause cycle must not hang the walker
        _entry("c", 1, "lease.claim", cause="c:2"),
        _entry("c", 2, "lease.release", cause="c:1"),
    ]
    chain = causal_chain(entries, "a:3")
    assert [e["id"] for e in chain] == ["a:1", "a:2", "b:1", "a:3"]
    assert [e["id"] for e in causal_chain(entries, "a:4")] == ["a:4"]
    cyc = causal_chain(entries, "c:1")
    assert {e["id"] for e in cyc} == {"c:1", "c:2"}  # visited once each
    assert causal_chain(entries, "nope:1") == []


def test_fleet_merge_epoch_leads_wall_clock_skew():
    """core A's wall clock runs 100 s AHEAD of core B's. The shared
    epoch must still order the cross-core handoff correctly — ts only
    breaks ties within an epoch."""
    core_a = [  # skewed fast: big ts, SMALL epochs
        _entry("a", 1, "migration.seal", epoch=5, ts=1100.0),
        _entry("a", 2, "migration.commit", epoch=7, ts=1101.0),
    ]
    core_b = [  # adopt happened between, on the slow clock
        _entry("b", 1, "migration.adopt", epoch=6, ts=1000.5),
        _entry("b", 2, "epoch.bump", epoch=6, ts=1000.9),
    ]
    merged = merge_entries([core_a, core_b])
    assert [e["id"] for e in merged] == ["a:1", "b:1", "b:2", "a:2"]
    # entries with no epoch (unbound journal) sort before any epoch
    merged2 = merge_entries([[_entry("c", 1, "core.start", ts=999.0)],
                             core_b])
    assert merged2[0]["id"] == "c:1"


# ------------------------------------------------------- history retention


def test_windowed_series_history_retirement_and_horizon():
    ws = WindowedSeries(window_s=10.0, buckets=5, history_s=60.0,
                        history_res_s=10.0)  # width 2 s, 6 slots
    ws.observe(5.0, now=5.0)
    ws.observe(7.0, now=5.5)       # same bucket
    pts = ws.history(now=6.0)      # live bucket visible immediately
    assert len(pts) == 1
    assert pts[0]["count"] == 2 and pts[0]["sum"] == 12.0
    assert pts[0]["max"] == 7.0 and pts[0]["t"] == 0.0
    # ring wrap retires the bucket into its history slot — the values
    # survive far past the 10 s live window
    ws.observe(1.0, now=25.0)      # reuses ring index 2 → retire
    pts = ws.history(now=25.0)
    assert [p["t"] for p in pts] == [0.0, 20.0]
    assert pts[0]["count"] == 2    # the retired blip, intact
    # the horizon prunes: 60 s later neither old slot is readable
    ws.observe(2.0, now=99.0)
    pts = ws.history(now=99.0)
    assert [p["t"] for p in pts] == [90.0]


def test_registry_window_history_names_and_label_filter():
    reg = MetricsRegistry()
    reg.observe_windowed("obs.hop.window_ms", 3.0, now=50.0,
                         pair="relay_to_relay")
    reg.observe_windowed("obs.hop.window_ms", 9.0, now=50.0,
                         pair="stage_to_execute")
    reg.observe_windowed("net.batch.window_ms", 1.0, now=50.0)
    hist = reg.window_history(now=50.0)
    assert set(hist) == {"obs.hop.window_ms", "net.batch.window_ms"}
    only = reg.window_history("obs.hop.window_ms", now=50.0,
                              pair="stage_to_execute")
    assert list(only) == ["obs.hop.window_ms"]
    (row,) = only["obs.hop.window_ms"]
    assert row["labels"] == {"pair": "stage_to_execute"}
    assert row["points"][0]["sum"] == 9.0


# ----------------------------------------------- appended hop ids (6/7/8)


def test_hop_pairs_full_pipeline_with_new_ids():
    """shed/stage/execute slot into pipeline order, and repeated relay
    stamps become relay_to_relay legs in arrival order."""
    hops = [(HOP_SHED, 1.0), (HOP_SUBMIT, 2.0), (HOP_RELAY, 3.0),
            (HOP_RELAY, 4.5), (HOP_ADMIT, 5.0), (HOP_DELI, 6.0),
            (HOP_STAGE, 7.0), (HOP_EXECUTE, 8.5), (HOP_FANOUT, 9.0),
            (HOP_ACK, 9.5)]
    pairs = hop_pairs(hops)
    assert pairs == [
        ("shed_to_submit", 1000.0), ("submit_to_relay", 1000.0),
        ("relay_to_relay", 1500.0), ("relay_to_admit", 500.0),
        ("admit_to_deli", 1000.0), ("deli_to_stage", 1000.0),
        ("stage_to_execute", 1500.0), ("execute_to_fanout", 500.0),
        ("fanout_to_ack", 500.0)]
    # out-of-catalog ids are ignored by pairs but counted for the
    # obs.trace.unknown_hops surface
    skewed = hops + [(42, 3.3), (99, 1.1)]
    assert hop_pairs(skewed) == pairs
    assert count_unknown_hops(skewed) == 2
    assert count_unknown_hops(hops) == 0


# ------------------------------------------------- relay-tree hoptail e2e


def _spawn(args):
    proc = subprocess.Popen(
        [sys.executable, "-m"] + args,
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        cwd="/root/repo")
    line = proc.stdout.readline().strip()
    assert line.startswith("LISTENING"), line
    return proc, int(line.rsplit(":", 1)[1])


@pytest.fixture(scope="module")
def tree():
    """core ← mid gateway ← leaf gateway, separate OS processes — the
    2-level relay tree of the read-fanout plane."""
    core, core_port = _spawn(
        ["fluidframework_tpu.service.front_end", "--port", "0"])
    mid, p_mid = _spawn(["fluidframework_tpu.service.gateway",
                         "--core-port", str(core_port), "--python"])
    leaf, p_leaf = _spawn(["fluidframework_tpu.service.gateway",
                           "--upstream-gateway", f"127.0.0.1:{p_mid}"])
    try:
        yield core_port, p_mid, p_leaf
    finally:
        for proc in (leaf, mid, core):
            proc.terminate()
            proc.wait(timeout=10)


def _frame(obj: dict) -> bytes:
    body = json.dumps(obj, separators=(",", ":")).encode()
    return len(body).to_bytes(4, "big") + body


def _bin_client(port: int, doc: str):
    s = socket.create_connection(("127.0.0.1", port), timeout=10)
    s.sendall(_frame({"t": "connect", "tenant": "t", "doc": doc,
                      "rid": 1, "bin": 1}))
    buf = [b""]

    def read_frame():
        while True:
            b = buf[0]
            if len(b) >= 4:
                n = int.from_bytes(b[:4], "big")
                if len(b) >= 4 + n:
                    buf[0] = b[4 + n:]
                    return b[4:4 + n]
            chunk = s.recv(65536)
            if not chunk:
                raise ConnectionError("closed")
            buf[0] += chunk
    while binwire.is_binary(read_frame()):
        pass  # drain until the JSON connect reply
    return s, read_frame


def test_relay_to_relay_pair_through_two_tier_tree(tree):
    """A sampled columnar submit climbing leaf → mid → core collects
    one HOP_RELAY stamp per tier; the broadcast hoptail therefore
    yields a nonzero relay_to_relay leg (the relay-depth witness)."""
    _, _, p_leaf = tree
    body = binwire.encode_submit_columns(_rand_cols_ops(random.Random(8), 5))
    t_submit = time.time()
    body = binwire.append_hop(body, HOP_SUBMIT, t_submit)
    s, read = _bin_client(p_leaf, "doc-tree-hops")
    s.sendall(binwire.frame(body))
    while True:
        f = read()
        if binwire.is_binary(f) and f[1] in (binwire.FT_COLS_OPS,
                                             binwire.FT_COLS_FOPS):
            break
    s.close()
    hops = binwire.read_hoptail(f)
    ids = [h for h, _ in hops]
    assert ids.count(HOP_RELAY) == 2  # one stamp per gateway tier
    assert ids[:3] == [HOP_SUBMIT, HOP_RELAY, HOP_RELAY]
    assert {HOP_ADMIT, HOP_DELI, HOP_FANOUT} <= set(ids)
    ts = [t for _, t in hops]
    assert ts == sorted(ts) and ts[0] == t_submit
    pairs = dict(hop_pairs(hops))
    assert "relay_to_relay" in pairs
    assert pairs["relay_to_relay"] >= 0.0
    assert "submit_to_relay" in pairs and "relay_to_admit" in pairs


# -------------------------------------------- forced-migration chain e2e


def _host(shard_dir, prefer=()) -> ShardHost:
    h = ShardHost(str(shard_dir), 2, prefer=prefer, ttl_s=30.0)
    h.address = f"inproc/{h.owner_id}"
    h.poll()
    return h


def test_forced_migration_emits_linked_chain(tmp_path):
    """seal → fence → checkpoint → adopt → epoch bump → commit, every
    link present and causally connected back to the operator command —
    the same chain ``admin journal --fleet`` renders after net_smoke's
    forced migration."""
    path = str(tmp_path / "journal" / "core-test.jsonl")
    arm_journal(path, core="core-test")
    try:
        src = _host(tmp_path, prefer=(0, 1))
        tgt = _host(tmp_path)
        try:
            eng = MigrationEngine(src)
            op_id = get_journal().emit(
                "operator.command", command="admin_migrate_part",
                part=0, target=tgt.address)
            res = eng.migrate(
                0, tgt.address, cause=op_id,
                adopt=lambda k, addr: MigrationEngine(tgt).adopt(
                    k, src.owner_id, cause=eng._adopt_cause))
            assert res["target"] == tgt.address
        finally:
            for h in (src, tgt):
                for srv in list(h.servers.values()):
                    srv.log.close()
        entries = read_journal(path)
        commit = [e for e in entries
                  if e["kind"] == "migration.commit"][-1]
        chain = causal_chain(entries, commit["id"])
        assert [e["kind"] for e in chain] == [
            "operator.command", "migration.seal", "migration.fence",
            "migration.checkpoint", "migration.adopt",
            "migration.commit"]
        assert chain[0]["id"] == op_id
        # the adoption's epoch bump hangs off the adopt entry (a side
        # branch of the same chain), and the commit recorded the epoch
        adopt_id = chain[4]["id"]
        bump = [e for e in entries if e["kind"] == "epoch.bump"
                and e["cause"] == adopt_id]
        assert len(bump) == 1
        assert commit["epoch"] == bump[0]["epoch"]
        # the startup claims linked too: every lease.claim's id causes
        # one epoch.bump (the poll() path)
        claims = {e["id"] for e in entries if e["kind"] == "lease.claim"}
        assert claims
        claim_bumps = {e["cause"] for e in entries
                       if e["kind"] == "epoch.bump"
                       and e["cause"] in claims}
        assert claim_bumps == claims
    finally:
        reset_journal()
