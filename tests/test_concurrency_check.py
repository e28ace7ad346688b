"""Concurrency contract checker specs: every violation class must fire
on a seeded fixture (self-tests that MUST fail), stay silent on the
clean twin, and the real tree must gate at ZERO unwaivered findings.

The two hard-way bugs this repo actually shipped and fixed — PR 11's
donation-on-CPU ``block_until_ready`` serialization and the
stage-buffer rotation without a consuming-execution fence — are
reconstructed as fixture copies, so the checker provably would have
caught them (ROADMAP "concurrency contracts").

Ref: RacerD's annotate-and-propagate design; Clang -Wthread-safety
REQUIRES()/EXCLUDES() capability analysis.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from tools.fluidlint.concurrency_check import check_concurrency
from tools.fluidlint.concurrency_waivers import WAIVERS
from tools.fluidlint.registries import LOCK_ORDER, LOCK_RANK

HERE = os.path.dirname(os.path.abspath(__file__))
FIX = os.path.join(HERE, "fixtures", "fluidlint", "concurrency")
REPO = os.path.abspath(os.path.join(HERE, ".."))


def _check(case, waivers=(), waived_out=None):
    """Run ONLY the concurrency pass over one fixture package."""
    return check_concurrency(repo_root=os.path.join(FIX, case),
                             roots=("pkg",), waivers=waivers,
                             waived_out=waived_out)


def _messages(case, **kw):
    return [v.message for v in _check(case, **kw)]


def _run_cli(*argv, cwd=REPO):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "tools.fluidlint", *argv],
        cwd=cwd, env=env, capture_output=True, text=True)


# ------------------------------------------------- seeded self-tests
# Each bad fixture is a MUST-FAIL self-test: if the checker ever stops
# seeing these, the pass is broken, not the tree clean.


def test_cross_affinity_detected():
    msgs = _messages("cross_bad")
    assert len(msgs) == 1
    (m,) = msgs
    assert m.startswith("CROSS-AFFINITY:")
    assert "mod.mutate_table" in m and "@loop_only('core')" in m
    # the witness chain names the seed AND the offending caller
    assert "ticker:rebalancer" in m and "mod.tick" in m


def test_cross_affinity_clean_twin_via_seam():
    # same shape, but the ticker crosses through call_soon_threadsafe:
    # the sanctioned seam TRANSFERS context instead of propagating it
    assert _messages("cross_clean") == []


def test_blocking_on_loop_detected():
    msgs = _messages("block_bad")
    assert len(msgs) == 3, msgs
    joined = "\n".join(msgs)
    # a coroutine body that sleeps synchronously
    assert "time.sleep() in mod.poll_loop" in joined
    # a coroutine that dials a @blocking helper (edge check, not entry)
    assert "mod.fan_out calls @blocking mod.dial" in joined
    # a call_soon callback runs ON the loop — its sendall counts
    assert ".sendall() in mod.flush_now" in joined
    assert "call_soon callback in mod.arm" in joined
    # ...but the unseeded helper with no loop context stays silent
    assert "mod.sender" not in joined


def test_blocking_clean_twin_via_executor():
    # the same @blocking dial behind run_in_executor: the handed-off
    # thunk runs in 'executor' context, where blocking is the point
    assert _messages("block_clean") == []


def test_unfenced_shared_state_detected():
    msgs = _messages("unfenced_bad")
    assert len(msgs) == 1
    (m,) = msgs
    assert m.startswith("UNFENCED-SHARED-STATE:")
    assert "Pump.value" in m
    # both writer contexts are named — that's the triage handle
    assert "loop" in m and "thread:pump" in m


def test_unfenced_clean_twin_common_lock():
    # both writers hold self._lock: the common fence clears the group
    assert _messages("unfenced_clean") == []


def test_lock_order_inversions_detected():
    msgs = _messages("lockorder_bad")
    assert len(msgs) == 2, msgs
    joined = "\n".join(msgs)
    # lexical inversion: @holds_lock('journal_lock') body takes the
    # epoch-table flock (rank 0 after rank 2) — the seeded flock case
    assert ("mod.flush_entry acquires 'epoch_table_flock' while "
            "holding 'journal_lock'") in joined
    # call-edge inversion: partition-claim holder calls an epoch-table
    # holder
    assert ("mod.drain_and_record acquires 'epoch_table_flock' while "
            "holding 'partition_claim_flock'") in joined
    # the message teaches the global order
    assert " -> ".join(LOCK_ORDER) in joined


def test_lock_order_clean_twin_ordered():
    assert _messages("lockorder_clean") == []


# -------------------------------------- hard-way bug reconstructions


def test_hardway_donation_on_cpu_bug_is_caught():
    """PR 11's donation bug: with the platform guard gone, dispatch
    block_until_ready()s every wave ON the loop — the checker flags it
    as BLOCKING-ON-LOOP in loop:core context."""
    msgs = [m for m in _messages("hardway")
            if m.startswith("BLOCKING-ON-LOOP:")]
    assert len(msgs) == 1
    (m,) = msgs
    assert ".block_until_ready() in donation.dispatch" in m
    assert "loop:core" in m


def test_hardway_rotation_fence_bug_is_caught():
    """The stage-buffer rotation bug: the staging slot refilled by the
    worker while the loop's ingest writes it, no fence keyed to the
    consuming execution — flagged as UNFENCED-SHARED-STATE."""
    msgs = [m for m in _messages("hardway")
            if m.startswith("UNFENCED-SHARED-STATE:")]
    assert len(msgs) == 1
    (m,) = msgs
    assert "Applier._stage" in m
    assert "ingest (loop)" in m and "recycle (thread:applier)" in m


# -------------------------------------------------- waiver machinery


def test_waiver_suppresses_and_is_reported():
    waiver = ("CROSS-AFFINITY", "mod.tick", "mod.mutate_table",
              "fixture: prove the waiver plumbing")
    waived = []
    assert _check("cross_bad", waivers=(waiver,),
                  waived_out=waived) == []
    assert len(waived) == 1
    # the printed entry carries the justification, not just the match
    assert "prove the waiver plumbing" in waived[0]


def test_stale_waiver_is_itself_a_violation():
    waiver = ("BLOCKING-ON-LOOP", "mod.no_such_function", "",
              "this excuse matches nothing")
    msgs = _messages("cross_clean", waivers=(waiver,))
    assert len(msgs) == 1
    assert "stale waiver" in msgs[0]
    assert "mod.no_such_function" in msgs[0]


def test_lock_rank_matches_order():
    assert tuple(sorted(LOCK_RANK, key=LOCK_RANK.get)) == LOCK_ORDER


# --------------------------------------------------- real-tree gates


def test_real_tree_gates_at_zero_unwaivered():
    """THE tentpole gate: the shipped tree has zero unwaivered
    concurrency findings (and zero stale waivers — stale entries show
    up as violations, so this asserts the waiver table is live too)."""
    waived = []
    violations = check_concurrency(repo_root=REPO, waived_out=waived)
    assert violations == [], "\n".join(v.message for v in violations)
    # every crossing the tree does make is sanctioned WITH an argument
    assert len(waived) >= len(WAIVERS)
    for _rule, _qual, _detail, why in WAIVERS:
        assert any(why in w for w in waived), why


def test_real_tree_without_waivers_shows_the_sanctioned_findings():
    """The waiver table is not decorative: stripped of it, the tree's
    sanctioned crossings surface (the by-design loopback RPC block and
    the in-proc actuation fallback among them)."""
    msgs = [v.message
            for v in check_concurrency(repo_root=REPO, waivers=())]
    assert msgs, "waivers waive nothing — table is dead weight"
    joined = "\n".join(msgs)
    assert "MigrationEngine._rpc_adopt" in joined
    assert "Rebalancer.tick" in joined


# ------------------------------------------------------ CLI surfaces


def test_cli_concurrency_pass_clean_and_prints_waivers():
    r = _run_cli("--pass", "concurrency")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "fluidlint: clean [concurrency]" in r.stdout
    # the text verdict shows WHAT was waived, never just "clean"
    assert "waived concurrency finding(s):" in r.stdout
    assert "loopback" in r.stdout  # a justification made it to stdout


def test_cli_fix_order_prints_lock_table():
    r = _run_cli("--fix-order")
    assert r.returncode == 0
    for i, name in enumerate(LOCK_ORDER):
        assert f"{i}. {name}" in r.stdout
    assert "outermost first" in r.stdout


def test_cli_json_report_shape():
    r = _run_cli("--pass", "concurrency", "--json")
    assert r.returncode == 0, r.stdout + r.stderr
    report = json.loads(r.stdout)
    assert report["clean"] is True
    assert report["passes"] == ["concurrency"]
    assert report["violations"] == []
    assert len(report["waived"]) >= len(WAIVERS)


def test_doctor_folds_lint_report_into_triage(tmp_path):
    """The debug-bundle seam: doctor reads the capturing build's
    ``lint.json`` (written by ``admin bundle`` via ``fluidlint
    --json``) and surfaces a dirty tree as an anomaly — deploying past
    the gate is an incident signal of its own."""
    from tools.doctor import diagnose

    bundle = tmp_path / "bundle"
    bundle.mkdir()
    (bundle / "lint.json").write_text(json.dumps({
        "clean": False, "passes": ["concurrency"],
        "violations": [{"pass": "concurrency", "path": "x.py",
                        "line": 3, "message": "BLOCKING-ON-LOOP: ...",
                        "suggestion": ""}],
        "waived": []}))
    report = diagnose(str(bundle))
    assert report["lint"]["clean"] is False
    assert any("lint [concurrency]" in a and "BLOCKING-ON-LOOP" in a
               for a in report["anomalies"])

    # a clean report raises no anomaly; a bundle without lint.json
    # (captured off-repo) reads as "not captured", never as an error
    (bundle / "lint.json").write_text(json.dumps(
        {"clean": True, "passes": [], "violations": [], "waived": []}))
    report = diagnose(str(bundle))
    assert report["lint"]["clean"] and report["anomalies"] == []
    (bundle / "lint.json").unlink()
    assert diagnose(str(bundle))["lint"] is None


def test_exit_one_contract_on_violations():
    # the ci.sh strict-gate contract: findings mean a nonzero verdict.
    # Drive main() in-process against a seeded fixture (the CLI scans
    # the real package roots, so the fixture rides in via the checker).
    violations = _check("cross_bad")
    assert violations and all(v.pass_name == "concurrency"
                              for v in violations)
    # and the Violation fields the JSON report serializes are populated
    (v,) = violations
    assert v.path.endswith("mod.py") and v.line > 0 and v.suggestion
