"""The long-document cell's harness at tiny sizes on the CPU, and its
reference.

- ``refmerge_runs.reference_text`` equals ``refmerge.reference_text`` on
  seeded mixed streams: 256-character appends, the editor's inserts,
  removes and annotates, several clients editing concurrently in their
  own perspectives;
- a ~700,000-character document with ~900 concurrent edits takes it
  under 10 s of CPU (0.2 s on the machine this was written on);
- the rehearsal of ``text-1k-long16.steady`` is ``correct``, and the
  control fault makes it not correct;
- a stage whose STAGE line shows no long tier makes the driver exit 1
  with no result line;
- the readers of ``kernel.long_apply_*`` and the bytes they count.
"""

import json
import os
import random
import shutil
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import refmerge, refmerge_runs, roofline  # noqa: E402
from benchmark.editor import Editor  # noqa: E402
from benchmark.run import reader  # noqa: E402

CELL = "text-1k-long16.steady"
APPEND = 256


def mixed_stream(seed, n_clients=4, boxcars=200, appends=40, lag=6):
    """(seq, ref, client, op) in seq order: a loader's 256-character
    appends, then seeded editors, each seeing the others' ops up to
    ``lag`` ops late, with the loader still appending among them."""
    rng = random.Random(f"mixed/{seed}")
    texts = ["".join(rng.choice("abcdefgh ") for _ in range(APPEND))
             for _ in range(4)]
    out, seq = [], 0
    for i in range(appends):
        seq += 1
        out.append((seq, seq - 1, "c0",
                    {"type": 0, "pos": i * APPEND, "text": texts[i % 4]}))
    eds = [Editor(random.Random(f"{seed}/{c}")) for c in range(n_clients)]
    for ed in eds:
        ed.length, ed.ref_seq = appends * APPEND, seq
    seen = [0] * n_clients
    log = []  # (seq, client, visible-length delta)
    for _ in range(boxcars):
        c = rng.randrange(n_clients)
        ed = eds[c]
        upto = max(seen[c], len(log) - rng.randint(0, lag))
        for s, who, delta in log[seen[c]:upto]:
            if who != c:
                ed.observe_delta(delta)
            ed.ref_seq = s
        seen[c] = upto
        if c == 0 and rng.random() < 0.2:  # an append at its own end
            ops = [{"type": 0, "pos": ed.length, "text": texts[0]}]
            ed.length += APPEND
        else:
            ops = ed.next_wire_ops(rng.choice([1, 1, 1, 2, 5, 16]))
        for op in ops:
            seq += 1
            delta = (len(op["text"]) if op["type"] == 0 else
                     op["start"] - op["end"] if op["type"] == 1 else 0)
            log.append((seq, c, delta))
            out.append((seq, ed.ref_seq, f"c{c}", op))
    return out


@pytest.mark.parametrize("seed", [0, 7, 42])
def test_runs_reference_matches_the_character_reference(seed):
    ops = mixed_stream(seed)
    assert any(op["type"] == 1 for *_x, op in ops)
    assert any(ref < seq - 1 for seq, ref, _c, _o in ops)  # concurrent
    want = refmerge.reference_text(ops)
    assert refmerge_runs.reference_text(ops) == want
    assert len(want) > 40 * APPEND // 2


def test_runs_reference_matches_on_small_blocks(monkeypatch):
    """Blocks of 2 runs: every op crosses block boundaries and splits."""
    monkeypatch.setattr(refmerge_runs, "BLOCK", 2)
    ops = mixed_stream(3, boxcars=120, appends=10)
    assert refmerge_runs.reference_text(ops) == refmerge.reference_text(ops)


def test_a_book_length_document_is_cheap():
    ops = mixed_stream(1, n_clients=10, boxcars=300, appends=2735)
    t = time.process_time()
    text = refmerge_runs.reference_text(ops)
    assert time.process_time() - t < 10.0
    assert len(text) > 690_000


def run_cell(root=ROOT, seed=2200000001, fault=None):
    env = dict(os.environ, PERFBENCH_REHEARSAL="1", JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT)
    env.pop("PERFBENCH_FAULT", None)
    if fault:
        env["PERFBENCH_FAULT"] = fault
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(seed), "--seconds", "3", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=240)


def test_rehearsal_is_correct():
    p = run_cell(seed=2200000003)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, out["checks"]
    assert set(out["checks"]) >= {"long_docs_off_tier", "host_escalations",
                                  "window_compiles", "text_mismatch_docs"}
    assert set(out["metrics"]) == {"staleness_p99_ms", "acked_ops_per_s",
                                   "setup_s"}
    long = json.loads(next(ln[5:] for ln in p.stdout.splitlines()
                           if ln.startswith("long ")))
    assert long["promotions"] >= 2 and long["long_dispatches"] > 0
    assert long["chars_min"] >= 16384


def test_the_control_is_not_correct():
    """The applier skips every 7th boxcar it is fed. A doc whose later
    ops then name positions past its device text escalates to the host;
    when a later skipped boxcar is that doc's, the host replica cannot
    apply the next one and the stage dies: which of the two happens
    depends on how the stage's turns batch the records. Either way no
    run of the control is correct."""
    p = run_cell(seed=2200000007, fault="control")
    if p.returncode == 0:
        out = json.loads(p.stdout.strip().splitlines()[-1])
        assert out["correct"] is False, out["checks"]
    else:
        assert p.returncode == 1
        assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
        assert "out of range" in p.stderr, p.stderr[-3000:]


def test_a_stage_without_the_long_tier_gives_no_result(tmp_path):
    """A program whose stage shows no long tier (here: the tier set to
    0 rows under the stage's wrapper, in a copy) exits 1 at the STAGE
    line, before any doc is loaded, and prints no result."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    wrap = tmp_path / "benchmark" / "stage_wrap.py"
    head = "from __future__ import annotations\n"
    wrap.write_text(wrap.read_text().replace(head, head + (
        'import os\nos.environ["FLUID_TPU_APPLIER_LONG_DOCS"] = "0"\n'), 1))
    p = run_cell(root=str(tmp_path))
    assert p.returncode == 1
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "no long tier" in p.stderr
    assert not any(line.startswith("load ") for line in
                   p.stdout.splitlines())


def test_the_readers():
    shape = [16, 4096, 32]
    nbytes = roofline.long_apply_bytes(*shape)
    assert 12.5e6 < nbytes < 12.7e6  # state read and written, op rows
    layer = {"device_kind": "TPU v5 lite",
             "long_kernel": {"n": 100, "seconds": 0.05, "shape": shape}}
    assert reader("kernel.long_apply_ms").read(layer) == pytest.approx(0.5)
    share = reader("kernel.long_apply_roofline_share").read(layer)
    assert share == pytest.approx(100 * 100 * nbytes / (0.05 * 819e9))
    for empty in ({}, {"long_kernel": None},
                  {"long_kernel": {"n": 0, "seconds": 0.0, "shape": shape}}):
        assert reader("kernel.long_apply_ms").read(empty) is None
        assert reader("kernel.long_apply_roofline_share").read(empty) is None
    with pytest.raises(KeyError):
        roofline.hbm_bytes_per_s("cpu")
