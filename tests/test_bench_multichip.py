"""MULTICHIP artifact schema (ISSUE 9/11 satellite): the v3 reader must
fold the committed series — rung-bearing v3 artifacts verbatim, v2
(r06) rungs gaining null overlap fields, the removed dryrun-era schema
refused — and the ci smoke's structural
counter-asserts (bytes scale with active shards, one compile per shape,
positive overlap with pipelined waves) must hold under pytest's
8-virtual-device config too."""

import json
import os

import pytest

from tools.bench_multichip import _V3_RUNG_FIELDS, read_multichip, run_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_reader_refuses_dryrun_schema(tmp_path):
    """The dryrun-era {n_devices, rc, ok, skipped, tail} files carried no
    rungs and are gone from the tree; the reader refuses, not guesses."""
    p = tmp_path / "m.json"
    p.write_text(json.dumps({"n_devices": 8, "rc": 0, "ok": True,
                             "skipped": False, "tail": "..."}))
    with pytest.raises(ValueError, match="schema"):
        read_multichip(str(p))


def test_reader_folds_v2_rungs_to_v3(tmp_path):
    v2 = {"schema": 2, "platform": "cpu", "n_devices": 8,
          "forced_host": True,
          "rungs": [{"docs_axis": 1, "n_docs": 64, "ops_per_sec": 1.0,
                     "scaling_efficiency": 1.0,
                     "staging_ms_per_wave": 0.1,
                     "staged_bytes_per_wave": 100}],
          "local_dense_ops_per_sec": 1.0, "mesh_vs_local_1shard": 1.0,
          "ok": True, "rc": 0}
    p = tmp_path / "m.json"
    p.write_text(json.dumps(v2))
    got = read_multichip(str(p))
    assert got["schema"] == 3
    # pre-overlap runs carry the v3 split fields as explicit unknowns
    for r in got["rungs"]:
        for f in _V3_RUNG_FIELDS:
            assert r[f] is None
        assert r["ops_per_sec"] == 1.0  # v2 data survives untouched
    assert got["overlap"] is False
    assert got["efficiency_basis"] == "wall"
    assert got["host_limited"] is True  # inherited from forced_host
    assert got["local_dense_ab"] is None


def test_reader_passes_v3_through(tmp_path):
    v3 = read_multichip(os.path.join(REPO, "MULTICHIP_r07.json")) \
        if os.path.exists(os.path.join(REPO, "MULTICHIP_r07.json")) else None
    if v3 is None:
        pytest.skip("r07 artifact not present")
    # already v3: byte-identical passthrough
    assert v3 == json.load(open(os.path.join(REPO, "MULTICHIP_r07.json")))


@pytest.mark.parametrize("rev", ["r06", "r07"])
def test_reader_loads_committed_artifact_series(rev):
    path = os.path.join(REPO, f"MULTICHIP_{rev}.json")
    if not os.path.exists(path):
        pytest.skip(f"{rev} artifact not present")
    got = read_multichip(path)
    assert got["schema"] == 3
    assert got["ok"] is True
    # every committed generation carries real throughput rungs
    assert len(got["rungs"]) == 4
    for r in got["rungs"]:
        assert r["ops_per_sec"] > 0
        assert 0 < r["scaling_efficiency"] <= 1.25
    assert got["mesh_vs_local_1shard"] >= 0.9  # acceptance: ≤10% tax
    # the overlap-era artifact must prove the pipeline did overlap
    if rev >= "r07":
        assert got["overlap"] is True
        assert any((r["overlap_ratio"] or 0) > 0.5 for r in got["rungs"])
        for r in got["rungs"]:
            assert r["kernel_lane"] in ("xla", "pallas")
        ab = got["local_dense_ab"]
        assert ab["improvement"] is not None and ab["improvement"] > 1.0
        # an honest artifact on a forced-host bench box says so
        if got["forced_host"]:
            assert got["host_limited"] is True
            assert got["host_limited_note"]


def test_smoke_counter_asserts_hold():
    """The ci.sh gate body, under pytest's forced 8-device config:
    staged bytes per wave scale with ACTIVE shards, the packed step
    compiles once per wave shape, and pipelined waves drive
    applier.stage.overlap_ratio positive."""
    run_smoke()
