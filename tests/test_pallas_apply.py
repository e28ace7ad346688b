"""Pallas VMEM-resident apply vs the XLA scan kernel: field-for-field
parity on fuzzed op streams (and through the existing kernel-vs-oracle
suite, parity with the scalar merge-tree).

Runs in interpreter mode on the CPU test mesh; the TPU path compiles the
real Mosaic kernel (exercised by the benchmark's cells on the chip).
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fluidframework_tpu.ops.apply import apply_ops_batch
from fluidframework_tpu.ops.doc_state import DocState
from fluidframework_tpu.ops.opgen import generate_batch_ops
from fluidframework_tpu.ops.pallas_apply import (pallas_apply_ops_batch,
                                                 pallas_apply_ops_batch_long)

FIELDS = ("length", "text_start", "flags", "ins_seq", "ins_client",
          "rem_seq", "rem_client_a", "rem_client_b", "prop_key",
          "prop_val", "count", "overflow")


def _run_pair(seed, D=16, S=64, K=24, kernel=pallas_apply_ops_batch,
              **gen):
    rng = np.random.default_rng(seed)
    state = jax.vmap(lambda _: DocState.empty(S))(jnp.arange(D))
    ops = jnp.asarray(generate_batch_ops(rng, D, K, **gen))
    ref = apply_ops_batch(state, ops)
    got = kernel(state, ops, interpret=True)
    return ref, got


# the long tier's layout (prop planes slot-last, state carried in refs)
# at a wider row, beside the farm's
@pytest.mark.parametrize("seed,kernel,S", [
    (0, pallas_apply_ops_batch, 64), (1, pallas_apply_ops_batch, 64),
    (2, pallas_apply_ops_batch, 64),
    (0, pallas_apply_ops_batch_long, 256),
    (7, pallas_apply_ops_batch_long, 256),
], ids=["0", "1", "2", "long-0", "long-7"])
def test_pallas_matches_xla_scan(seed, kernel, S):
    ref, got = _run_pair(seed, S=S, kernel=kernel, remove_fraction=0.3,
                         annotate_fraction=0.15, max_insert=6)
    for f in FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, f)), np.asarray(getattr(ref, f)), f)


def test_pallas_matches_on_annotate_heavy_stream():
    ref, got = _run_pair(9, remove_fraction=0.15, annotate_fraction=0.5,
                         max_insert=4)
    for f in FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, f)), np.asarray(getattr(ref, f)), f)


def test_pallas_flags_overflow_identically():
    # tiny slot budget: splits overflow some docs; the flag must match
    ref, got = _run_pair(4, D=8, S=16, K=32, remove_fraction=0.4,
                         annotate_fraction=0.1, max_insert=8)
    assert np.asarray(ref.overflow).any()  # the stream really overflows
    np.testing.assert_array_equal(
        np.asarray(got.overflow), np.asarray(ref.overflow))


def test_applier_with_pallas_dense_step_matches_live_clients():
    """The live TpuDocumentApplier with kernel=pallas rides the same
    sequenced stream as real clients and converges identically
    (interpret mode on the CPU test mesh)."""
    from fluidframework_tpu.driver import LocalDocumentServiceFactory
    from fluidframework_tpu.loader import Loader
    from fluidframework_tpu.service import LocalServer
    from fluidframework_tpu.service.tpu_applier import (
        TpuDocumentApplier,
        channel_stream,
    )

    server = LocalServer()
    loader = Loader(LocalDocumentServiceFactory(server))
    c1 = loader.resolve("t", "pdoc")
    c2 = loader.resolve("t", "pdoc")
    s1 = c1.runtime.create_data_store("default").create_channel(
        "text", "shared-string")
    s1.insert_text(0, "pallas in the loop")
    s2 = c2.runtime.get_data_store("default").get_channel("text")
    s2.insert_text(0, ">> ")
    s1.remove_text(3, 10)
    s1.annotate_range(0, 4, {"bold": True})
    assert s1.get_text() == s2.get_text()

    applier = TpuDocumentApplier(max_docs=8, max_slots=64,
                                 ops_per_dispatch=8, kernel="pallas",
                                 pallas_interpret=True)
    applier.set_replay_source(lambda t, d: [])
    for m in channel_stream(server, "t", "pdoc", "default", "text"):
        applier.ingest("t", "pdoc", m, m.contents)
    applier.finalize()
    assert applier.host_escalations == 0
    assert applier.get_text("t", "pdoc") == s1.get_text()


# -------------------------------------------- kernel selection matrix

SEEDS = (0, 7, 42)


def _fuzz_session(seed, doc):
    """Seeded two-client session through the real stack; returns the
    server and the converged oracle text."""
    from fluidframework_tpu.driver import LocalDocumentServiceFactory
    from fluidframework_tpu.loader import Loader
    from fluidframework_tpu.service import LocalServer

    server = LocalServer()
    loader = Loader(LocalDocumentServiceFactory(server))
    c1 = loader.resolve("t", doc)
    c2 = loader.resolve("t", doc)
    s1 = c1.runtime.create_data_store("default").create_channel(
        "text", "shared-string")
    s1.insert_text(0, "kernel matrix seed text")
    s2 = c2.runtime.get_data_store("default").get_channel("text")
    rng = np.random.default_rng(seed)
    for _ in range(48):
        s = (s1, s2)[int(rng.integers(0, 2))]
        n = len(s.get_text())
        r = rng.random()
        if n > 4 and r < 0.3:
            a = int(rng.integers(0, n - 1))
            s.remove_text(a, int(rng.integers(a + 1, min(n, a + 5) + 1)))
        elif n > 2 and r < 0.45:
            a = int(rng.integers(0, n - 1))
            s.annotate_range(a, a + 1, {"k": int(rng.integers(0, 4))})
        else:
            s.insert_text(int(rng.integers(0, n + 1)),
                          f"<{rng.integers(0, 99)}>")
    assert s1.get_text() == s2.get_text()
    return server, s1.get_text()


@pytest.fixture(scope="module")
def sessions():
    return {seed: _fuzz_session(seed, f"mx{seed}") for seed in SEEDS}


def _drive_applier(server, doc, **kw):
    from fluidframework_tpu.service.tpu_applier import (
        TpuDocumentApplier,
        channel_stream,
    )

    applier = TpuDocumentApplier(max_docs=16, max_slots=256,
                                 ops_per_dispatch=8, **kw)
    applier.set_replay_source(lambda t, d: [])
    for m in channel_stream(server, "t", doc, "default", "text"):
        applier.ingest("t", doc, m, m.contents)
    applier.finalize()
    assert applier.host_escalations == 0
    return applier


@pytest.mark.parametrize("kernel", ["auto", "xla", "pallas"])
@pytest.mark.parametrize("seed", SEEDS)
def test_applier_kernel_matrix_matches_oracle(sessions, kernel, seed):
    """applier.kernel=auto|pallas|xla all converge to the scalar oracle
    through the real client stack. ``auto`` resolves per backend; a
    forced ``pallas`` compiles the REAL Mosaic kernel, so off-TPU it is
    skipped LOUDLY — never silently green."""
    if kernel == "pallas" and jax.default_backend() != "tpu":
        pytest.skip(
            "applier.kernel=pallas forces the real Mosaic lowering, "
            f"which needs a TPU (backend={jax.default_backend()}); "
            "interpret-mode parity for the same kernel is covered by "
            "the tests above, and this forced lane runs on TPU CI")
    server, want = sessions[seed]
    applier = _drive_applier(server, f"mx{seed}", kernel=kernel)
    assert applier.get_text("t", f"mx{seed}") == want
    want_lane = ("pallas" if kernel == "pallas"
                 or (kernel == "auto" and jax.default_backend() == "tpu")
                 else "xla")
    assert applier.kernel_lane == want_lane


@pytest.mark.parametrize("shards", [0, 2, 4, 8])
@pytest.mark.parametrize("seed", SEEDS)
def test_pipelined_applier_matches_oracle(sessions, shards, seed):
    """The overlap-staged pipeline (wave N+1 stages on the host while
    wave N executes on device) converges to the scalar oracle, locally
    and across 2/4/8-shard meshes, with strict wave order preserved
    through finalize."""
    server, want = sessions[seed]
    kw = {}
    if shards:
        from fluidframework_tpu.parallel.mesh import make_mesh

        kw["mesh"] = make_mesh(shards, seg_shards=1)
    doc = f"mx{seed}"
    applier = _drive_applier(server, doc, **kw)
    assert applier.get_text("t", doc) == want
    # every wave went through the stage/execute split and fed the
    # per-lane stage accounting, on the dense lane as on the mesh
    assert applier.waves_staged == applier.dispatches > 0
    assert applier.stage_seconds > 0
    assert applier.stage_bytes > 0


@pytest.mark.parametrize("kernel, backend, tile, want", [
    ("auto", "cpu", 8, False),
    ("auto", "tpu", 8, True),
    ("auto", "tpu", 12, "warn"),
    ("xla", "tpu", 8, False),
    ("pallas", "cpu", 16, True),
    ("pallas", "tpu", 12, ValueError),
    ("bogus", "cpu", 8, ValueError),
    (None, "tpu", 8, True),
])
def test_resolve_kernel(monkeypatch, kernel, backend, tile, want):
    """``kernel`` (else config ``applier_kernel``, "auto" by default)
    resolves to the Pallas lane: auto takes it on a TPU when the
    docs-per-shard tile is a multiple of 8 and otherwise warns and takes
    the XLA scan; a forced pallas on a bad tile and an unknown name
    raise."""
    from fluidframework_tpu.config import Config
    from fluidframework_tpu.service.tpu_applier import _resolve_kernel

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = Config()
    if want is ValueError:
        with pytest.raises(ValueError):
            _resolve_kernel(kernel, cfg, tile)
    elif want == "warn":
        with pytest.warns(UserWarning, match="picks the XLA scan"):
            assert _resolve_kernel(kernel, cfg, tile) is False
    else:
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            assert _resolve_kernel(kernel, cfg, tile) is want
