"""fluidlint self-tests: each pass catches its fixture violation and
comes back clean on the clean twin (and on the real tree).

Fixtures live in tests/fixtures/fluidlint/ — a deliberate layering
violation, a deliberately gather-ful kernel, and an int16-promotion
bug. The hygiene/layer walkers skip fixtures/ directories, so the bad
fixtures never pollute the real-tree run.
"""

from __future__ import annotations

import importlib.util
import os
import subprocess
import sys
from dataclasses import replace

import jax.numpy as jnp
import numpy as np

from fluidframework_tpu.utils.contracts import (
    kernel_contract,
    register_kernel_contract,
)
from tools.fluidlint import (
    hygiene,
    jaxpr_check,
    journal_check,
    layers,
    metrics_check,
    storage_check,
    wire_check,
)

HERE = os.path.dirname(__file__)
FIX = os.path.join(HERE, "fixtures", "fluidlint")
REPO = os.path.abspath(os.path.join(HERE, ".."))

BAD_TREE = os.path.join(FIX, "layering_bad", "fluidframework_tpu")
CLEAN_TREE = os.path.join(FIX, "layering_clean", "fluidframework_tpu")


# ---------------------------------------------------------------- layers

def test_layering_violation_caught():
    vs = layers.check_layers(root=BAD_TREE, repo_root=FIX)
    assert len(vs) == 1, [str(v) for v in vs]
    v = vs[0]
    assert "'utils' may not import 'protocol'" in v.message
    assert v.path.endswith("utils/leaky.py")
    assert v.line > 0
    assert "protocol" in v.suggestion  # names the layers it IS legal from


def test_layering_clean_fixture_passes():
    assert layers.check_layers(root=CLEAN_TREE, repo_root=FIX) == []


def test_unclassified_subpackage_caught(tmp_path):
    root = tmp_path / "fluidframework_tpu"
    (root / "rogue").mkdir(parents=True)
    (root / "rogue" / "__init__.py").write_text("")
    vs = layers.check_classified(root=str(root), repo_root=str(tmp_path))
    assert len(vs) == 1 and "'rogue'" in vs[0].message


def test_emit_packages_md_is_deterministic():
    a = layers.emit_packages_md(repo_root=REPO)
    b = layers.emit_packages_md(repo_root=REPO)
    assert a == b
    assert "GENERATED" in a
    # every classified layer appears as a section
    for pkg in layers.ALLOWED:
        assert f"## {pkg}" in a


def test_stale_packages_md_caught(tmp_path):
    md = tmp_path / "PACKAGES.md"
    md.write_text("# PACKAGES\n\nstale by hand-editing\n")
    vs = layers.check_packages_md(md_path=str(md), repo_root=REPO)
    assert len(vs) == 1 and "stale" in vs[0].message


# ----------------------------------------------------------------- jaxpr

def _fixture_kernels():
    spec = importlib.util.spec_from_file_location(
        "fluidlint_fixture_kernels", os.path.join(FIX, "kernels.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _example_gather():
    return ((jnp.arange(12.0).reshape(3, 4), jnp.array([0, 2, 1])), {})


def _example_int16():
    return ((jnp.zeros((3, 4), jnp.int16), jnp.zeros((3, 2), jnp.int32)),
            {})


def test_gatherful_kernel_caught():
    mod = _fixture_kernels()
    reg: dict = {}
    kernel_contract("fixture.gatherful", example=_example_gather,
                    no_gather=True, registry=reg)(mod.gatherful_kernel)
    vs = jaxpr_check.check_kernels(registry=reg, required=())
    assert len(vs) == 1, [str(v) for v in vs]
    assert "gather" in vs[0].message and "no_gather" in vs[0].message


def test_clean_kernel_passes():
    mod = _fixture_kernels()
    reg: dict = {}
    kernel_contract("fixture.clean", example=_example_gather,
                    no_gather=True, no_scatter=True, single_jit=True,
                    registry=reg)(mod.clean_kernel)
    assert jaxpr_check.check_kernels(registry=reg, required=()) == []


def test_int16_promotion_caught():
    mod = _fixture_kernels()
    reg: dict = {}
    kernel_contract("fixture.int16_promoting", example=_example_int16,
                    no_int16_arithmetic=True,
                    registry=reg)(mod.int16_promoting_kernel)
    vs = jaxpr_check.check_kernels(registry=reg, required=())
    assert len(vs) == 1, [str(v) for v in vs]
    assert "int16" in vs[0].message


def test_int16_clean_passes():
    mod = _fixture_kernels()
    reg: dict = {}
    kernel_contract("fixture.int16_clean", example=_example_int16,
                    no_int16_arithmetic=True,
                    registry=reg)(mod.int16_clean_kernel)
    assert jaxpr_check.check_kernels(registry=reg, required=()) == []


def test_missing_required_registration_flagged():
    vs = jaxpr_check.check_kernels(registry={},
                                   required=("ops.apply_ops_batch",))
    assert len(vs) == 1 and "not registered" in vs[0].message


def test_real_registry_covers_required_kernels():
    reg = jaxpr_check.load_registry()
    for name in jaxpr_check.REQUIRED_KERNELS:
        assert name in reg, f"{name} lost its contract registration"


def test_batched_apply_jaxpr_is_gather_free():
    """The acceptance-criterion check, as a direct assertion: the
    registered batched-apply kernel's jaxpr has NO gather/scatter."""
    reg = jaxpr_check.load_registry()
    contract = reg["ops.apply_ops_batch"]
    fn, example = contract.build()
    args, kwargs = example()
    closed = jaxpr_check._trace(fn, args, kwargs)
    counts = jaxpr_check.primitive_counts(closed.jaxpr)
    assert counts.get("gather", 0) == 0, counts
    assert not any(p.startswith("scatter") for p in counts), counts


def test_packed_sharded_step_contract_holds():
    """The multi-chip fast lane's contract, run directly: the registered
    parallel.sharded_step_packed kernel must satisfy all its declared
    checks (scatter-free, bounded gathers, no silent int16 promotion,
    single compile) — and must actually be in REQUIRED_KERNELS so a
    future deregistration can't slip through."""
    assert "parallel.sharded_step_packed" in jaxpr_check.REQUIRED_KERNELS
    reg = jaxpr_check.load_registry()
    vs = [v for v in jaxpr_check.check_kernels(registry=reg, required=())
          if "sharded_step_packed" in str(v)]
    assert vs == [], [str(v) for v in vs]


def test_pallas_packed_contracts_hold():
    """The default-on Pallas lane (applier.kernel=pallas): both the
    dense and mesh selections must satisfy every declared invariant —
    the checker walks INTO the pallas_call jaxpr — and must be pinned in
    REQUIRED_KERNELS so a future deregistration can't slip through."""
    names = ("service.dense_step_packed_pallas",
             "parallel.sharded_step_packed_pallas")
    for name in names:
        assert name in jaxpr_check.REQUIRED_KERNELS, name
    reg = jaxpr_check.load_registry()
    sub = {n: reg[n] for n in names}
    vs = jaxpr_check.check_kernels(registry=sub, required=())
    assert vs == [], [str(v) for v in vs]


def test_tile_step_contracts_hold():
    """The farm's tile step on both lanes: its gathers are zamboni's
    repack plus one row gather per DocState field, its scatters one row
    write-back per field, within the declared budgets, and both names
    are pinned in REQUIRED_KERNELS."""
    names = ("service.tile_step_packed", "service.tile_step_packed_pallas")
    reg = jaxpr_check.load_registry()
    for name in names:
        assert name in jaxpr_check.REQUIRED_KERNELS, name
        assert (reg[name].max_gathers, reg[name].max_scatters) == (22, 12)
    vs = jaxpr_check.check_kernels(registry={n: reg[n] for n in names},
                                   required=())
    assert vs == [], [str(v) for v in vs]


def test_a_scatter_past_the_budget_fails_lint():
    """One row write-back too many in the tile step is flagged by
    ``max_scatters``."""
    good = jaxpr_check.load_registry()["service.tile_step_packed"]

    def regressed_build():
        fn, example = good.build()

        def regressed(state, rows, wave16, bases):
            state, stats = fn(state, rows, wave16, bases)
            return replace(state, count=state.count.at[rows].add(0)), stats

        return regressed, example

    sub: dict = {}
    register_kernel_contract(
        "fixture.tile_scatter", build=regressed_build, max_scatters=12,
        registry=sub)
    vs = jaxpr_check.check_kernels(registry=sub, required=())
    assert len(vs) == 1 and "over the budget of 12" in vs[0].message, \
        [str(v) for v in vs]


def test_pallas_contract_regression_fails_lint():
    """A contract REGRESSION in the Pallas lane must fail the lint, not
    pass silently: wrap the real registered kernel with int16 arithmetic
    smuggled in ahead of the explicit widen and assert the checker flags
    it under the same declared invariants."""
    reg = jaxpr_check.load_registry()
    good = reg["service.dense_step_packed_pallas"]

    def regressed_build():
        fn, example = good.build()

        def regressed(state, wave16, bases):
            return fn(state, wave16 * jnp.int16(2), bases)

        return regressed, example

    sub: dict = {}
    register_kernel_contract(
        "fixture.pallas_regressed", build=regressed_build,
        no_int16_arithmetic=True, registry=sub)
    vs = jaxpr_check.check_kernels(registry=sub, required=())
    assert len(vs) == 1 and "int16" in vs[0].message, \
        [str(v) for v in vs]


# ------------------------------------------------------------------ wire

def test_wire_bad_fixture_caught():
    vs = wire_check.check_wire(
        paths=(os.path.join(FIX, "wire_bad.py"),), repo_root=FIX)
    msgs = [v.message for v in vs]
    assert any("not explicitly big-endian" in m for m in msgs), msgs
    assert any("non-fixed-width" in m for m in msgs), msgs
    assert any("arithmetic on int16 array 'wave16'" in m
               for m in msgs), msgs
    assert any("in-place arithmetic on int16 array 'w'" in m
               for m in msgs), msgs


def test_wire_clean_fixture_passes():
    assert wire_check.check_wire(
        paths=(os.path.join(FIX, "wire_clean.py"),), repo_root=FIX) == []


def test_wire_real_tree_clean():
    assert wire_check.check_wire(repo_root=REPO) == []


# --------------------------------------------------------------- hygiene

def test_hygiene_catches_all_three(tmp_path):
    p = tmp_path / "sloppy.py"
    p.write_text(
        "import jax.numpy as jnp\n"
        "ZEROS = jnp.zeros(4)\n"
        "def f(x=[]):\n"
        "    try:\n"
        "        return x\n"
        "    except:\n"
        "        return None\n")
    vs = hygiene.check_file(str(p), repo_root=str(tmp_path),
                            import_silent=True)
    msgs = [v.message for v in vs]
    assert any("bare `except:`" in m for m in msgs), msgs
    assert any("mutable default" in m for m in msgs), msgs
    assert any("import time" in m for m in msgs), msgs


def test_hygiene_real_tree_clean():
    assert hygiene.check_hygiene(repo_root=REPO) == []


# --------------------------------------------------------------- storage

def _storage_tree(tmp_path, durable_log_src, shim=True):
    """A minimal fake repo tree shaped like the real one."""
    svc = tmp_path / "fluidframework_tpu" / "service"
    svc.mkdir(parents=True)
    (svc / "durable_log.py").write_text(durable_log_src)
    if shim:
        (svc / "log_compat.py").write_text("import json\n")
    return str(tmp_path)


def test_storage_json_ban_caught(tmp_path):
    root = _storage_tree(
        tmp_path,
        "import json\n"
        "def enc(v):\n"
        "    return json.dumps(v).encode()\n")
    vs = storage_check.check_storage(repo_root=root)
    msgs = [v.message for v in vs]
    assert any("json import in a storage hot-path module" in m
               for m in msgs), msgs
    assert any("json.dumps on the storage hot path" in m
               for m in msgs), msgs
    assert all("log_compat" not in v.path for v in vs)  # shim exempt


def test_storage_missing_shim_caught(tmp_path):
    root = _storage_tree(tmp_path, "x = 1\n", shim=False)
    vs = storage_check.check_storage(repo_root=root)
    assert any("shim module is missing" in v.message for v in vs)


def test_storage_undeclared_metric_caught(tmp_path):
    root = _storage_tree(
        tmp_path,
        "def f(c):\n"
        "    c.inc('storage.segment.append')\n")  # typo: missing 's'
    vs = storage_check.check_storage(repo_root=root)
    assert any('undeclared storage metric "storage.segment.append"'
               in v.message for v in vs), [v.message for v in vs]


def test_storage_real_tree_clean():
    assert storage_check.check_storage(repo_root=REPO) == []


def test_snapcols_json_ban_caught(tmp_path):
    proto = tmp_path / "fluidframework_tpu" / "protocol"
    proto.mkdir(parents=True)
    (proto / "snapcols.py").write_text(
        "import json\n"
        "def enc(v):\n"
        "    return json.dumps(v).encode()\n")
    svc = tmp_path / "fluidframework_tpu" / "service"
    svc.mkdir(parents=True)
    (svc / "log_compat.py").write_text("import json\n")
    vs = storage_check.check_storage(repo_root=str(tmp_path))
    assert any(v.path.endswith("snapcols.py")
               and "json import" in v.message for v in vs), \
        [str(v) for v in vs]


def test_snapshot_metric_undeclared_caught(tmp_path):
    root = _storage_tree(
        tmp_path,
        "def f(c):\n"
        "    c.inc('storage.snapshot.reencodes')\n")  # not a member
    vs = storage_check.check_storage(repo_root=root)
    assert any('undeclared storage metric "storage.snapshot.reencodes"'
               in v.message for v in vs), [v.message for v in vs]


# --------------------------------------------------------------- metrics

def _metrics_file(tmp_path, src):
    pkg = tmp_path / "fluidframework_tpu"
    pkg.mkdir()
    path = pkg / "mod.py"
    path.write_text(src)
    return str(path)


def test_boot_family_lock_caught(tmp_path):
    path = _metrics_file(
        tmp_path,
        "def f(c):\n"
        "    c.inc('boot.snapshot.fellback')\n")  # not a member
    vs = metrics_check.check_file(path, repo_root=str(tmp_path))
    assert len(vs) == 1 and 'locked "boot.*" family' in vs[0].message, \
        [str(v) for v in vs]
    assert "boot.snapshot.fallback" in vs[0].message  # names the members


def test_snapshot_family_lock_caught(tmp_path):
    path = _metrics_file(
        tmp_path,
        "def f(c):\n"
        "    c.inc('storage.snapshot.reencoded')\n")
    vs = metrics_check.check_file(path, repo_root=str(tmp_path))
    assert len(vs) == 1 \
        and 'locked "storage.snapshot.*" family' in vs[0].message, \
        [str(v) for v in vs]


def test_placement_family_lock_caught(tmp_path):
    path = _metrics_file(
        tmp_path,
        "def f(c):\n"
        "    c.inc('placement.migration.commited')\n")  # typo'd member
    vs = metrics_check.check_file(path, repo_root=str(tmp_path))
    assert len(vs) == 1 and 'locked "placement.*" family' in vs[0].message, \
        [str(v) for v in vs]
    assert "placement.migration.committed" in vs[0].message


def test_rebalance_family_lock_caught(tmp_path):
    path = _metrics_file(
        tmp_path,
        "def f(c):\n"
        "    c.inc('placement.rebalance.migrations')\n")  # not a member
    vs = metrics_check.check_file(path, repo_root=str(tmp_path))
    assert len(vs) == 1 and 'locked "placement.*" family' in vs[0].message, \
        [str(v) for v in vs]
    assert "placement.rebalance.migrations_issued" in vs[0].message


def test_heat_family_members_pass(tmp_path):
    # the rebalancer's locked heat/decision names are legal as written
    path = _metrics_file(
        tmp_path,
        "def f(c):\n"
        "    c.inc('placement.heat.ops')\n"
        "    c.inc('placement.heat.bytes')\n"
        "    c.inc('placement.rebalance.ticks')\n"
        "    c.inc('placement.rebalance.plans')\n"
        "    c.inc('placement.rebalance.suppressed_hysteresis')\n"
        "    c.inc('placement.rebalance.suppressed_budget')\n")
    vs = metrics_check.check_file(path, repo_root=str(tmp_path))
    assert vs == [], [str(v) for v in vs]


def test_applier_family_lock_caught(tmp_path):
    path = _metrics_file(
        tmp_path,
        "def f(c):\n"
        "    c.inc('applier.stage.secs')\n")  # typo'd member
    vs = metrics_check.check_file(path, repo_root=str(tmp_path))
    assert len(vs) == 1 and 'locked "applier.*" family' in vs[0].message, \
        [str(v) for v in vs]
    assert "applier.stage.seconds" in vs[0].message


def test_fanout_family_lock_caught(tmp_path):
    path = _metrics_file(
        tmp_path,
        "def f(c):\n"
        "    c.inc('fanout.relay.reencodes')\n")  # typo'd member
    vs = metrics_check.check_file(path, repo_root=str(tmp_path))
    assert len(vs) == 1 and 'locked "fanout.*" family' in vs[0].message, \
        [str(v) for v in vs]
    assert "fanout.relay.encodes" in vs[0].message


def test_presence_family_lock_caught(tmp_path):
    path = _metrics_file(
        tmp_path,
        "def f(c):\n"
        "    c.inc('presence.lane.coalesces')\n")  # not a member
    vs = metrics_check.check_file(path, repo_root=str(tmp_path))
    assert len(vs) == 1 and 'locked "presence.*" family' in vs[0].message, \
        [str(v) for v in vs]
    assert "presence.lane.coalesced" in vs[0].message


def test_readonly_family_lock_caught(tmp_path):
    path = _metrics_file(
        tmp_path,
        "def f(c):\n"
        "    c.inc('session.readonly.opens')\n")
    vs = metrics_check.check_file(path, repo_root=str(tmp_path))
    assert len(vs) == 1 \
        and 'locked "session.readonly.*" family' in vs[0].message, \
        [str(v) for v in vs]


def test_fanout_prefix_does_not_lock_net_fanout(tmp_path):
    # the front end's encode-once cache counters live under
    # "net.fanout.*" — the "fanout." lock must not swallow them
    path = _metrics_file(
        tmp_path,
        "def f(c):\n"
        "    c.inc('net.fanout.encodes')\n"
        "    c.inc('net.fanout.cache_hits')\n")
    assert metrics_check.check_file(path, repo_root=str(tmp_path)) == []


def test_boot_family_members_pass(tmp_path):
    path = _metrics_file(
        tmp_path,
        "def f(c):\n"
        "    c.inc('boot.snapshot.used')\n"
        "    c.inc('boot.backfill.bounded')\n"
        "    c.inc('storage.snapshot.served')\n"
        "    c.inc('placement.epoch.bumps')\n"
        "    c.inc('applier.stage.overlap_ratio')\n"
        "    c.inc('fanout.relay.splices')\n"
        "    c.inc('presence.lane.coalesced')\n"
        "    c.inc('session.readonly.connects')\n")
    assert metrics_check.check_file(path, repo_root=str(tmp_path)) == []


def _span_tree(tmp_path, mod_src, catalog_src=None):
    """A fake repo with a utils/telemetry.py span catalog and one
    module."""
    pkg = tmp_path / "fluidframework_tpu"
    (pkg / "utils").mkdir(parents=True)
    (pkg / "utils" / "telemetry.py").write_text(
        catalog_src if catalog_src is not None else
        'SPANS = {"stage.poll": "x", "log.flush": "x"}\n'
        'SAMPLES = {"frontend.loop.lag_ms": "x"}\n')
    path = pkg / "mod.py"
    path.write_text(mod_src)
    return str(path)


def test_uncatalogued_span_name_caught(tmp_path):
    path = _span_tree(
        tmp_path,
        "from .utils import telemetry\n"
        "from .utils.telemetry import record_span, sample, span\n"
        "def f(n):\n"
        "    with span('stage.pol'):\n"          # typo'd span
        "        telemetry.span('log.flush')\n"  # catalogued: fine
        "    record_span('frontend.stall', 0, 1)\n"
        "    sample('frontend.loop.lag', 1.0)\n"
        "    span(n)\n")  # computed: out of scope
    vs = metrics_check.check_file(path, repo_root=str(tmp_path))
    msgs = [v.message for v in vs]
    assert len(vs) == 3, msgs
    assert any("stage.pol" in m and "SPANS" in m for m in msgs)
    assert any("frontend.stall" in m for m in msgs)
    assert any("frontend.loop.lag" in m and "SAMPLES" in m for m in msgs)


def test_nonliteral_span_catalog_caught(tmp_path):
    _span_tree(tmp_path, "x = 1\n",
               catalog_src="SPANS = dict(make())\nSAMPLES = {}\n")
    vs = metrics_check.check_metrics(repo_root=str(tmp_path))
    assert len(vs) == 1 and "pure dict" in vs[0].message, \
        [str(v) for v in vs]


def test_metrics_real_tree_clean():
    assert metrics_check.check_metrics(repo_root=REPO) == []


def test_journal_family_lock_caught(tmp_path):
    path = _metrics_file(
        tmp_path,
        "def f(c):\n"
        "    c.inc('obs.journal.writes')\n")  # not a member
    vs = metrics_check.check_file(path, repo_root=str(tmp_path))
    assert len(vs) == 1 \
        and 'locked "obs.journal.*" family' in vs[0].message, \
        [str(v) for v in vs]
    assert "obs.journal.entries" in vs[0].message


def test_health_family_lock_caught(tmp_path):
    # the health plane's probe/engine names are an operator contract
    # (dashboards + Fleet.wait_healthy key on them): the family locks
    path = _metrics_file(
        tmp_path,
        "def f(c):\n"
        "    c.inc('health.probe.errors')\n")  # not a member
    vs = metrics_check.check_file(path, repo_root=str(tmp_path))
    assert len(vs) == 1 and 'locked "health.*" family' in vs[0].message, \
        [str(v) for v in vs]
    assert "health.probe.failures" in vs[0].message


def test_health_family_members_pass(tmp_path):
    path = _metrics_file(
        tmp_path,
        "def f(c):\n"
        "    c.inc('health.probe.ms')\n"
        "    c.inc('health.probe.failures')\n"
        "    c.inc('health.engine.state')\n")
    assert metrics_check.check_file(path, repo_root=str(tmp_path)) == []


# ---------------------------------------------------------- journal-kind

def _journal_tree(tmp_path, mod_src, kinds_src=None):
    """A fake repo with an obs/journal.py KINDS table and one module."""
    pkg = tmp_path / "fluidframework_tpu"
    obs = pkg / "obs"
    obs.mkdir(parents=True)
    (obs / "journal.py").write_text(
        kinds_src if kinds_src is not None else
        'KINDS = {"epoch.bump": "x", "migration.seal": "x",\n'
        '         "core.start": "x", "core.recover": "x"}\n')
    path = pkg / "mod.py"
    path.write_text(mod_src)
    return str(path)


def test_journal_undeclared_kind_caught(tmp_path):
    path = _journal_tree(
        tmp_path,
        "def f(jr):\n"
        "    jr.emit('migration.sealed', part=1)\n")  # typo'd kind
    kinds = journal_check.load_kinds(str(tmp_path))
    vs = journal_check.check_file(path, kinds, repo_root=str(tmp_path))
    assert len(vs) == 1 and "migration.sealed" in vs[0].message, \
        [str(v) for v in vs]


def test_journal_kind_kwarg_and_ifexp_checked(tmp_path):
    # kind= keyword and both arms of a conditional are all literals
    path = _journal_tree(
        tmp_path,
        "def f(jr, n):\n"
        "    jr.emit(kind='lease.claim')\n"  # undeclared in the fake table
        "    jr.emit('core.recover' if n else 'core.stop')\n")
    kinds = journal_check.load_kinds(str(tmp_path))
    vs = journal_check.check_file(path, kinds, repo_root=str(tmp_path))
    msgs = [v.message for v in vs]
    assert len(vs) == 2, msgs
    assert any("lease.claim" in m for m in msgs)
    assert any("core.stop" in m for m in msgs)


def test_journal_declared_kinds_and_dict_emits_pass(tmp_path):
    path = _journal_tree(
        tmp_path,
        "def f(jr, stage):\n"
        "    jr.emit('epoch.bump', part=0)\n"
        "    jr.emit('migration.seal', cause=None)\n"
        "    stage.emit({'kind': 'applied'})\n"  # backchannel: out of scope
        "    jr.emit(computed_kind())\n")  # computed: out of scope
    kinds = journal_check.load_kinds(str(tmp_path))
    assert journal_check.check_file(path, kinds,
                                    repo_root=str(tmp_path)) == []


def test_journal_nonliteral_kinds_table_caught(tmp_path):
    _journal_tree(tmp_path, "x = 1\n",
                  kinds_src="KINDS = dict(make_kinds())\n")
    vs = journal_check.check_journal_kinds(repo_root=str(tmp_path))
    assert len(vs) == 1 and "pure dict literal" in vs[0].message, \
        [str(v) for v in vs]


def test_journal_real_tree_clean():
    assert journal_check.check_journal_kinds(repo_root=REPO) == []


def test_health_journal_kinds_declared():
    # the HealthEngine's transition/probe entries are declared in the
    # real KINDS table (the journal-kind pass enforces emit sites;
    # this pins the declarations themselves against deletion)
    kinds = journal_check.load_kinds(REPO)
    assert "health.state" in kinds
    assert "health.probe" in kinds
    assert "flight.dump" in kinds  # the critical-transition evidence


# ------------------------------------------------------------------- CLI

def _run_cli(*argv, cwd=REPO):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "tools.fluidlint", *argv],
        cwd=cwd, env=env, capture_output=True, text=True)


def test_cli_clean_on_real_tree_fast_passes():
    # layers + wire + hygiene; the jaxpr pass is covered in-process above
    r = _run_cli("--pass", "layers", "--pass", "wire", "--pass", "hygiene")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "clean" in r.stdout


def test_cli_exits_nonzero_on_violation():
    bad_root = os.path.join(FIX, "layering_bad")
    r = _run_cli("--pass", "layers", "--repo-root", bad_root)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "'utils' may not import 'protocol'" in r.stdout


# -------------------------------------------------- frame-id registry

def test_frame_registry_real_tree_clean():
    # every FT_* id unique, every id paired with both codec halves in
    # registries.FT_CODECS, no stale manifest entries
    assert wire_check.check_frame_registry(repo_root=REPO) == []


def test_frame_registry_seeded_violations():
    msgs = [v.message for v in wire_check.check_frame_registry(
        repo_root=os.path.join(FIX, "wire_registry"))]
    joined = "\n".join(msgs)
    # a reused wire id is version skew baked into one binary
    assert ("frame id 1 is assigned to both FT_SUBMIT and FT_OPS"
            in joined)
    # a frame id with no (encoder, decoder) manifest entry
    assert "FT_BOGUS has no (encoder, decoder) entry" in joined
