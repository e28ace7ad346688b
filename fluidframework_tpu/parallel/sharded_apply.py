"""Doc-sharded batched apply: the multi-chip hot step.

Documents are independent CRDTs, so the 'docs' mesh axis is pure data
parallelism — each shard applies its own docs' sequenced ops (the analog of
one Kafka partition's DocumentLambda loop, lambdas-driver
document-router/documentLambda.ts). The only cross-shard traffic is a
``psum`` of scalar stats (applied-op count, overflow count) used by the
host scheduler, so the step scales linearly over ICI/DCN.

The per-shard body is the vmapped scan kernel from ops/apply.py; zamboni
compaction runs fused in the same dispatch when ``min_seq`` advances.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.apply import (
    F_TYPE,
    OP_FIELDS,
    OP_NOOP,
    apply_ops_batch,
    compact_batch,
    unpack_wave16,
    wave_min_seq,
)
from ..ops.doc_state import DocState
from ..utils.contracts import register_kernel_contract


def doc_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for [D, ...] doc-batched arrays: split docs, replicate rest."""
    return NamedSharding(mesh, P("docs"))


def shard_state(state: DocState, mesh: Mesh) -> DocState:
    s = doc_sharding(mesh)
    return jax.tree.map(lambda x: jax.device_put(x, s), state)


def donation_supported() -> bool:
    """Whether donating the state buffer into the step is a win here.

    On TPU (and GPU) the PJRT client honors input/output aliasing and the
    dispatch stays asynchronous — donation halves the state's device
    footprint for free. The CPU client instead runs donating computations
    SYNCHRONOUSLY (the dispatch call blocks until the step completes) and
    then ignores the aliasing request anyway — donation there buys
    nothing and serializes the stage/execute overlap pipeline. Gate by
    backend so host runs keep async dispatch."""
    return jax.default_backend() != "cpu"


def make_sharded_step(mesh: Mesh, donate: Optional[bool] = None):
    """Build the jitted sharded step:

    ``step(state, ops) -> (state', stats)`` where ``state`` holds [D, S]
    segment arrays sharded over 'docs', ``ops`` is [D, K, OP_FIELDS] int32
    (NOOP-padded, each op carrying its deli msn in F_MSN), and ``stats``
    is a replicated dict of globals. Zamboni compaction runs fused per
    doc at the wave's own msn floor (apply.wave_min_seq).
    """

    def _local(state: DocState, ops: jax.Array):
        state = apply_ops_batch(state, ops)
        state = compact_batch(state, wave_min_seq(ops))
        applied = jnp.sum((ops[..., F_TYPE] != OP_NOOP).astype(jnp.int32))
        overflowed = jnp.sum(state.overflow.astype(jnp.int32))
        stats = {
            "applied_ops": jax.lax.psum(applied, "docs"),
            "overflow_docs": jax.lax.psum(overflowed, "docs"),
        }
        return state, stats

    dp = P("docs")
    sharded = jax.shard_map(
        _local,
        mesh=mesh,
        in_specs=(dp, dp),
        out_specs=(dp, P()),
        check_vma=False,
    )
    if donate is None:
        donate = donation_supported()
    return jax.jit(sharded, donate_argnums=(0,) if donate else ())


# jitted packed steps shared across applier instances, keyed on the mesh
# (hashable) + build options: per-instance closures would each re-trace
# and re-compile every wave-shape bucket
_PACKED_STEP_CACHE: dict = {}


def make_sharded_packed_step(mesh: Mesh, donate: Optional[bool] = None,
                             use_pallas: bool = False,
                             pallas_interpret: bool = False,
                             trace_hook=None):
    """The mesh lane's fast step pair ``(packed_fn, wide_fn)``:

    ``packed_fn(state, wave16, bases) -> (state', stats)`` takes the
    int16-delta packed wave (ops/apply.unpack_wave16 wire format) with
    int32 [D, 2] per-doc bases; ``wide_fn(state, wave)`` is the int32
    escape lane (giant docs / huge windows / chaos force_wide). Both
    shard every [D, ...] input over 'docs' — each device unpacks and
    applies ONLY its own rows — donate the state, and psum scalar stats
    only, so the step scales linearly over ICI/DCN like the plain
    ``make_sharded_step``.

    ``trace_hook(kernel, shape)`` (optional) runs at TRACE time inside
    the jitted body — the service layer injects its recompile-telemetry
    counter through it (parallel must not import obs; layer DAG)."""
    if donate is None:
        donate = donation_supported()
    key = (mesh, donate, use_pallas, pallas_interpret)
    fn = _PACKED_STEP_CACHE.get(key)
    if fn is not None:
        return fn
    if use_pallas:
        from ..ops.pallas_apply import pallas_apply_ops_batch

        def apply_fn(state, wave):
            return pallas_apply_ops_batch(
                state, wave, interpret=pallas_interpret)
    else:
        apply_fn = apply_ops_batch

    def _apply_local(state, wave, shape):
        if trace_hook is not None:
            trace_hook("sharded_step_packed", shape)
        state = apply_fn(state, wave)
        state = compact_batch(state, wave_min_seq(wave))
        applied = jnp.sum((wave[..., F_TYPE] != OP_NOOP).astype(jnp.int32))
        overflowed = jnp.sum(state.overflow.astype(jnp.int32))
        stats = {
            "applied_ops": jax.lax.psum(applied, "docs"),
            "overflow_docs": jax.lax.psum(overflowed, "docs"),
        }
        return state, stats

    def _local_packed(state: DocState, wave16, bases):
        shape = "x".join(map(str, wave16.shape[:2]))
        return _apply_local(state, unpack_wave16(wave16, bases), shape)

    def _local_wide(state: DocState, wave):
        shape = "x".join(map(str, wave.shape[:2])) + "w"
        return _apply_local(state, wave, shape)

    dp = P("docs")
    don = (0,) if donate else ()
    packed = jax.shard_map(_local_packed, mesh=mesh,
                           in_specs=(dp, dp, dp), out_specs=(dp, P()),
                           check_vma=False)
    wide = jax.shard_map(_local_wide, mesh=mesh, in_specs=(dp, dp),
                         out_specs=(dp, P()), check_vma=False)
    fn = (jax.jit(packed, donate_argnums=don),
          jax.jit(wide, donate_argnums=don))
    _PACKED_STEP_CACHE[key] = fn
    return fn


def _contract_build():
    """Build the sharded step on a 1-device 'docs' mesh — the contract
    is about the traced program, which is shard-count-invariant."""
    import numpy as np

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("docs",))
    step = make_sharded_step(mesh, donate=False)

    def example():
        D, S, K = 8, 16, 4
        state = jax.vmap(lambda _: DocState.empty(S))(jnp.arange(D))
        state = shard_state(state, mesh)
        ops = jnp.zeros((D, K, OP_FIELDS), jnp.int32)
        return (state, ops), {}

    return step, example


# contract: per-op path gather-free; the only gathers are zamboni
# compaction's once-per-wave argsort repack (one per DocState field).
# Collectives (psum of stats) are not memory gathers and don't count.
register_kernel_contract(
    "parallel.sharded_step",
    build=_contract_build,
    no_scatter=True,
    max_gathers=10,
    single_jit=True,
    notes="doc-sharded apply + fused zamboni over the 'docs' mesh axis",
)


def _packed_contract_build():
    """The packed mesh step at a small fixed geometry on a 1-device
    'docs' mesh (the traced program is shard-count-invariant)."""
    import numpy as np

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("docs",))
    step, _wide = make_sharded_packed_step(mesh, donate=False)

    def example():
        D, S, K = 8, 16, 4
        state = jax.vmap(lambda _: DocState.empty(S))(jnp.arange(D))
        state = shard_state(state, mesh)
        wave16 = jnp.zeros((D, K, OP_FIELDS), jnp.int16)
        bases = jnp.zeros((D, 2), jnp.int32)
        return (state, wave16, bases), {}

    return step, example


# contract: the wave arrives int16 and must be EXPLICITLY widened before
# any arithmetic (no_int16_arithmetic catches silent promotion); the
# unpack+apply is gather-free, the fused zamboni repack owns the only
# gathers (one per DocState field, once per wave, off the K-amplified
# path); psum of scalar stats is a collective, not a memory gather; one
# compile per wave-shape bucket.
register_kernel_contract(
    "parallel.sharded_step_packed",
    build=_packed_contract_build,
    no_scatter=True,
    max_gathers=10,
    no_int16_arithmetic=True,
    single_jit=True,
    notes="int16 packed-wave unpack + doc-sharded apply + fused zamboni",
)


def _packed_pallas_contract_build():
    """The packed mesh step with the per-shard Pallas apply selected
    (interpret mode so the contract checks run on any backend; the
    traced program is what the contract is about and is identical to
    the Mosaic-lowered one)."""
    import numpy as np

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("docs",))
    step, _wide = make_sharded_packed_step(
        mesh, donate=False, use_pallas=True, pallas_interpret=True)

    def example():
        D, S, K = 8, 16, 4
        state = jax.vmap(lambda _: DocState.empty(S))(jnp.arange(D))
        state = shard_state(state, mesh)
        wave16 = jnp.zeros((D, K, OP_FIELDS), jnp.int16)
        bases = jnp.zeros((D, 2), jnp.int32)
        return (state, wave16, bases), {}

    return step, example


# contract: the mesh lane's Pallas selection keeps every invariant of the
# XLA lane — the checker walks INTO the pallas_call jaxpr inside the
# shard_map body, so the segmented-scan rewrite cannot smuggle a scatter,
# an extra gather, or silent int16 promotion past the lint
register_kernel_contract(
    "parallel.sharded_step_packed_pallas",
    build=_packed_pallas_contract_build,
    no_scatter=True,
    max_gathers=10,
    no_int16_arithmetic=True,
    single_jit=True,
    notes="int16 packed wave + per-shard Pallas VMEM apply + fused "
          "zamboni over the 'docs' mesh axis",
)
