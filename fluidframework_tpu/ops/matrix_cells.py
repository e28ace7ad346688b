"""matrix_cells: SharedMatrix ``setCell`` ops applied on the device.

A grid's row and column permutation vectors are two rows of the
merge-tree farm (service/tpu_applier.py), applied by the farm's own wave.
A vector row's text arena holds nothing but its handles, one character
each, allocated in insert order, so a segment's ``text_start`` is the
handle of its first item and item ``o`` of it has handle
``text_start + o`` (a split advances the tail's ``text_start`` by the
offset, so this holds for every slot). The cells of grid ``g`` are
``cells[g]``, an [H, H] int32 plane indexed by (row handle, column
handle), and ``written[g]`` an int8 plane of the same shape, 1 where a
cell was ever written (an int32 cell has no value free to mean "never
set").

One dispatch takes a wave of setCell rows, int32 [G, K, CELL_FIELDS],
grid ``g``'s rows in seq order along K. Each row is resolved at its
author's (refSeq, client) perspective through both vectors, with the
farm's visibility rule (``ops/apply._visibility``) read from the vector
rows as they stand: the applier dispatches a grid's setCells after
every vector op of lower seq and before any of higher seq. Then
``cells[g, rh, ch] = value``:

- a row whose row or column is removed by the time it applies (a remove
  stamp on the slot found: every stamp on the farm is below its seq) is
  a dead write, and writes nothing; so is a position outside its
  perspective's vector;
- the cells are written one at a time, in (grid, seq) order, by a loop
  of single-element updates as long as the wave's writes, each marking
  its cell in ``written``: of two rows of one grid that name one cell,
  the later (higher seq) wins. (XLA's scatter picks an unspecified
  winner among duplicate indices, and on the TPU it flattens the store,
  which copies all of it each call.)

It returns the store and the marks, the flat index ``(g * H + rh) * H +
ch`` and value of each cell written (-1 where none), and the count of
dead writes: the checkpoint's delta carries the cells written, not the
store.

``written_cells`` reads the cells ever written back for a base, in
fixed-shape pages of C cells a grid: the device finds them from the
marks, so the host keeps no set of them and a base takes one dispatch
per C cells of its fullest grid.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .apply import _visibility
from .doc_state import NO_SEQ, DocState

#: the setCell row layout (int32[CELL_FIELDS]); VALID 0 is padding
CELL_FIELDS = 6
C_VALID, C_ROW, C_COL, C_REF, C_CLIENT, C_VALUE = range(CELL_FIELDS)

#: the jitted step's name: its module in the device trace is
#: ``jit_matrix_cells``
KERNEL_NAME = "matrix_cells"


def _resolve_one(vec: DocState, pos, ref, client):
    """(handle, found, removed) of item ``pos`` of one vector row at the
    (ref, client) perspective."""
    vis, vlen, cum = _visibility(vec, ref, client)
    hit = vis & (cum <= pos) & (pos < cum + vlen)
    handle = jnp.sum(jnp.where(hit, vec.text_start + pos - cum, 0))
    return handle, jnp.any(hit), jnp.any(hit & (vec.rem_seq != NO_SEQ))


# [G vectors] x [K positions each]
_resolve = jax.vmap(jax.vmap(_resolve_one, in_axes=(None, 0, 0, 0)))


def matrix_cells(cells, written, farm: DocState, rows, cols, wave):
    """Apply one wave of setCell rows; see the module docstring.

    ``cells`` int32 [G, H, H]; ``written`` int8 [G, H, H]; ``farm`` the
    [D, S] merge-tree farm; ``rows``/``cols`` int32 [G], each grid's
    row- and column-vector farm rows; ``wave`` int32 [G, K,
    CELL_FIELDS]. Returns (cells, written, written flat index [G, K],
    written value [G, K], dead writes)."""
    G, H, _ = cells.shape
    K = wave.shape[1]
    rv = jax.tree.map(lambda a: a[rows], farm)
    cv = jax.tree.map(lambda a: a[cols], farm)
    valid = wave[..., C_VALID] != 0
    ref, client = wave[..., C_REF], wave[..., C_CLIENT]
    rh, r_found, r_gone = _resolve(rv, wave[..., C_ROW], ref, client)
    ch, c_found, c_gone = _resolve(cv, wave[..., C_COL], ref, client)
    live = valid & r_found & c_found & ~r_gone & ~c_gone
    g = jnp.broadcast_to(jnp.arange(G, dtype=jnp.int32)[:, None], (G, K))
    value = wave[..., C_VALUE]
    # the writes first, in (grid, seq) order
    order = jnp.argsort(~live.ravel(), stable=True)
    gs, rs, cs, vs = (a.ravel()[order] for a in (g, rh, ch, value))

    one = jnp.ones((1, 1, 1), written.dtype)

    def write(i, carry):
        cells, written = carry
        at = (gs[i], rs[i], cs[i])
        return (lax.dynamic_update_slice(cells, vs[i].reshape(1, 1, 1), at),
                lax.dynamic_update_slice(written, one, at))

    cells, written = lax.fori_loop(0, jnp.sum(live), write, (cells, written))
    flat = (g * H + rh) * H + ch
    return (cells, written, jnp.where(live, flat, -1),
            jnp.where(live, value, 0), jnp.sum(valid & ~live))


def written_cells(cells, written, start, *, page: int):
    """One page of the cells ever written, grid by grid.

    ``start`` int32 [G]: the rank, among grid g's written cells in flat
    order, of the first one wanted. Returns (flat index [G, page], -1
    past the grid's last; value [G, page]; the count written [G]). The
    grids go one at a time (``lax.map``), so the scratch is one plane's
    ranks, not the store's."""
    G, H, _ = cells.shape
    want = jnp.arange(page, dtype=jnp.int32)

    def one(args):
        g, plane, marks, s = args
        ranks = jnp.cumsum(marks.ravel().astype(jnp.int32))
        k = s + want
        found = k < ranks[-1]
        # the k-th written cell is where the running count first
        # passes k
        pos = jnp.where(found, jnp.searchsorted(ranks, k + 1), 0)
        return (jnp.where(found, g * H * H + pos, -1),
                jnp.where(found, plane.ravel()[pos], 0), ranks[-1])

    return lax.map(one, (jnp.arange(G, dtype=jnp.int32), cells, written,
                         start))
