"""Mean device milliseconds of one dispatch of the grids' kernel
(``matrix_cells``, its ``jit_matrix_cells`` module events in the
device trace) in the traced window: what a wave of setCells costs the
chip."""


def read(layer):
    k = layer.get("grid_kernel") or {}
    return 1e3 * k["seconds"] / k["n"] if k.get("n") else None
