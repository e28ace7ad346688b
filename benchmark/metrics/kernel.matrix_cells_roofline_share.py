"""The grids' kernel against the HBM roofline, in %: the bytes its
dispatches in the traced window must move (``roofline_grid``: the vector
rows' slot planes and the setCell rows a dispatch, one int32 cell read
and written and one int8 mark written a cell set), over their device seconds times the device's
HBM peak (``roofline.HBM_BYTES_PER_S``). It cannot pass 100% unless the
bytes are overcounted or the time is short of the work."""

from benchmark import roofline_grid


def read(layer):
    return roofline_grid.roofline_share(layer.get("grid_kernel") or {},
                                        layer.get("device_kind"))
