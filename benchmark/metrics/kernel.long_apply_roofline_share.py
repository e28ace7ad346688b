"""The long tier's kernel against the HBM roofline, in %: the bytes one
dispatch must move (``roofline.long_apply_bytes``, from the tier's
shapes), times the dispatches in the traced window, over their device
seconds times the device's HBM peak (``roofline.HBM_BYTES_PER_S``). The
apply is bound by the vector unit, not memory, so the share is small;
it cannot pass 100% unless the bytes are overcounted or the time is
short of the work."""

from benchmark import roofline


def read(layer):
    k = layer.get("long_kernel") or {}
    if not k.get("n") or not k.get("seconds"):
        return None
    moved = k["n"] * roofline.long_apply_bytes(*k["shape"])
    return 100.0 * moved / (k["seconds"] * roofline.hbm_bytes_per_s(
        layer["device_kind"]))
