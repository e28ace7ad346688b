"""Mean device milliseconds of one dispatch of the long tier's kernel
(``long_apply``) in the traced window, from its events in the device
trace: what the tier's wave costs the chip each turn a long doc moved."""


def read(layer):
    k = layer.get("long_kernel") or {}
    return 1e3 * k["seconds"] / k["n"] if k.get("n") else None
