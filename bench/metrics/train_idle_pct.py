"""train_idle_pct: share of the traced training window with no device op, %.

1 - (union of device-busy intervals / traced window), averaged over the
cell's devices (``bench.trace``).
"""


def read(layer):
    w = layer.window
    if w is None or w.window_s <= 0 or w.busy_s <= 0:
        return None
    return 100.0 * (1.0 - w.busy_s / w.window_s)
