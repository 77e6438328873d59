"""device_idle_share (%): the share of the traced window in which no
operation ran on the device, averaged over the chips.  Layer: device."""
from fedbench import tracing


def read(r):
    window = tracing.window_ns(r.trace) / 1e9
    return 100.0 * (1.0 - tracing.mean_busy_s(r.trace) / window)
