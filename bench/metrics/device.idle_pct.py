"""device.idle_pct: percent of the traced window with no device op.

1 - (union of the device's operation intervals) / (traced window), from
``bench/tracereduce.py``, averaged over the chips used. Layer: device.
"""


def read(record):
    t = record.trace
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
