"""executor.merge_apply_pct: device time outside the Pallas kernel.

Percent of the traced window in which the device runs an operation that
is not the GAS kernel: the merge scatter, apply, the Big pipeline's
source gather and the convergence reads (``bench/tracereduce.py``).
Layer: executor.
"""


def read(record):
    t = record.trace
    if not t or t["window_s"] <= 0 or t["other_s"] <= 0:
        return None
    return 100.0 * t["other_s"] / t["window_s"]
