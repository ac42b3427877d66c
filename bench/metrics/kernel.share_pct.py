"""kernel.share_pct: device time in the Pallas GAS kernel.

Percent of the traced window covered by the kernel's device events
(``bench/tracereduce.py``). Little and Big launches are one kernel
body and are not told apart in the trace. Layer: kernel
(``kernels/gas_kernel.py``).
"""


def read(record):
    t = record.trace
    if not t or t["window_s"] <= 0 or t["kernel_s"] <= 0:
        return None
    return 100.0 * t["kernel_s"] / t["window_s"]
