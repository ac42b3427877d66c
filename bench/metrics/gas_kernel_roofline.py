"""gas_kernel_roofline: the GAS kernel's share of its HBM roofline.

The least time the chip needs for the window's iterations (the
algorithm's bytes over peak HBM bandwidth, ``bench/roofline.py``; the
operations term is far smaller, so HBM bounds it) over the kernel's
device time in the trace, in percent. Layer: kernel.
"""
from bench import roofline


def read(record):
    t = record.trace
    if not t or t["kernel_s"] <= 0:
        return None
    n_v, n_e = record.plan["num_vertices"], record.plan["num_edges"]
    least = sum(roofline.roofline_s(r.app, n_e, n_v, r.iterations,
                                    record.device_kind)
                for r in record.requests if r.error is None)
    return 100.0 * least / t["kernel_s"] if least > 0 else None
