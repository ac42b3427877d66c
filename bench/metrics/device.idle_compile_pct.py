"""device.idle_compile_pct: device idle while the host compiles.

Percent of the traced window in which the device runs nothing and the
innermost program span open on the host is ``executor.compile``
(``bench/spanreduce.py``). Layer: device.
"""
from bench import spanreduce


def read(record):
    return spanreduce.idle_pct(getattr(record, "spans", None),
                               [spanreduce.COMPILE])
