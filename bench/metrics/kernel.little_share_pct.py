"""kernel.little_share_pct: device time in Little GAS kernel launches.

Percent of the traced window covered by kernel events whose metadata
names the Little pipeline (``bench/spanreduce.py``). Layer: kernel
(``kernels/little_pipeline.py``).
"""
from bench import spanreduce


def read(record):
    return spanreduce.kind_pct(getattr(record, "spans", None), "little")
