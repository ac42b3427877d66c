"""kernel.big_share_pct: device time in Big GAS kernel launches.

Percent of the traced window covered by kernel events whose metadata
names the Big pipeline (``bench/spanreduce.py``). Layer: kernel
(``kernels/big_pipeline.py``).
"""
from bench import spanreduce


def read(record):
    return spanreduce.kind_pct(getattr(record, "spans", None), "big")
