"""executor.compile_ms: milliseconds a request spends compiling.

Median over the window's requests of the summed ``executor.compile``
span time inside each ``request:<app>`` annotation (the first call of a
freshly built iteration jit: jaxpr trace, lowering, compile or
persistent-cache fetch), from ``bench/spanreduce.py`` on the traced
window's ``record.spans``. Layer: executor (``core/executor.py``).
"""
from bench import spanreduce


def read(record):
    return spanreduce.compile_ms(getattr(record, "spans", None))
