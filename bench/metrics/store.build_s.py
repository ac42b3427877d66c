"""store.build_s: seconds to build the GraphStore (DBG and partitioning).

The benchmark's host timer around ``GraphService.register``, which
builds the store eagerly. Layer: store (``core/store.py``).
"""


def read(record):
    return record.setup.get("store_build_s")
