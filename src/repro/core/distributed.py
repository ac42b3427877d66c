"""Chunk-granular SPMD execution over a GraphStore plan via shard_map.

One of the repo's two multi-device paths, built directly on the layered
GraphStore → Planner → Executor API: ``DistributedEngine(store, app)``
plans on the store (cached per :class:`~.planner.PlanConfig`), re-chunks
the plan's blocked works into fixed-shape units (tile-snapped, so chunks
never share a destination tile), LPT-balances chunks across the mesh
with a uniform per-block cost model, and runs ONE ``shard_map`` program
in which every device scans its stacked chunk queue — Little chunks and
Big chunks — accumulating a device-local property delta. The
cross-device merge is a collective psum/pmin/pmax (tiles are
device-disjoint, so 'or' merges exactly via psum).

The other path is ``repro.sharding`` (lane-granular: the packed lane
payload is the shard unit, per-device jit'd fns instead of one SPMD
program, native payload shapes, streaming payload-residency reuse).
This module trades that flexibility for a single fixed-shape SPMD
program — padding chunks to a uniform (depth, B, E_BLK) stack — which
is the shape collective-offload compilers want; it also serves as the
shard_map reference the lane-granular path is tested against.

At real scale the vertex property array would be window-sharded with a
halo exchange; on a multi-pod mesh the graph engine runs as a
per-pod-replica service, so vprops stays replicated here (it is the
small array; edges dominate and are fully sharded).
"""
from __future__ import annotations

from functools import partial
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels import ops
from ..kernels import ref as ref_mod
from .executor import init_props
from .gas import GATHER_IDENTITY
from .types import BlockedEdges, Geometry

def _mark_varying(x, axis: str):
    """Tell the manual-axes checker the accumulator diverges across
    devices once sharded chunks land."""
    return jax.lax.pcast(x, (axis,), to="varying")


def _chunk_work(work: BlockedEdges, blocks_per_chunk: int) -> List[tuple]:
    """Split a work into tile-snapped chunks of <= blocks_per_chunk."""
    chunks = []
    lo = 0
    while lo < work.n_blocks:
        hi = ops.snap_down(work, min(lo + blocks_per_chunk, work.n_blocks))
        if hi <= lo:  # giant tile: overflow a chunk (rare; keep correctness)
            nxt = lo + blocks_per_chunk
            while nxt < work.n_blocks and work.tile_first[nxt] != 1:
                nxt += 1
            hi = min(nxt, work.n_blocks)
        chunks.append((work, lo, hi))
        lo = hi
    return chunks


def _stack_chunks(chunks, B, geom: Geometry, umax: int, kind: str):
    """Pad each chunk to B blocks / B tiles and stack into numpy arrays."""
    E = geom.E_BLK
    n = len(chunks)
    out = {
        "src_local": np.zeros((n, B, E), np.int32),
        "dst_local": np.zeros((n, B, E), np.int32),
        "weights": np.zeros((n, B, E), np.float32),
        "valid": np.zeros((n, B, E), np.int32),
        "window_id": np.zeros((n, B), np.int32),
        "tile_id": np.zeros((n, B), np.int32),
        "tile_first": np.zeros((n, B), np.int32),
        "tile_idx": np.full((n, B), 2**30, np.int32),  # OOB -> dropped
    }
    if kind == "big":
        out["unique_src"] = np.zeros((n, umax), np.int32)
    for ci, (work, lo, hi) in enumerate(chunks):
        nb = hi - lo
        t0 = int(work.tile_id[lo])
        t1 = int(work.tile_id[hi - 1]) + 1
        out["src_local"][ci, :nb] = work.src_local[lo:hi]
        out["dst_local"][ci, :nb] = work.dst_local[lo:hi]
        out["weights"][ci, :nb] = work.weights[lo:hi]
        out["valid"][ci, :nb] = work.valid[lo:hi]
        out["window_id"][ci, :nb] = work.window_id[lo:hi]
        out["window_id"][ci, nb:] = work.window_id[hi - 1] if nb else 0
        tid = work.tile_id[lo:hi] - t0
        out["tile_id"][ci, :nb] = tid
        out["tile_id"][ci, nb:] = tid[-1] if nb else 0
        tf = work.tile_first[lo:hi].copy()
        if nb:
            tf[0] = 1
        out["tile_first"][ci, :nb] = tf
        out["tile_idx"][ci, :t1 - t0] = work.tile_dst_start[t0:t1] // geom.T
        if kind == "big":
            u = work.unique_src
            out["unique_src"][ci, :u.shape[0]] = u
    return out


class DistributedEngine:
    """Chunk-granular SPMD runner for one app on a GraphStore.

    Parameters
    ----------
    store:  a prepared :class:`~.store.GraphStore`.
    app:    the :class:`~.gas.GASApp` to execute.
    config: :class:`~.planner.PlanConfig` for the (cached) plan whose
            blocked works are chunked; defaults to ``PlanConfig()``.
    mesh:   jax mesh to run on (defaults to a 1-D mesh over every
            local device).
    blocks_per_chunk: chunk size in E_BLK blocks before tile-snapping
            (the fixed shape every chunk is padded to).
    axis:   mesh axis name the chunk queues are sharded over.

    ``run`` matches ``Executor.run``'s contract: returns props in
    ORIGINAL vertex ids plus an iteration count, numerically matching
    the single-device paths up to reduction order (the collective merge
    is exact for min/max/or; 'sum' apps may differ by 1 ULP).
    """

    def __init__(self, store, app, config=None, mesh: Optional[Mesh] = None,
                 blocks_per_chunk: int = 32, axis: str = "pipe"):
        from .planner import PlanConfig
        self.store = store
        self.app = app
        self.bundle = store.plan(config or PlanConfig())
        self.axis = axis
        self.geom = store.geom
        self.V_pad = store.V_pad
        if mesh is None:
            devs = np.array(jax.devices())
            mesh = Mesh(devs, (axis,))
        self.mesh = mesh
        self.n_dev = mesh.devices.size
        B = blocks_per_chunk

        little = [c for w in self.bundle.little_works.values()
                  for c in _chunk_work(w, B)]
        big = [c for w in self.bundle.big_works for c in _chunk_work(w, B)]
        self.Bl = max([hi - lo for _, lo, hi in little], default=1)
        self.Bb = max([hi - lo for _, lo, hi in big], default=1)
        umax = max([w.unique_src.shape[0]
                    for w in self.bundle.big_works], default=0)
        umax = max(umax, self.geom.W)

        # LPT-balance chunks over devices (est ~ #blocks; uniform-cost model)
        def balance(chunks):
            queues = [[] for _ in range(self.n_dev)]
            loads = np.zeros(self.n_dev)
            for c in sorted(chunks, key=lambda c: -(c[2] - c[1])):
                k = int(np.argmin(loads))
                queues[k].append(c)
                loads[k] += c[2] - c[1]
            depth = max((len(q) for q in queues), default=0)
            return queues, depth

        lq, ld = balance(little)
        bq, bd = balance(big)
        self.ld, self.bd = max(ld, 1), max(bd, 1)

        def stack_all(queues, depth, Bpad, kind):
            per_dev = []
            for q in queues:
                s = _stack_chunks(q, Bpad, self.geom, umax, kind)
                pad = depth - len(q)
                if pad:
                    for k, v in s.items():
                        shape = (pad,) + v.shape[1:]
                        fill = np.full(shape, 2**30, np.int32) \
                            if k == "tile_idx" else np.zeros(shape, v.dtype)
                        s[k] = np.concatenate([v, fill], 0)
                per_dev.append(s)
            return {k: np.stack([d[k] for d in per_dev])
                    for k in per_dev[0]} if per_dev else None

        self.little_stack = stack_all(lq, self.ld, self.Bl, "little")
        self.big_stack = stack_all(bq, self.bd, self.Bb, "big")
        self._iter_fn = None

    def _build(self):
        app, geom = self.app, self.geom
        ident = GATHER_IDENTITY[app.gather]
        dt = jnp.int32 if app.gather == "or" else jnp.float32
        V_pad, T, axis = self.V_pad, geom.T, self.axis
        n_rows = V_pad // T

        def run_chunk(vwin, c, n_tiles):
            return ref_mod.gas_ref(
                vwin, c["src_local"], c["dst_local"], c["weights"], c["valid"],
                c["window_id"], c["tile_id"], c["tile_first"],
                scatter_fn=app.scatter, mode=app.gather, t=T,
                n_out_tiles=n_tiles)

        def scan_queue(accum, vprops, stack, kind, n_tiles):
            def body(acc, c):
                if kind == "big":
                    vwin = vprops[c["unique_src"]].reshape(-1, geom.W)
                else:
                    vwin = vprops.reshape(-1, geom.W)
                tiles = run_chunk(vwin, c, n_tiles)
                a = acc.reshape(n_rows, T)
                a = a.at[c["tile_idx"][:n_tiles]].set(
                    tiles.astype(a.dtype), mode="drop")
                return a.reshape(-1), None
            accum, _ = jax.lax.scan(body, accum, stack)
            return accum

        combine = {"sum": jax.lax.psum, "or": jax.lax.psum,
                   "min": jax.lax.pmin, "max": jax.lax.pmax}[app.gather]

        @partial(jax.shard_map, mesh=self.mesh,
                 in_specs=(P(), P(axis), P(axis)), out_specs=P())
        def gather_phase(vprops, little_stack, big_stack):
            # local shard keeps a leading device axis of size 1 — drop it
            squeeze = lambda s: (None if s is None else
                                 jax.tree.map(lambda x: x[0], s))
            little_stack, big_stack = squeeze(little_stack), squeeze(big_stack)
            accum = jnp.full((V_pad,), ident, dt)
            accum = _mark_varying(accum, axis)
            if little_stack is not None:
                accum = scan_queue(accum, vprops, little_stack, "little",
                                   self.Bl)
            if big_stack is not None:
                accum = scan_queue(accum, vprops, big_stack, "big", self.Bb)
            return combine(accum, axis)

        def iteration(vprops, aux, it, ls, bs):
            accum = gather_phase(vprops, ls, bs)
            return app.apply(accum, vprops, aux, it)

        return jax.jit(iteration)

    def run(self, max_iters: Optional[int] = None):
        """Run to convergence; returns ``(props, meta)`` with props in
        ORIGINAL vertex ids (the chunk queues are uploaded sharded over
        the mesh axis once, on first call)."""
        if self._iter_fn is None:
            self._iter_fn = self._build()
        vprops = init_props(self.store, self.app)
        ls = (None if self.little_stack is None else
              jax.device_put(self.little_stack,
                             NamedSharding(self.mesh, P(self.axis))))
        bs = (None if self.big_stack is None else
              jax.device_put(self.big_stack,
                             NamedSharding(self.mesh, P(self.axis))))
        iters = max_iters or self.app.max_iters
        it_done = 0
        for it in range(iters):
            new = self._iter_fn(vprops, self.store.aux, it, ls, bs)
            new.block_until_ready()
            it_done = it + 1
            if self.app.converged(vprops, new, it):
                vprops = new
                break
            vprops = new
        return np.asarray(vprops)[self.store.perm], {"iterations": it_done}
