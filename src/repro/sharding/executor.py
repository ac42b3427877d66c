"""ShardedExecutor — per-device lane ownership over the layered API.

The shard unit is the packed lane payload (``kernels.ops.pack_lane``):
:func:`~repro.sharding.placement.place_lanes` LPT-assigns lanes to
devices from the perf model's per-lane estimates (Little and Big lanes
interleaved per device), each lane's packed arrays are ``device_put``
to their OWNER device, and one jit'd function per device runs that
device's lanes locally — committed inputs pin execution to the owner,
so dispatching all device fns back-to-back runs them concurrently
(jax dispatch is async). Each device returns its output TILES (and
their global tile indices), and the primary device merges every
device's tiles with ONE tile-indexed scatter-set per iteration per
property, then runs the app's Apply.

Because lanes are globally tile-disjoint, that single scatter-set is a
complete cross-device merge — a psum/pmin/pmax over replicated
per-device accumulators (what the chunk-granular ``core.distributed``
path does inside shard_map) would compute the same values, but would
move ``n_devices × V_pad`` accumulator rows where the tile merge moves
only the real output tiles, and — decisively — it changes the program
shape around Apply: XLA re-fuses a reduce feeding an elementwise chain
differently from a scatter feeding it, which shows up as 1-ULP drift in
'sum' apps. Keeping the merge+apply region STRUCTURALLY IDENTICAL to
the fused single-device iteration (accumulator init → ``merge_all``
scatter-set → Apply) is what makes sharded results bit-identical to it
(tests/test_sharding.py asserts exact equality for all five builtin
apps on both the ref and pallas-interpret kernel paths) — the same
reasoning PR 3 applied to the fused-vs-per-entry pair.

vprops stays replicated (broadcast to every device each iteration; the
property array is the small side — edges dominate and are fully
sharded), mirroring the per-pod-replica serving layout described in
``core.distributed``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.executor import _sub_jaxprs, init_props
from ..core.gas import GASApp, GATHER_IDENTITY
from ..kernels import ops
from .placement import LanePlacement, place_lanes

__all__ = ["ShardedExecutor", "ShardedLanes", "materialize_sharded",
           "resolve_devices"]


def resolve_devices(devices=None) -> tuple:
    """Normalize a ``shard=`` / ``devices=`` argument to a device tuple.

    ``None`` or ``True`` → every local device; an ``int`` n → the first
    n local devices (n must not exceed ``jax.device_count()``); a
    sequence of jax devices → itself, verbatim.
    """
    if devices is None or devices is True:
        return tuple(jax.devices())
    if isinstance(devices, int):
        devs = jax.devices()
        if not (1 <= devices <= len(devs)):
            raise ValueError(
                f"shard={devices} devices requested but only "
                f"{len(devs)} available (hint: on CPU set XLA_FLAGS="
                f"--xla_force_host_platform_device_count=N before "
                f"importing jax)")
        return tuple(devs[:devices])
    devs = tuple(devices)
    if not devs:
        raise ValueError("devices must name at least one device")
    return devs


@dataclasses.dataclass
class ShardedLanes:
    """One plan's lanes materialized onto a fixed device tuple.

    lanes[i] is lane i's packed payload list, RESIDENT on
    ``devices[placement.device_of_lane[i]]``. ``moved``/``bytes_moved``
    account the uploads this materialization performed;
    ``reused``/``bytes_reused`` the lanes carried over resident from a
    pre-delta bundle (streaming) — together they are the
    ``shards_moved`` accounting :func:`repro.streaming.apply_delta`
    surfaces. Memoized on the owning :class:`~repro.core.planner.PlanBundle`
    (one entry per device tuple), so every app executing the plan
    sharded shares one resident copy.
    """

    devices: tuple
    placement: LanePlacement
    lanes: List[List[dict]]
    moved: int = 0
    bytes_moved: int = 0
    reused: int = 0
    bytes_reused: int = 0

    def payloads_of(self, device_idx: int) -> List[dict]:
        """The device's local execution queue: payloads of every lane it
        owns, in lane order (Little lanes first — interleaved kinds)."""
        return [p for i in self.placement.lanes_of(device_idx)
                for p in self.lanes[i]]

    def bytes_per_device(self) -> List[int]:
        out = [0] * self.placement.n_devices
        for i, lane in enumerate(self.lanes):
            out[self.placement.device_of_lane[i]] += sum(
                ops.payload_nbytes(p) for p in lane)
        return out

    def nbytes(self) -> int:
        return sum(self.bytes_per_device())

    def stats(self) -> dict:
        return {
            **self.placement.stats(),
            "lanes_per_device": [
                sum(1 for i in self.placement.lanes_of(d) if self.lanes[i])
                for d in range(self.placement.n_devices)],
            "bytes_per_device": self.bytes_per_device(),
            "shards_moved": self.moved,
            "shard_bytes_moved": self.bytes_moved,
            "shards_reused": self.reused,
            "shard_bytes_reused": self.bytes_reused,
        }


def materialize_sharded(bundle, devices: tuple,
                        keep: Optional[Dict[int, int]] = None,
                        seed: Optional[Dict[int, list]] = None
                        ) -> ShardedLanes:
    """Place a bundle's lanes and upload each to its owner device.

    ``keep`` pins lane→device assignments (streaming: clean lanes stay
    where resident); ``seed`` maps kept lane indices to their resident
    payload lists, which are spliced in without packing or transfer.
    Callers normally go through
    :meth:`repro.core.planner.PlanBundle.sharded_lanes`, which memoizes
    the result per device tuple.
    """
    placement = place_lanes(bundle.plan, len(devices), keep=keep)
    seed = seed or {}
    owners = placement.device_of_lane
    lanes, moved, bytes_moved = ops.pack_lanes_sharded(
        bundle.plan, bundle.little_works, bundle.big_works,
        owners, devices, reuse=seed,
        max_working_set=bundle.config.hw.vmem_lane_budget)
    reused = sum(1 for i, ps in seed.items() if ps)
    bytes_reused = sum(ops.payload_nbytes(p)
                       for ps in seed.values() for p in ps)
    return ShardedLanes(devices=tuple(devices), placement=placement,
                        lanes=lanes, moved=moved, bytes_moved=bytes_moved,
                        reused=reused, bytes_reused=bytes_reused)


class ShardedExecutor:
    """Multi-device counterpart of :class:`~repro.core.executor.Executor`.

    Parameters
    ----------
    store:   the :class:`~repro.core.store.GraphStore` (aux, V_pad, perm).
    bundle:  the cached :class:`~repro.core.planner.PlanBundle` to run.
    app:     the :class:`~repro.core.gas.GASApp`.
    devices: anything :func:`resolve_devices` accepts (None = all local
             devices, int = first n, or an explicit device sequence).
    path:    kernel path ("ref" | "pallas"), as in the Executor.

    Same run/time/stats surface as the Executor (``run`` returns props
    in ORIGINAL vertex ids plus a meta dict; ``time_lanes`` exists only
    on the single-device form). One iteration performs: vprops
    broadcast → per-device local execution (each lane one kernel
    launch, concurrent across devices) → ONE cross-device merge per
    property (a single tile-indexed scatter-set over every device's
    output tiles; ``cross_device_merges`` in :meth:`dispatch_stats`) →
    Apply on the primary device. Results are bit-identical to the
    single-device fused path for every gather mode.
    """

    def __init__(self, store, bundle, app: GASApp, devices=None,
                 path: Optional[str] = None):
        self.store = store
        self.bundle = bundle
        self.app = app
        self.geom = store.geom
        self.path = path or ops.default_path()
        self.V_pad = store.V_pad
        self.devices = resolve_devices(devices)

        t0 = time.perf_counter()
        self.sharded: ShardedLanes = bundle.sharded_lanes(self.devices)
        self.placement = self.sharded.placement
        # per-device local queues (payloads resident on that device)
        self._dev_payloads = [self.sharded.payloads_of(d)
                              for d in range(len(self.devices))]
        self.t_materialize = time.perf_counter() - t0

        self.aux = store.aux
        self._dev_fns = None
        self._merge_apply = None

    @property
    def plan(self):
        return self.bundle.plan

    @property
    def accum_dtype(self):
        return jnp.int32 if self.app.gather == "or" else jnp.float32

    # ------------------------------------------------------------------
    def _build(self) -> None:
        """Build the per-device local fns and the merge+apply fn.

        Each device fn takes its resident payload arrays as jit
        arguments (bound here, never folded into the lowered module);
        calling it with vprops committed to the same device executes
        there (no implicit transfers — jax refuses mixed-device jit
        inputs, which doubles as an assertion that payloads really are
        resident). It
        returns the device's concatenated output tiles + global tile
        indices; the merge+apply fn scatter-sets them all at once — the
        same ``merge_all`` + Apply program region the fused
        single-device iteration ends with (bit-identicality; see the
        module docstring)."""
        app, geom = self.app, self.geom
        ident = GATHER_IDENTITY[app.gather]
        dt = self.accum_dtype
        V_pad, path = self.V_pad, self.path

        def make_dev_fn(payloads):
            def local(vprops, arrays):
                outs = [ops.run_lane({**p, **a}, vprops, app.scatter,
                                     app.gather, path)
                        for p, a in zip(payloads, arrays)]
                return (jnp.concatenate([o[0] for o in outs]),
                        jnp.concatenate([o[1] for o in outs]))
            fn = jax.jit(local)
            arrays = [ops.device_arrays(p) for p in payloads]
            return lambda vprops: fn(vprops, arrays)

        dev_fns = [make_dev_fn(ps) if ps else None
                   for ps in self._dev_payloads]

        def merge_apply(outs, vprops, aux, it):
            accum = jnp.full((V_pad,), ident, dt)
            accum = ops.merge_all(accum, outs, geom.T)
            return app.apply(accum, vprops, aux, it)

        self._merge_apply = jax.jit(merge_apply)
        # published last: a thread that sees _dev_fns sees merge_apply
        self._dev_fns = dev_fns

    def _iterate(self, vprops, it):
        """One sharded iteration: broadcast vprops → per-device local
        lanes (concurrent) → pull each device's output tiles to the
        primary → ONE scatter-set merge + Apply there."""
        outs = []
        for d, fn in enumerate(self._dev_fns):
            if fn is None:
                continue
            t, i = fn(jax.device_put(vprops, self.devices[d]))
            outs.append((jax.device_put(t, self.devices[0]),
                         jax.device_put(i, self.devices[0])))
        return self._merge_apply(outs, vprops, self.aux, it)

    def init_props(self):
        return init_props(self.store, self.app)

    def run(self, max_iters: Optional[int] = None, collect_history=False,
            start: Optional[GASApp] = None):
        """Run to convergence; returns ``(props, meta)`` with props in
        ORIGINAL vertex ids — the same contract as ``Executor.run``,
        ``start`` included."""
        if self._dev_fns is None:
            self._build()
        vprops = init_props(self.store, start or self.app)
        iters = max_iters or self.app.max_iters
        history = []
        it_done = 0
        for it in range(iters):
            new = self._iterate(vprops, it)
            new.block_until_ready()
            it_done = it + 1
            if collect_history:
                history.append(np.asarray(new))
            if self.app.converged(vprops, new, it):
                vprops = new
                break
            vprops = new
        out = np.asarray(vprops)[self.store.perm]
        return out, {"iterations": it_done, "history": history}

    def time_iteration(self, repeats: int = 5) -> float:
        """Median wall time of one full sharded iteration (broadcast +
        local lanes + merge + apply)."""
        if self._dev_fns is None:
            self._build()
        vprops = self.init_props()
        self._iterate(vprops, 0).block_until_ready()   # warmup/compile
        ts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            self._iterate(vprops, 0).block_until_ready()
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    # ------------------------------------------------------------------
    def memory_footprint(self) -> int:
        """Device bytes pinned by the sharded payloads (summed over
        devices; shared with every executor on this bundle+devices —
        attribution for cache budgeting, not exclusive ownership)."""
        return self.sharded.nbytes()

    def merge_trace_stats(self) -> dict:
        """Trace the merge+apply program and count its scatter ops —
        the PROGRAM-DERIVED check that the cross-device merge really is
        one scatter-set per property (:meth:`dispatch_stats` reports
        the static design intent; this can actually fail if a regression
        sneaks extra merges in). Traces fresh on every call — not a hot
        path. Benchmarks/CI gate on ``merge_scatter_ops == 1``."""
        if self._dev_fns is None:
            self._build()
        vprops = self.init_props()
        outs = []
        for d, fn in enumerate(self._dev_fns):
            if fn is None:
                continue
            t, i = fn(jax.device_put(vprops, self.devices[d]))
            outs.append((jax.device_put(t, self.devices[0]),
                         jax.device_put(i, self.devices[0])))
        jaxpr = jax.make_jaxpr(self._merge_apply)(outs, vprops, self.aux,
                                                  0)

        def count_scatters(jx):
            n = sum(1 for e in jx.eqns
                    if e.primitive.name.startswith("scatter"))
            for eqn in jx.eqns:
                for v in eqn.params.values():
                    for sub in _sub_jaxprs(v):
                        n += count_scatters(sub)
            return n

        return {"merge_scatter_ops": count_scatters(jaxpr.jaxpr)}

    def dispatch_stats(self) -> dict:
        """Static launch accounting for one iteration. Kernel launches
        happen per device and run concurrently; the cross-device merge
        is exactly ONE scatter-set per property over all devices'
        output tiles (complete because lanes are tile-disjoint; verify
        against the traced program with :meth:`merge_trace_stats`)."""
        per_dev = [len(ps) for ps in self._dev_payloads]
        return {
            "shard": True,
            "n_devices": len(self.devices),
            "num_entries": sum(p["n_entries"]
                               for ps in self._dev_payloads for p in ps),
            "kernel_dispatches": sum(per_dev),
            "kernel_dispatches_per_device": per_dev,
            "cross_device_merges": 1,
            "payload_bytes": self.memory_footprint(),
        }

    def stats(self) -> dict:
        b, store = self.bundle, self.store
        return {
            "V": store.graph.num_vertices, "E": store.graph.num_edges,
            "partitions": len(b.infos),
            "little_lanes": b.plan.num_little_lanes,
            "big_lanes": b.plan.num_big_lanes,
            "est_makespan": b.plan.est_makespan,
            "placement": self.sharded.stats(),
            **self.dispatch_stats(),
        }
