"""Device-mesh sharding for the batch evaluator (SURVEY.md §7 stage 9).

The scaling axes of this domain are the pod and node dimensions of the
(pods × nodes) scheduling matrices — the analog of data/model parallelism
(SURVEY.md §5.7/§5.8).  Design, per the standard JAX recipe: pick a Mesh,
annotate the tables' shardings, and let XLA's GSPMD partitioner insert the
collectives (the masked-argmax reduction over sharded node columns rides
ICI as tree-reduce; nothing NCCL-like is hand-written).

Mesh axes:
* ``"pods"``  — data-parallel axis: pod waves split across devices; each
  device schedules its pod shard independently (decisions are per-pod).
* ``"nodes"`` — model-parallel axis: the node table splits across devices;
  per-pod reductions (max score, min tie-break hash) become cross-device
  collectives inserted by XLA.

The reference has no equivalent — its "fabric" is client-go informers +
REST over loopback (k8sapiserver.go:45-62); multi-host scale-out there
means nothing.  Here one chip holds ~10k nodes easily; the node axis is
sharded when the cluster (or the pod wave) outgrows one chip's HBM.
"""

from __future__ import annotations

import math
import os
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from minisched_tpu.models.tables import NodeTable, PodTable

POD_AXIS = "pods"
NODE_AXIS = "nodes"


def mesh_shape_key(mesh: Optional[Mesh]) -> Tuple:
    """Hashable (axis, size) signature of a mesh — folded into every
    compile-cache key the mesh path touches (ISSUE 7 satellite: an
    executable compiled for one mesh factoring must never be served to
    another, even where the table shapes coincide)."""
    if mesh is None:
        return ()
    return tuple((name, int(size)) for name, size in mesh.shape.items())


def mesh_axis_sizes(mesh: Optional[Mesh]) -> Tuple[int, int]:
    """(pod-axis size, node-axis size); (1, 1) off-mesh."""
    if mesh is None:
        return 1, 1
    return int(mesh.shape[POD_AXIS]), int(mesh.shape[NODE_AXIS])


def cap_multiple(base: int, axis: int) -> int:
    """Table-capacity quantum under a mesh axis: capacities must stay
    lane-padded (multiples of ``base``) AND divide evenly across the
    axis's shards — lcm covers non-power-of-two factorings (a 6-device
    2×3 mesh) where 128 alone would leave a 3-shard axis with ragged
    tiles."""
    return base * axis // math.gcd(base, axis)


def resolve_mesh(env: Optional[Dict[str, str]] = None) -> Optional[Mesh]:
    """The live engine's startup mesh policy (ISSUE 7 tentpole):

    * ``MINISCHED_MESH=0`` — never shard (the single-device packed path,
      byte-for-byte the pre-mesh engine);
    * ``MINISCHED_MESH=1`` — always build a mesh over every visible
      device, even a degenerate 1-device one (same placements, exercises
      the sharded program);
    * unset — auto: a mesh exactly when ``jax.device_count() > 1``
      (multi-chip hosts shard by default, laptops/CI keep the exact
      single-device behavior).

    ``MINISCHED_MESH_POD_SHARDS`` pins the pod-axis factoring (default:
    hosts on the pod axis, chips on the node axis — see make_mesh)."""
    env = env if env is not None else os.environ
    flag = env.get("MINISCHED_MESH", "")
    if flag == "0":
        return None
    if flag not in ("", "0", "1"):
        raise ValueError(f"MINISCHED_MESH must be '', '0' or '1', got {flag!r}")
    if flag != "1" and jax.device_count() <= 1:
        return None
    pod_shards = env.get("MINISCHED_MESH_POD_SHARDS", "")
    return make_mesh(pod_shards=int(pod_shards) if pod_shards else None)


def default_pod_shards(n_devices: int, n_processes: int = 1) -> int:
    """The pod-axis size of the 2D mesh factoring.

    Multi-host: the pod axis is DATA-parallel — per-pod decisions need no
    cross-pod-shard collectives — while the node axis carries the
    argmax/argmin reductions.  So hosts belong on the POD axis (the
    inter-host DCN link only moves the final per-pod results) and each
    host's chips on the NODE axis (the per-wave collectives ride ICI) —
    the standard "DCN on the data axis, ICI on the model axis" recipe.
    Single host: largest power-of-two divisor ≤ √n keeps the per-device
    (P, N) tiles near-square (HBM-friendly).
    """
    if n_processes > 1 and n_devices % n_processes == 0:
        return n_processes
    shards = 1
    while shards * 2 <= math.isqrt(n_devices) and n_devices % (shards * 2) == 0:
        shards *= 2
    return shards


def make_mesh(
    n_devices: Optional[int] = None,
    pod_shards: Optional[int] = None,
    devices=None,
) -> Mesh:
    """A 2D (pods × nodes) Mesh over the first ``n_devices`` devices.

    Factoring: ``default_pod_shards`` — hosts land on the pod axis (DCN
    carries no per-wave collectives there; the node-axis reductions stay
    on ICI), per-host chips on the node axis; ``pod_shards`` pins it.
    ``jax.devices()`` orders devices host-major, so reshaping to
    (processes, chips-per-process) puts each row's node shards on one
    host's ICI domain.
    """
    if n_devices is not None and n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    full_roster = devices is None
    devices = list(devices if devices is not None else jax.devices())
    n = n_devices or len(devices)
    if n > len(devices):
        raise ValueError(f"requested {n} devices, only {len(devices)} available")
    covers_all = full_roster and n == len(devices)
    devices = devices[:n]
    if pod_shards is None:
        # the hosts-on-pod-axis factoring relies on the (processes,
        # chips-per-process) reshape aligning mesh rows with hosts — only
        # true for the full host-major jax.devices() roster; a truncated
        # or caller-supplied list falls back to the square-ish factoring
        pod_shards = default_pod_shards(
            n, jax.process_count() if covers_all else 1
        )
    if n % pod_shards:
        raise ValueError(f"{n} devices not divisible by pod_shards={pod_shards}")
    grid = np.array(devices).reshape(pod_shards, n // pod_shards)
    return Mesh(grid, (POD_AXIS, NODE_AXIS))


def _table_sharding(
    mesh: Mesh, table: Any, axis: str, replicated: tuple = ()
) -> Any:
    """NamedSharding pytree: leading dim on ``axis``, trailing dims
    replicated; fields named in ``replicated`` replicate fully (their
    leading dim is NOT the table's primary axis — e.g. the NodeTable's
    tiny per-profile label/taint planes)."""
    from dataclasses import fields as dc_fields

    specs = {}
    for f in dc_fields(type(table)):
        leaf = getattr(table, f.name)
        if f.name in replicated:
            specs[f.name] = NamedSharding(mesh, P())
        else:
            extra = (None,) * (leaf.ndim - 1)
            specs[f.name] = NamedSharding(mesh, P(axis, *extra))
    return type(table)(**specs)


def pod_sharding(mesh: Mesh, table: PodTable):
    return _table_sharding(mesh, table, POD_AXIS)


def node_sharding(mesh: Mesh, table: NodeTable):
    from minisched_tpu.models.tables import NODE_PROFILE_COLS

    return _table_sharding(mesh, table, NODE_AXIS, replicated=NODE_PROFILE_COLS)


#: ConstraintTables field → mesh placement, derived from the single
#: authoritative layout map (models/constraints.CONSTRAINT_AXES): leading
#: pod dims split on "pods", trailing node dims on "nodes", small
#: per-combo/key metadata replicates.
from minisched_tpu.models.constraints import CONSTRAINT_AXES as _LAYOUT

_AXIS_NAME = {"pods": POD_AXIS, "nodes": NODE_AXIS, None: None}
_CONSTRAINT_AXES = {
    name: (kind, _AXIS_NAME[role]) for name, (kind, role) in _LAYOUT.items()
}


def constraint_sharding(mesh: Mesh, extra: Any) -> Any:
    """NamedSharding pytree for a ConstraintTables bundle: node-axis planes
    split with the node table, per-pod constraint arrays with the pod table,
    small combo metadata replicated."""
    from dataclasses import fields as dc_fields

    specs = {}
    for f in dc_fields(type(extra)):
        leaf = getattr(extra, f.name)
        kind, axis = _CONSTRAINT_AXES.get(f.name, ("first", POD_AXIS))
        if kind == "rep":
            spec = P()
        elif kind == "last":
            spec = P(*((None,) * (leaf.ndim - 1)), axis)
        else:
            spec = P(axis, *((None,) * (leaf.ndim - 1)))
        specs[f.name] = NamedSharding(mesh, spec)
    return type(extra)(**specs)


def static_col_shardings(mesh: Mesh, cols: Dict[str, Any]) -> Dict[str, Any]:
    """NamedSharding per device-resident static node column: leading
    node dim split on the node axis, the tiny per-profile label/taint
    planes replicated (they must be whole on every shard — every node
    row gathers through profile_id)."""
    from minisched_tpu.models.tables import NODE_PROFILE_COLS

    out = {}
    for name, leaf in cols.items():
        if name in NODE_PROFILE_COLS:
            out[name] = NamedSharding(mesh, P())
        else:
            out[name] = NamedSharding(
                mesh, P(NODE_AXIS, *((None,) * (leaf.ndim - 1)))
            )
    return out


def scan_constraint_sharding(mesh: Mesh, extra: Any) -> Any:
    """ConstraintTables shardings for the sequential-scan layout: the
    node-axis planes split with the node table, everything pod-indexed
    replicates (the scan walks pods one dynamic row slice at a time — a
    pod-sharded layout would turn every step into a cross-shard
    gather)."""
    from dataclasses import fields as dc_fields

    specs = {}
    for f in dc_fields(type(extra)):
        leaf = getattr(extra, f.name)
        kind, _axis = _CONSTRAINT_AXES.get(f.name, ("first", POD_AXIS))
        if kind == "last":
            spec = P(*((None,) * (leaf.ndim - 1)), NODE_AXIS)
        else:
            spec = P()
        specs[f.name] = NamedSharding(mesh, spec)
    return type(extra)(**specs)


def shard_tables(
    mesh: Mesh, pods: PodTable, nodes: NodeTable
) -> Tuple[PodTable, NodeTable]:
    """Place tables on the mesh: pods split on the pod axis, nodes on the
    node axis.  Capacities must divide the respective mesh axis sizes
    (tables.pad_to(128) guarantees this for meshes up to 128-wide)."""
    pods = jax.device_put(pods, pod_sharding(mesh, pods))
    nodes = jax.device_put(nodes, node_sharding(mesh, nodes))
    return pods, nodes


class _CompiledShardedStep:
    """One jitted executable per call signature (with/without the
    constraint tables) — waves may alternate between the two.  ``fn`` is
    ``fn(nodes, pods, extra=None)``.

    The node table is deliberately NOT donated: table builds route
    all-zero columns through a shared splitter executable whose outputs
    can ALIAS (one broadcasted-zero buffer serving several columns), and
    a donation-compiled program then rejects the call with "supplied N
    buffers but compiled program expected M" — an order-dependent live
    failure (a whole wave parked unschedulable) first seen when another
    engine's builds warmed the splitter caches.  Donation only saved an
    on-device copy on the virtual-mesh path; the single-chip hot path
    never goes through here."""

    #: process-wide count of poisoned-dispatch self-heals (see __call__)
    #: — repeated poisoning is a real bug and must be visible, not masked
    #: by silent recompiles
    heal_count = 0

    def __init__(self, mesh: Mesh, fn):
        self._mesh = mesh
        self._fn = fn
        self._jitted = {}

    def __call__(self, nodes, pods, extra=None):
        try:
            out = self._call(nodes, pods, extra)
            # execution is async — the poisoned-dispatch fault below only
            # surfaces when results are awaited, which would be outside
            # this handler.  Blocking here costs pipelining only on the
            # virtual-mesh path.
            jax.block_until_ready(out)
            return out
        # the fault has surfaced as ValueError on this jaxlib, but PJRT
        # execution errors are XlaRuntimeError (a RuntimeError) in other
        # paths — catch both, gate on the message
        except (ValueError, RuntimeError) as err:
            # jit-cache poisoning self-heal: with other engines' builds
            # in this process's jit caches, dispatch can land on an
            # executable traced for a DIFFERENT argument set and fail
            # with "Execution supplied N buffers but compiled program
            # expected M" (constant delta, every wave — the whole wave
            # would park unschedulable).  Dropping the entry recompiles
            # against THIS call's actual structure; a second failure is
            # a real bug and surfaces.
            if "buffers but compiled program expected" not in str(err):
                raise
            import os as _os
            import sys as _sys
            # heals are ALWAYS visible (advisor r4): a genuine argument-
            # mismatch bug in a new caller would otherwise be silently
            # masked by its first recompile and only surface if it
            # repeats.  The counter lets harnesses assert no-heal runs.
            _CompiledShardedStep.heal_count += 1
            print(
                f"[sharded-step] poisoned dispatch #"
                f"{_CompiledShardedStep.heal_count}; recompiling "
                f"({str(err)[-120:]})",
                file=_sys.stderr,
                flush=True,
            )
            # evict only the poisoned signature — other entries' compiled
            # executables (warm shapes, the other extra variant) are fine
            self._jitted.pop(self._sig_key(nodes, pods, extra), None)
            try:
                out = self._call(nodes, pods, extra)
                jax.block_until_ready(out)
            except Exception as err2:
                if _os.environ.get("MINISCHED_DEBUG_HEAL"):
                    print("[sharded-step] heal retry FAILED:",
                          type(err2).__name__, str(err2)[-200:], flush=True)
                raise
            if _os.environ.get("MINISCHED_DEBUG_HEAL"):
                print("[sharded-step] heal retry ok", flush=True)
            return out

    def _sig_key(self, nodes, pods, extra):
        # the mesh factoring is part of the key: a multi-engine process
        # can host differently-shaped meshes, and an executable compiled
        # for one must never serve another even at equal table shapes
        return (
            mesh_shape_key(self._mesh),
            extra is not None,
            tuple(
                (l.shape, str(l.dtype))
                for l in jax.tree_util.tree_leaves((nodes, pods, extra))
            ),
        )

    def _call(self, nodes, pods, extra=None):
        # one jax.jit OBJECT per full input signature — not just per
        # with/without-extra: sharing one jit across signatures let the
        # dispatch fast path land on the executable of ANOTHER signature
        # (the prewarm's warm tables vs live waves) once enough other
        # programs populated this process's jit caches — the
        # buffers-count fault handled in __call__.  jax would retrace per
        # signature anyway; distinct jit objects only pin the dispatch.
        key = self._sig_key(nodes, pods, extra)
        if key not in self._jitted:
            mesh, fn = self._mesh, self._fn
            shardings = [node_sharding(mesh, nodes), pod_sharding(mesh, pods)]
            if extra is not None:
                shardings.append(constraint_sharding(mesh, extra))

                def wrapped(nodes, pods, extra):
                    return fn(nodes, pods, extra=extra)

            else:

                def wrapped(nodes, pods):
                    return fn(nodes, pods)

            # keep_unused: argument PRUNING is the second half of the
            # order-dependent failure this class documents above — the
            # compiled program and the dispatch fast path can disagree on
            # the pruned argument set ("supplied 102 buffers but compiled
            # program expected 109") once other engines' builds populated
            # the jit caches.  Keeping every argument makes both sides
            # count the same buffers; the cost is shipping a few unused
            # columns to a virtual mesh.
            self._jitted[key] = jax.jit(
                wrapped,
                in_shardings=tuple(shardings),
                keep_unused=True,
            )
        # trace-time Pallas guard (see MeshPackedCaller): the first call
        # traces the sharded program; fast routes incompatible with GSPMD
        # must take their XLA tails
        from minisched_tpu.ops import fused as _fused

        with _fused.mesh_trace_guard():
            if extra is not None:
                return self._jitted[key](nodes, pods, extra)
            return self._jitted[key](nodes, pods)


def sharded_repair_step(
    mesh: Mesh,
    filter_plugins,
    pre_score_plugins,
    score_plugins,
    ctx,
    max_rounds: int = 16,
    with_diagnostics: bool = False,
    split_static: bool = True,
):
    """The conflict-repair wave loop (ops/repair.repair_wave_step) jitted
    with explicit shardings over ``mesh`` — same placement contract as
    ``sharded_wave_step`` but never double-books a node.  The accept rule's
    sort/segment scans run replicated per pod shard; the evaluate inside
    each round keeps the (pods × nodes) tiles sharded on both axes.
    ``with_diagnostics``/``split_static`` pass through to repair_wave_step
    (the live engine runs with diagnostics for per-pod failing-plugin
    requeue gating)."""
    from functools import partial

    from minisched_tpu.ops.repair import repair_wave_step

    step = partial(
        repair_wave_step,
        filter_plugins=tuple(filter_plugins),
        pre_score_plugins=tuple(pre_score_plugins),
        score_plugins=tuple(score_plugins),
        ctx=ctx,
        max_rounds=max_rounds,
        with_diagnostics=with_diagnostics,
        split_static=split_static,
    )
    return _CompiledShardedStep(mesh, step)


def sharded_scan_step(
    mesh: Mesh,
    filter_plugins,
    pre_score_plugins,
    score_plugins,
    ctx,
):
    """The bind-exact sequential scan (ops/sequential.scan_schedule) jitted
    over ``mesh``.  The scan is sequential over PODS by construction, so
    only the NODE axis parallelizes: the node table (and every node-axis
    constraint plane) shards across devices and each step's evaluation
    reduces over node shards via XLA collectives; pod-axis inputs stay
    replicated — a pod-sharded layout would turn every step's dynamic
    row slice into a cross-shard gather for no compute win."""
    from functools import partial

    from minisched_tpu.ops.sequential import scan_schedule

    step = partial(
        scan_schedule,
        filter_plugins=tuple(filter_plugins),
        pre_score_plugins=tuple(pre_score_plugins),
        score_plugins=tuple(score_plugins),
        ctx=ctx,
    )

    class _ScanStep(_CompiledShardedStep):
        def __call__(self, nodes, pods, extra=None):
            key = extra is not None
            if key not in self._jitted:
                node_sh = node_sharding(self._mesh, nodes)
                pod_rep = jax.tree_util.tree_map(
                    lambda _a: NamedSharding(self._mesh, P()), pods
                )
                shardings = [node_sh, pod_rep]
                if extra is not None:
                    # node-axis planes shard with the node table; pod-axis
                    # rows replicate (see docstring)
                    shardings.append(
                        scan_constraint_sharding(self._mesh, extra)
                    )

                    def wrapped(nodes, pods, extra):
                        return self._fn(nodes, pods, extra=extra)

                else:
                    def wrapped(nodes, pods):
                        return self._fn(nodes, pods)

                self._jitted[key] = jax.jit(
                    wrapped, in_shardings=tuple(shardings)
                )
            from minisched_tpu.ops import fused as _fused

            with _fused.mesh_trace_guard():
                if extra is not None:
                    # inputs re-placed per call (tables arrive host- or
                    # single-device-resident)
                    return self._jitted[key](nodes, pods, extra)
                return self._jitted[key](nodes, pods)

    return _ScanStep(mesh, step)


def sharded_wave_step(
    mesh: Mesh,
    filter_plugins,
    pre_score_plugins,
    score_plugins,
    ctx,
):
    """The full device step (evaluate + commit) jitted with explicit
    input/output shardings over ``mesh``.

    Input: (NodeTable sharded on nodes, PodTable sharded on pods).
    Output: (NodeTable same sharding, choice/best replicated per pod shard).
    XLA inserts the cross-node-shard argmax/argmin reductions and the
    scatter-add's collectives; the node table stays resident and sharded
    across waves (donated so updates are in-place).
    """
    from minisched_tpu.ops.state import wave_step

    chains = (
        tuple(filter_plugins),
        tuple(pre_score_plugins),
        tuple(score_plugins),
    )

    def step(nodes, pods, extra=None):
        return wave_step(nodes, pods, *chains, ctx, extra=extra)

    return _CompiledShardedStep(mesh, step)


class MeshPackedCaller:
    """The mesh-sharded twin of ``models.tables.PackedCaller`` — the live
    engine's ISSUE 7 tentpole path.

    Same single-program contract: the per-wave tables arrive as PACKED
    host buffers plus the device-resident static node columns, and the
    one jitted program unpacks them — but here the unpacked tables get
    explicit sharding constraints so GSPMD partitions the whole wave over
    the (pods × nodes) mesh: the flat buffers replicate (they are the
    wire format, a few MB), the static columns arrive already node-
    sharded, and XLA inserts the cross-shard argmax / tie-break-min /
    scatter collectives exactly as the dryrun steps above prove.

    ``scan_layout=True`` switches to the sequential-scan placement (pods
    replicated, only the node axis parallel — see sharded_scan_step).

    Inherits PackedCaller's dispatch-heal machinery; the jit-cache key
    additionally carries the mesh factoring (and the layout flag), so an
    executable compiled for one mesh never serves another."""

    def __init__(
        self, consumer, mesh: Mesh, name: str, scan_layout: bool = False
    ):
        from minisched_tpu.models.tables import PackedCaller

        self._mesh = mesh
        self._scan_layout = scan_layout
        # composition via a single-inheritance subclass built here keeps
        # models/tables.py free of any jax.sharding import (host-build
        # code must stay importable without a mesh in sight)
        outer = self

        class _Caller(PackedCaller):
            def _key(self, pod_packed, node_static, node_agg_packed,
                     ex_schema):
                return (
                    mesh_shape_key(outer._mesh),
                    outer._scan_layout,
                ) + super()._key(
                    pod_packed, node_static, node_agg_packed, ex_schema
                )

            def _build_fn(self, key, pod_packed, node_static,
                          node_agg_packed, extra_packed):
                return outer._build_sharded_fn(
                    pod_packed, node_static, node_agg_packed, extra_packed
                )

        self._inner = _Caller(consumer, name)

    def __call__(self, pod_packed, node_static, node_agg_packed,
                 extra_packed=None):
        return self._inner(
            pod_packed, node_static, node_agg_packed, extra_packed
        )

    def lowered_texts(self, debug_info: bool = False):
        return self._inner.lowered_texts(debug_info)

    def _build_sharded_fn(self, pod_packed, node_static, node_agg_packed,
                          extra_packed):
        from minisched_tpu.models.constraints import ConstraintTables
        from minisched_tpu.models.tables import unpack_columns

        mesh = self._mesh
        scan_layout = self._scan_layout
        ex_schema = extra_packed.schema if extra_packed is not None else None
        pod_metas, pod_zeros = pod_packed.schema
        agg_metas, agg_zeros = node_agg_packed.schema
        consumer = self._inner._consumer
        replicated = NamedSharding(mesh, P())
        static_sh = static_col_shardings(mesh, node_static)
        # trace-time guard: kernels with mesh-incompatible fast routes
        # (the Pallas select_hosts tail cannot ride GSPMD partitioning
        # without a shard_map) consult this while the sharded program
        # traces — see ops.fused.tracing_under_mesh
        from minisched_tpu.ops import fused as _fused

        def run(pod_flat, agg_flat, ex_flat, static_cols):
            from minisched_tpu.models.tables import NodeTable, PodTable

            pods = PodTable(**unpack_columns(pod_flat, pod_metas, pod_zeros))
            nodes = NodeTable(
                **static_cols,
                **unpack_columns(agg_flat, agg_metas, agg_zeros),
            )
            extra = (
                ConstraintTables(
                    **unpack_columns(ex_flat, *ex_schema, fence_narrow=True)
                )
                if ex_schema is not None
                else None
            )
            # the constraints are what make GSPMD split the compute: the
            # node table on the node axis (profile planes whole), pods on
            # the pod axis (or replicated for the scan layout), the
            # constraint planes per the authoritative layout map
            nodes = jax.lax.with_sharding_constraint(
                nodes, node_sharding(mesh, nodes)
            )
            if scan_layout:
                pods = jax.lax.with_sharding_constraint(
                    pods,
                    jax.tree_util.tree_map(lambda _a: replicated, pods),
                )
                if extra is not None:
                    extra = jax.lax.with_sharding_constraint(
                        extra, scan_constraint_sharding(mesh, extra)
                    )
            else:
                pods = jax.lax.with_sharding_constraint(
                    pods, pod_sharding(mesh, pods)
                )
                if extra is not None:
                    extra = jax.lax.with_sharding_constraint(
                        extra, constraint_sharding(mesh, extra)
                    )
            return consumer(pods, nodes, extra)

        run.__name__ = run.__qualname__ = self._inner._name
        jitted = jax.jit(
            run,
            # flat wire buffers replicate; statics arrive pre-sharded.
            # keep_unused: the compiled program and the dispatch fast
            # path must count the same buffers (see _CompiledShardedStep)
            in_shardings=(replicated, replicated, replicated, static_sh),
            keep_unused=True,
        )

        def traced(pod_flat, agg_flat, ex_flat, static_cols):
            with _fused.mesh_trace_guard():
                return jitted(pod_flat, agg_flat, ex_flat, static_cols)

        def lower(*avals):
            with _fused.mesh_trace_guard():
                return jitted.lower(*avals)

        # the jit surface PackedCaller uses: clear_cache for the heal path,
        # lower for lowered_texts
        traced.clear_cache = jitted.clear_cache
        traced.lower = lower
        return traced
