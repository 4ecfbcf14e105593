"""Device-backed scheduling engine: the TPU path wired to the live
control plane.

The scalar engine (engine/scheduler.py) is the reference-shaped loop: one
pod per cycle.  This engine is the TPU-native alternative behind the same
control-plane contract: it drains the scheduling queue in WAVES
(queue.pop_batch), builds the struct-of-arrays tables for the snapshot,
evaluates the whole wave on device in repair mode (ops/repair.py — commits
are conflict-free), then runs the host-side permit machinery and binds
each placed pod.  Unplaced pods flow through the same ErrorFunc →
unschedulableQ → event-gated requeue path as the scalar engine.

Cross-pod plugins get per-wave constraint tables (models/constraints.py);
the informer/event machinery, waiting-pod registry, and queue are shared
with the scalar engine via subclassing — the device part replaces only
the evaluate step, exactly the boundary SURVEY.md §7's design stance
draws (host control plane / device batch evaluator).
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Any, List, Optional, Tuple

from minisched_tpu.api.objects import Pod
from minisched_tpu.engine.pipeline import WavePipeline, build_wave
from minisched_tpu.engine.scheduler import Scheduler
from minisched_tpu.framework.types import (
    CycleState,
    Diagnosis,
    FitError,
    QueuedPodInfo,
    Status,
)
from minisched_tpu.models.constraints import (
    SCAN_ELIDE_GROUPS,
    build_constraint_tables,
    combo_rows,
)
from minisched_tpu.models.tables import (
    CachedNodeTableBuilder,
    DIRTY_UNTRACKED,
    build_pod_table,
    pad_to,
)
from minisched_tpu.observability import profiling
from minisched_tpu.ops.repair import RepairingEvaluator

# the spans this module opens (the build worker's are pipeline.py's, the
# scalar tail's — permit, bind — scheduler.py's): on /metrics from boot
profiling.register_phases(
    "constraints_lock_wait", "constraints_store_list",
    "scan_flush", "scan_grouping", "scan_build", "scan_build_nodes",
    "scan_build_pods", "scan_build_constraints", "scan_evaluate",
    "scan_dispatch", "scan_fetch",
    "loop_pop", "loop_gc",
    "wave", "wave_evaluate", "wave_device", "wave_dispatch", "wave_fetch",
    "wave_postfetch", "wave_winners", "losers_handle", "commit",
)
profiling.register_phases("wave_pipeline_stall", cpu=False)


import os as _os

#: env-gated per-wave stderr trace (timeline debugging at cluster scale)
_WAVE_LOG = _os.environ.get("MINISCHED_WAVE_LOG", "") not in ("", "0")


def _is_cross_pod(pod: Pod) -> bool:
    """Pods that read or write intra-wave cross-pod coupling state
    (topology spread / pod (anti-)affinity).  The repair wave evaluates
    every pod against wave-start combo planes, so two such pods in one
    wave would be blind to each other — they ride the sequential scan
    instead (bind-exact; ops/sequential.py carries the combo planes)."""
    if pod.spec.topology_spread_constraints:
        return True
    aff = pod.spec.affinity
    if aff is None:
        return False
    return aff.pod_affinity is not None or aff.pod_anti_affinity is not None


class DeviceScheduler(Scheduler):
    """Scheduler whose evaluation step runs on device, a wave at a time."""

    def __init__(
        self,
        *args,
        max_wave: int = 1024,
        mesh: Any = None,
        assume_ttl_s: float = 30.0,
        faults: Any = None,
        **kwargs,
    ):
        super().__init__(*args, **kwargs)
        self.max_wave = max_wave
        #: assume-lease TTL: every assumption expires after this many
        #: seconds unless the informer confirms the bind first.  A pod
        #: whose bind was LOST to a fault (transport failure whose error
        #: path itself failed, a crashed bind thread) would otherwise
        #: double-book its node forever — at expiry the AUTHORITATIVE
        #: store decides: bound → renew (informer merely lagging);
        #: unbound → release the capacity and requeue the pod; store
        #: unreachable → renew and retry next check.  None disables.
        self.assume_ttl_s: Optional[float] = assume_ttl_s
        #: optional faults.FaultFabric for the engine-side injection
        #: points (``engine.bind``) — tests/chaos soak arm it
        self.faults = faults
        #: optional jax.sharding.Mesh — waves then evaluate SHARDED over
        #: the (pods × nodes) device mesh (parallel/sharding.py): pod rows
        #: data-parallel, node columns model-parallel, XLA collectives
        #: over ICI.  None at construction resolves the startup policy
        #: (parallel/sharding.resolve_mesh): MINISCHED_MESH=1 forces a
        #: mesh over every visible device (a degenerate 1-device mesh
        #: keeps current behavior), MINISCHED_MESH=0 pins single-device,
        #: unset auto-shards exactly when jax.device_count() > 1.
        #: ``mesh=False`` pins single-device EXPLICITLY (bypassing the
        #: policy) on a box whose device count would auto-shard.
        if mesh is None:
            from minisched_tpu.parallel.sharding import resolve_mesh

            mesh = resolve_mesh()
        elif mesh is False:
            mesh = None
        self.mesh = mesh
        #: pod-table capacity quantum: lane-padded AND divisible by the
        #: mesh pod axis so every shard gets equal whole tiles (the node
        #: quantum lives in the table builder)
        self._pod_cap_mult = 128
        #: per-wave single-device fallback evaluator (mesh mode only) —
        #: mirrors the pipeline's _BuildFallback: any sharded-evaluate
        #: failure re-runs THAT wave on one device, later waves retry
        #: the mesh (see _eval_packed_wave)
        self._mesh_fallback_evaluator: Any = None
        #: id of the wave the loop thread is on, stamped on trace spans
        #: (observability/trace) so a pod's enqueue→bind chain joins its
        #: wave's build/evaluate spans; (pod_shards, node_shards) rides
        #: along in mesh mode
        self._wave_seq = 0
        #: where wave ids come from: the build worker draws one at pop,
        #: the loop thread one for a wave it pops itself (``next`` on a
        #: count is atomic; ``+= 1`` from two threads is not)
        self._wave_ids = itertools.count(1)
        #: the flush's id on its spans (loop thread only)
        self._scan_call = 0
        self._mesh_shards: Any = None
        if mesh is not None:
            from minisched_tpu.observability import counters
            from minisched_tpu.parallel.sharding import (
                cap_multiple,
                mesh_axis_sizes,
            )

            pod_ax, node_ax = mesh_axis_sizes(mesh)
            self._pod_cap_mult = cap_multiple(128, pod_ax)
            self._mesh_shards = (pod_ax, node_ax)
            # gauges, not counters: the factoring is state — restarts and
            # multi-engine processes must not sum 2x4 into 4x8
            counters.set_gauge("wave_mesh.pod_shards", pod_ax)
            counters.set_gauge("wave_mesh.node_shards", node_ax)
        # chains with a combo-carrying (cross-pod) plugin route constrained
        # pods through the sequential scan; volume-only chains never do —
        # nothing in them evaluates spread/affinity constraints.  Unknown
        # cross-pod plugins without the attribute get the safe default.
        self._has_cross_pod = any(
            getattr(p, "needs_extra", False)
            and "combos" in getattr(
                p, "scan_carried_planes", ("combos", "volumes")
            )
            for p in (*self.filter_plugins, *self.score_plugins)
        )
        self._evaluator: Optional[RepairingEvaluator] = None
        self._scan_scheduler: Any = None  # lazy SequentialScheduler
        self._blocked_scheduler: Any = None  # lazy BlockedSequentialScheduler
        self._narrow_scheduler: Any = None  # the same at SCAN_NARROW_WIDTH
        #: two-stage wave pipeline (engine/pipeline.py): the host build
        #: for wave N+1 runs on a worker thread while the device evaluates
        #: wave N.  Started by the first ``schedule_one``; from then on
        #: the worker owns queue popping.
        self._pipeline: Any = None
        #: commit-time re-arbitration only matters when the chain
        #: actually filters on capacity — chains without NodeResourcesFit
        #: accept over-booking by design (the scalar engine does too),
        #: and rejecting there would CHANGE placements
        self._rearb_capacity = any(
            p.name() == "NodeResourcesFit" for p in self.filter_plugins
        )
        # static node columns cached across waves, keyed on each node's
        # (name, resource_version) — only the assigned-pod aggregates are
        # re-encoded per wave.  Under a mesh the device-resident statics
        # live SHARDED on the node axis (the packed mesh program consumes
        # them in place; nothing donates them)
        self._table_builder = CachedNodeTableBuilder(
            device_static=True, mesh=self.mesh
        )
        #: observability.resultstore.Store — set by the service when
        #: record_results is on: each wave then also runs a diagnostics
        #: evaluation and records the same per-plugin artifact scalar
        #: cycles produce (O(pods × nodes × plugins) host dicts — a
        #: simulator feature, not for headline-scale waves).  The
        #: recorder builds device tables of its own (see _record_wave).
        self.result_store: Any = None
        self._diag_evaluator: Any = None
        self._record_builder: Any = None
        # cross-pod pods deferred across waves (see schedule_wave): every
        # scan-lane call re-ships the packed node/constraint tables and
        # dispatches a whole program however few pods it carries, so
        # constrained pods accumulate here and the lane is entered once per
        # ~BLOCKED_MAX_CHUNK of them — or at queue drain, whichever comes
        # first — and cuts them into the calls their grouping asks for
        # (_plan_blocked_calls).  Pop order is preserved, so per-group FIFO
        # (the lane's exactness contract) is unchanged.
        self._scan_backlog: List[QueuedPodInfo] = []
        self._scan_backlog_waves = 0  # full waves survived since first defer
        #: the scan lanes' combo capacity so far (_build_constraints)
        self._scan_combo_cap = 0
        from minisched_tpu.observability import counters

        for name in counters.LANE_COUNTERS:
            counters.inc(name, 0)
        # assume-pod cache (upstream's scheduler cache AssumePod): a placed
        # pod counts against its node IMMEDIATELY, before the async bind
        # lands in the informer cache — without it, the next wave snapshots
        # stale state and can double-book the capacity wave N just used
        self._assumed: dict = {}  # uid → pod clone with node_name set
        #: uid → (milli_cpu, mem_mib, eph_mib, nz_milli_cpu, nz_mem_mib,
        #: ports) with NodeInfo.add_pod's exact quantization — computed
        #: once at assume time so per-wave snapshots fold assumptions as
        #: numeric aggregate deltas instead of per-pod add_pod calls
        #: (~250ms/16k-pod wave of duplicated host work)
        self._assumed_agg: dict = {}
        #: uid → monotonic deadline; see assume_ttl_s
        self._assumed_expiry: dict = {}
        self._assumed_lock = threading.Lock()
        # control-plane reconnect (watch resumed OR relisted — either way
        # the stream broke, and a server RESTART may sit behind it):
        # every assumption's lease is marked due immediately, so the next
        # snapshot/idle check re-arbitrates each against the AUTHORITATIVE
        # store instead of trusting pre-crash memory — a bind the dead
        # server never committed is released+requeued, one that committed
        # without an event is confirmed (see _expire_assume_leases)
        self.informer_factory.informer_for("Pod").on_reconnect.append(
            self._revalidate_assume_ledger
        )

    def _revalidate_assume_ledger(self) -> None:
        from minisched_tpu.observability import counters

        now = time.monotonic()
        with self._assumed_lock:
            n = len(self._assumed_expiry)
            for uid in self._assumed_expiry:
                self._assumed_expiry[uid] = now
        if n:
            counters.inc("assume.revalidate_on_reconnect", n)

    def _wire_pre_cache(self, informer_factory: Any) -> None:
        """Create + wire the incremental constraint index when the chains
        read cross-pod/volume planes.  Registered BEFORE the NodeInfo
        cache (see Scheduler.__init__): the assume-cache prunes against
        the cache, so an index that lagged it could drop a just-confirmed
        bind from the planes for one wave; index-ahead is harmless (the
        assumed fold checks index membership first)."""
        self._needs_extra = any(
            getattr(p, "needs_extra", False)
            for p in (*self.filter_plugins, *self.score_plugins)
        )
        self.constraint_index = None
        if self._needs_extra:
            from minisched_tpu.models.constraint_index import ConstraintIndex

            self.constraint_index = ConstraintIndex()
            self.constraint_index.wire(informer_factory)
        # gang placement directory: wired pre-cache for the same reason
        # the constraint index is — the assume-cache prunes against the
        # NodeInfo cache, so the gang view must never lag it
        self.gang_index = None
        if any(
            p.name() in ("GangTopology", "Coscheduling")
            for p in (
                *self.filter_plugins,
                *self.score_plugins,
                *self.permit_plugins,
            )
        ):
            from minisched_tpu.engine.gang import GangIndex

            self.gang_index = GangIndex()
            self.gang_index.wire(informer_factory)

    def _build_constraints(self, pods_, nodes, assigned, **kw) -> Any:
        """Constraint tables for one wave/chunk.  With a live index the
        assumed-pod membership check and the aggregate reads happen under
        ONE index lock hold — otherwise a bind event landing in between
        would count its pod both as "assumed" and in the index planes
        (TOCTOU double-count).

        The scan lanes' builds (``scan_planes``) share one combo
        capacity that only grows (``_scan_combo_cap``): the combos of a
        build are the selectors among ITS pods, many in the call that
        holds a flush's head and one in the narrow tail, and each
        capacity is a program of its own at every pod tier.  Holding the
        largest tier reached, the lanes run one program a pod tier
        whatever a call holds, all reached once the first big flush has
        been (spare combo rows are all-zero: they never match or count).
        """
        import contextlib

        index = self.constraint_index
        with self.metrics.timed("constraints_lock_wait"):
            lock_cm = (
                index.lock() if index is not None else contextlib.nullcontext()
            )
            lock_cm.__enter__()
        try:
            extra: Any = ()
            if index is not None:
                uids = index.assigned_uids()
                with self._assumed_lock:
                    extra = [
                        a for uid, a in self._assumed.items()
                        if uid not in uids
                    ]
            with self.metrics.timed("constraints_store_list"):
                pvcs = self.client.store.list("PersistentVolumeClaim")
                pvs = self.client.store.list("PersistentVolume")
            scan = kw.get("scan_planes")
            if scan:
                kw["combo_capacity"] = self._scan_combo_cap
            tables = build_constraint_tables(
                pods_, nodes, assigned,
                pvcs=pvcs,
                pvs=pvs,
                index=index,
                extra_assigned=extra,
                **kw,
            )
            if scan:
                self._scan_combo_cap = combo_rows(tables)
            return tables
        finally:
            lock_cm.__exit__(None, None, None)

    def _gang_placed_count(self, key: str, exclude=()) -> int:
        """GangIndex-backed placed-member count (O(gang), not O(pods))."""
        if self.gang_index is None:
            return super()._gang_placed_count(key, exclude)
        return self.gang_index.placed_count(key, exclude)

    def _gang_view(self, pods_) -> Any:
        """Placed-gang aggregates for this wave's gang members: the
        incremental GangIndex plus the assume-cache folded on top (an
        assumed member is placed capacity before its bind event lands).
        None when the wave carries no gang members — build_pod_table
        then skips the columns entirely."""
        if self.gang_index is None:
            return None
        from minisched_tpu.api.objects import gang_key

        keys = {gang_key(p) for p in pods_}
        keys.discard(None)
        if not keys:
            return None
        with self._assumed_lock:
            extra = [
                (k, uid, a.spec.node_name)
                for uid, a in self._assumed.items()
                if (k := gang_key(a)) is not None
            ]
        return self.gang_index.view_for(keys, extra)

    # -- assume-pod cache ---------------------------------------------------
    def _assume(self, pod: Pod, node_name: str) -> None:
        from minisched_tpu.api.objects import (
            DEFAULT_POD_CPU_REQUEST,
            DEFAULT_POD_MEMORY_REQUEST,
            MIB,
        )

        assumed = pod.clone()
        assumed.spec.node_name = node_name
        req = pod.resource_requests()
        mem_mib = req.memory // MIB
        agg = (
            req.milli_cpu,
            mem_mib,
            req.ephemeral_storage // MIB,
            req.milli_cpu or DEFAULT_POD_CPU_REQUEST,
            mem_mib or (DEFAULT_POD_MEMORY_REQUEST // MIB),
            tuple(
                port for c in pod.spec.containers if c.ports for port in c.ports
            ),
        )
        with self._assumed_lock:
            self._assumed[pod.metadata.uid] = assumed
            self._assumed_agg[pod.metadata.uid] = agg
            if self.assume_ttl_s is not None:
                self._assumed_expiry[pod.metadata.uid] = (
                    time.monotonic() + self.assume_ttl_s
                )

    def _forget(self, uid: str) -> None:
        with self._assumed_lock:
            self._assumed.pop(uid, None)
            self._assumed_agg.pop(uid, None)
            self._assumed_expiry.pop(uid, None)

    def _expire_assume_leases(self) -> None:
        """Release (or renew) assumptions whose lease ran out — the
        backstop that keeps a lost bind from double-booking a node for
        the life of the process.  Runs at every snapshot AND on the idle
        path: with the queue drained there is no wave left to notice the
        leak.  The authoritative-store read happens OUTSIDE the assume
        lock (it may be a network call)."""
        if self.assume_ttl_s is None:
            return
        now = time.monotonic()
        # pods re-deferred to the scan backlog keep their assumption ON
        # PURPOSE (_park_scan_failures: commit unverifiable, a later flush
        # arbitrates) — expiring them here would put the same pod live in
        # two lanes at once (queue.add dedupes against queues, not the
        # backlog), and whichever lane ran second would overwrite the
        # first's assumption.  The backlog and this method both run on
        # the loop thread, so the read is unsynchronized but safe.
        backlog_uids = {q.pod.metadata.uid for q in self._scan_backlog}
        with self._assumed_lock:
            expired = [
                (uid, self._assumed[uid])
                for uid, deadline in self._assumed_expiry.items()
                if deadline <= now
                and uid in self._assumed
                and uid not in backlog_uids
            ]
        if not expired:
            return
        from minisched_tpu.observability import counters

        # bound the authoritative probes per round: each is a store
        # round-trip ON the scheduling-loop thread, and a lost big wave
        # can expire hundreds of leases at once — probe a slice now,
        # leave the rest expired for the next round (snapshot or idle,
        # both frequent) instead of stalling the loop for N × RTT
        probe, deferred = (
            expired[: self.MAX_LEASE_PROBES_PER_ROUND],
            expired[self.MAX_LEASE_PROBES_PER_ROUND :],
        )
        if deferred:
            counters.inc("assume.lease_probe_deferred", len(deferred))
        expired = probe
        for i, (uid, assumed) in enumerate(expired):
            try:
                cur = self.client.pods().get(
                    assumed.metadata.name, assumed.metadata.namespace
                )
            except KeyError:
                # pod deleted while assumed: just release the capacity
                self._forget(uid)
                counters.inc("assume.lease_expired")
                continue
            except Exception:
                # store unreachable: keep the capacity reserved (the bind
                # may have landed), re-arm the lease — and for EVERY
                # remaining expired lease too, without probing: each get
                # pays the remote client's whole retry budget while the
                # plane is down, and N sequential probes would stall the
                # scheduling loop for N × that budget to learn the same
                # answer N times
                with self._assumed_lock:
                    for uid2, _ in expired[i:]:
                        if uid2 in self._assumed_expiry:
                            self._assumed_expiry[uid2] = (
                                now + self.assume_ttl_s
                            )
                counters.inc(
                    "assume.lease_renewed_unreachable", len(expired) - i
                )
                return
            if cur.metadata.uid != uid:
                self._forget(uid)  # recreated under the same name
                counters.inc("assume.lease_expired")
            elif cur.spec.node_name:
                # bound per the authority.  If the informer cache has
                # caught up, the assumption is redundant — forget it (this
                # is how the assume counter reaches zero at quiesce: the
                # wave-snapshot prune only runs while waves run).  Cache
                # still behind: renew so capacity stays booked until it is.
                cached = self.informer_factory.informer_for("Pod").get(
                    assumed.metadata.key
                )
                if cached is not None and cached.spec.node_name:
                    self._forget(uid)
                    counters.inc("assume.lease_confirmed")
                else:
                    with self._assumed_lock:
                        if uid in self._assumed_expiry:
                            self._assumed_expiry[uid] = now + self.assume_ttl_s
                    counters.inc("assume.lease_renewed_bound")
            else:
                # the bind never landed anywhere: release the capacity and
                # put the pod back through the queue (deduped by uid, so a
                # pod that somehow also sits in a queue segment is safe;
                # requeue: a retry must never be quota-held)
                self._forget(uid)
                self.queue.add(cur, requeue=True)
                counters.inc("assume.lease_requeued")

    def snapshot_nodes(self):
        """Object-level snapshot (scalar cycles, tests): the surviving
        assumptions are folded INTO the cloned NodeInfos.  One prune
        implementation — this is _snapshot_for_wave plus the per-pod
        fold the wave path replaces with the numeric delta."""
        infos, _delta, leftover = self._snapshot_for_wave()
        if leftover:
            by_name = {ni.name: ni for ni in infos}
            for assumed in leftover:
                ni = by_name.get(assumed.spec.node_name)
                if ni is not None:
                    ni.add_pod(assumed)
        return infos

    def _snapshot_for_wave(self):
        """(node infos, aggregate delta, surviving assumed pods) — the
        scan lanes' snapshot; see ``_snapshot_for_tables`` for the wave
        paths' dirty-tracking variant (this wrapper leaves the cache's
        dirty-set alone, so the wave builder misses nothing)."""
        infos, delta, leftover, _, _ = self._snapshot_for_tables(
            want_dirty=False
        )
        return infos, delta, leftover

    def _snapshot_for_tables(
        self, want_dirty: bool = True, expire_leases: bool = True
    ):
        """(node infos, aggregate delta, surviving assumed pods, dirty,
        epoch) — the wave path's snapshot.  Unlike ``snapshot_nodes`` the
        assume-cache is NOT folded into the NodeInfos pod-by-pod; it
        comes back as a numeric per-node delta (see
        CachedNodeTableBuilder._apply_agg_delta) that the table build
        adds into the aggregate columns.  Same pruning rule: an
        assumption confirmed by the cache or whose pod vanished is
        dropped.  Consumers that need assumed pods as OBJECTS
        (preemption's _merged_infos, the index-less constraint build)
        use the returned list or the live assume-cache — both disjoint
        from the snapshot's pod population by this prune.

        ``want_dirty`` drains the cache's dirty node-set atomically with
        the snapshot (SchedulerCache.snapshot_for_tables) — the builder
        then re-encodes only those aggregate rows; the wave paths are
        single-threaded (loop thread, or the pipeline's build worker),
        so drained sets reach the builder in snapshot order.
        ``expire_leases=False`` skips the lease-expiry store probes —
        the pipeline's build worker must not stall its overlap window on
        store round-trips (the loop thread expires leases per wave)."""
        if expire_leases:
            self._expire_assume_leases()
        if want_dirty:
            infos, cache_assigned, dirty, epoch = (
                self.cache.snapshot_for_tables()
            )
        else:
            infos, cache_assigned = self.cache.snapshot_with_assigned()
            dirty, epoch = DIRTY_UNTRACKED, None
        delta: dict = {}
        with self._assumed_lock:
            if not self._assumed:
                return infos, delta, [], dirty, epoch
            uids = list(self._assumed)
            keys = [self._assumed[u].metadata.key for u in uids]
        # one bulk cache read outside the assume lock (the informer lock is
        # held batch-long by the dispatch thread; nesting the two invites
        # stalls); re-check each uid under the lock after
        currents = self.informer_factory.informer_for("Pod").get_many(keys)
        leftover = []
        with self._assumed_lock:
            for uid, current in zip(uids, currents):
                assumed = self._assumed.get(uid)
                if assumed is None:
                    continue  # forgotten (failed bind) meanwhile
                exists = current is not None and current.metadata.uid == uid
                if uid in cache_assigned or not exists:
                    del self._assumed[uid]
                    self._assumed_agg.pop(uid, None)
                    self._assumed_expiry.pop(uid, None)
                    continue
                agg = self._assumed_agg[uid]
                leftover.append(assumed)
                d = delta.get(assumed.spec.node_name)
                if d is None:
                    delta[assumed.spec.node_name] = d = [0, 0, 0, 0, 0, 0, []]
                d[0] += agg[0]
                d[1] += agg[1]
                d[2] += agg[2]
                d[3] += 1
                d[4] += agg[3]
                d[5] += agg[4]
                if agg[5]:
                    d[6].extend(agg[5])
        return infos, delta, leftover, dirty, epoch

    def error_func(self, qpi: QueuedPodInfo, err, plugin: str = "") -> None:
        # a failed permit/bind releases the assumed capacity
        self._forget(qpi.pod.metadata.uid)
        super().error_func(qpi, err, plugin)

    def _get_evaluator(self) -> RepairingEvaluator:
        if self._evaluator is None:
            self._evaluator = RepairingEvaluator(
                self.filter_plugins,
                self.pre_score_plugins,
                self.score_plugins,
                weights=self.score_weights,
                # per-pod first-failing-plugin masks for the losers, so
                # event-gated requeue sees the ACTUAL failing plugins, not
                # the whole chain
                with_diagnostics=True,
                mesh=self.mesh,
            )
        return self._evaluator

    def _get_mesh_fallback_evaluator(self) -> RepairingEvaluator:
        """Single-device twin of the mesh evaluator — consumes the same
        packed wave the build stage produced (against the builder's
        default-device static copy), so a sharded failure costs one
        re-dispatch, never a rebuild."""
        if self._mesh_fallback_evaluator is None:
            self._mesh_fallback_evaluator = RepairingEvaluator(
                self.filter_plugins,
                self.pre_score_plugins,
                self.score_plugins,
                weights=self.score_weights,
                with_diagnostics=True,
                mesh=None,
            )
        return self._mesh_fallback_evaluator

    def _eval_packed_wave(
        self, pod_table, node_static, node_agg, extra,
        n_pods: int, n_nodes: int,
    ):
        """One packed repair-wave dispatch with the mesh ladder (ISSUE 7):
        sharded evaluate when a mesh is live, single-device re-dispatch of
        the SAME packed wave on any sharding failure (mirroring the build
        stage's _BuildFallback: this wave degrades, later waves retry the
        mesh), the caller's _evaluate_or_park park as the last rung."""
        ev = self._get_evaluator()
        if self.mesh is None:
            return ev.call_packed(pod_table, node_static, node_agg, extra)
        import jax

        from minisched_tpu.observability import counters

        # pad-waste ledger: rows shipped beyond the live roster/wave —
        # divide by wave_mesh.waves for the mesh-alignment overhead a wave
        counters.inc("wave_mesh.pad_pod_rows", pod_table.capacity - n_pods)
        counters.inc("wave_mesh.pad_node_rows", node_agg.capacity - n_nodes)
        try:
            if self.faults is not None:
                self.faults.check("mesh.evaluate", str(n_pods))
            out = ev.call_packed(pod_table, node_static, node_agg, extra)
            # execution is async — block HERE so a sharded-dispatch
            # failure surfaces inside this handler, not at the caller's
            # device_get past the fallback's chance
            jax.block_until_ready(out[1])
            counters.inc("wave_mesh.waves")
            return out
        except Exception as err:
            import sys as _sys

            counters.inc("wave_mesh.fallbacks")
            print(
                f"[wave-mesh] sharded evaluate failed, single-device "
                f"fallback: {type(err).__name__}: {str(err)[-160:]}",
                file=_sys.stderr,
                flush=True,
            )
            return self._get_mesh_fallback_evaluator().call_packed(
                pod_table,
                self._table_builder.static_dev_default(),
                node_agg,
                extra,
            )

    #: the exact lane's two chunk capacities (_scan_cap): few executables,
    #: each persistent-cached.  A chunk carries at most SCAN_MAX_CHUNK
    #: pods — chunking bounds executable size and the lump that binds
    #: land in; chunk k+1 re-snapshots so it sees chunk k's binds
    #: (sequential semantics across chunks).  The blocked lane's narrow
    #: layout carries the same number of live pods a call
    SCAN_MIN_CAP = 128
    SCAN_MAX_CHUNK = 1024
    #: the size of the cross-pod backlog that flushes it (schedule_one),
    #: and the wide layout's chunk stride and top tier, in ROWS: every
    #: call pays one dispatch, one packed node/constraint build and one
    #: static pass over all its rows whatever they hold, so full blocks
    #: ride FEWER, BIGGER calls than the exact lane (8,192 live pods a
    #: call when every block is full; fully-padded trailing blocks skip
    #: their step via lax.cond).  Blocks of ONE live pod do not come
    #: here: see SCAN_NARROW_WIDTH
    BLOCKED_MAX_CHUNK = 8192
    #: small-wave pod capacity: partial and requeue waves (a 2k-pod
    #: backoff replay after a 16k-pod drain) evaluate at this capacity
    #: instead of the full max_wave executable — the (P, N) planes scale
    #: with capacity, so a 2k wave on a 16384-cap program paid ~8× its
    #: share of device time.  Exactly TWO wave shapes ever run (both
    #: prewarmed); engines with max_wave <= this keep one.
    WAVE_SMALL_CAP = 2048

    def _wave_cap(self, n_pods: int) -> int:
        # capacities quantize to the mesh pod-axis multiple too (equal
        # whole tiles per shard); off-mesh this is the plain 128 padding
        full = pad_to(max(self.max_wave, 128), self._pod_cap_mult)
        small = min(pad_to(self.WAVE_SMALL_CAP, self._pod_cap_mult), full)
        return small if n_pods <= small else full
    #: blocked-scan lane (VERDICT r3 item 4): cross-pod pods pre-grouped
    #: into blocks of pairwise-disjoint interaction sets, each block one
    #: kernel step (ops/sequential.blocked_scan_schedule) — within-group
    #: sequential exactness, repair-acceptance safety across groups.
    #: ≤1 disables it (every cross-pod pod rides the exact per-pod scan).
    #: 32 rows a step is the WIDE layout, for the blocks the grouping
    #: could fill with more than one pod.
    SCAN_BLOCK_SIZE = 32
    #: the NARROW layout: the grouping's trailing blocks of exactly one
    #: live pod (all of them, where every pod shares one selector) are
    #: laid out this many rows a pod and run through the same kernel at
    #: this block size, SCAN_MAX_CHUNK live pods a call at ONE capacity
    #: (_plan_blocked_calls) — at 32 rows a pod a call built 8,192 rows
    #: and ran 256 steps of 32 for 256 live pods.  PERF.md §6 PR 32 has
    #: the chip reading that chose the width.
    SCAN_NARROW_WIDTH = 1
    #: blocked rounds before leftover capacity-race losers fall back to
    #: the exact per-pod scan
    SCAN_BLOCK_RETRIES = 3
    #: deferral age bound: flush the cross-pod backlog after this many
    #: consecutive FULL waves even if neither the size threshold nor a
    #: queue drain arrives — a sustained stream of plain waves must not
    #: starve constrained pods indefinitely
    SCAN_DEFER_MAX_WAVES = 8
    #: cap on PostFilter (preemption) passes per wave — each is
    #: O(nodes × pods) host work (see _handle_wave_losers)
    MAX_PREEMPT_PER_WAVE = 256
    #: cap on authoritative-store probes per lease-expiry round (see
    #: _expire_assume_leases) — bounds loop-thread stall after a lost
    #: wave expires many leases at once
    MAX_LEASE_PROBES_PER_ROUND = 64

    @classmethod
    def _scan_cap(cls, n_pods: int) -> int:
        """Exactly TWO chunk capacities (128 for small waves, 1024
        otherwise): every distinct cap is a scan-executable shape, and a
        full-roster compile inside a wave costs more than masked no-op
        steps ever will.  tests/test_shape_discipline.py pins this."""
        return cls.SCAN_MIN_CAP if n_pods <= cls.SCAN_MIN_CAP else cls.SCAN_MAX_CHUNK

    @classmethod
    def _blocked_cap(cls, n_rows: int) -> int:
        """The wide layout's capacity tiers, in rows: {128, 1024, 8192}.
        Same shape discipline as _scan_cap, one more tier — the kernel's
        fully-padded blocks skip their step via lax.cond, so the big tier
        runs only its live blocks' steps (its build and its static pass
        still cover every row) while it amortizes the per-call dispatch
        and table transfer."""
        if n_rows <= cls.SCAN_MIN_CAP:
            return cls.SCAN_MIN_CAP
        if n_rows <= cls.SCAN_MAX_CHUNK:
            return cls.SCAN_MAX_CHUNK
        return cls.BLOCKED_MAX_CHUNK

    @classmethod
    def _plan_blocked_calls(
        cls, blocks: List[List[Optional[Any]]]
    ) -> List[Tuple[bool, List[Optional[Any]], int]]:
        """(narrow, rows, capacity) of every kernel call one grouping
        takes, in the order they must run.  The width of a call's rows
        follows the fill the grouping found: the longest SUFFIX of blocks
        that hold exactly one live pod is laid out SCAN_NARROW_WIDTH rows
        a pod, SCAN_MAX_CHUNK live pods a call, at one capacity whatever
        its length (a short last call pads up; its padded steps are
        skipped); the head keeps SCAN_BLOCK_SIZE rows a block at
        _blocked_cap's tiers and runs first.  A group's members sit in
        strictly increasing blocks (scan_groups), so head-then-suffix in
        block order is still FIFO within every group."""
        head = len(blocks)
        while head and blocks[head - 1][1] is None:
            head -= 1
        wide = [m for blk in blocks[:head] for m in blk]
        calls = []
        for i in range(0, len(wide), cls.BLOCKED_MAX_CHUNK):
            part = wide[i : i + cls.BLOCKED_MAX_CHUNK]
            calls.append((False, part, cls._blocked_cap(len(part))))
        W = cls.SCAN_NARROW_WIDTH
        pad: List[Optional[Any]] = [None] * (W - 1)
        for i in range(head, len(blocks), cls.SCAN_MAX_CHUNK):
            rows = [
                m
                for blk in blocks[i : i + cls.SCAN_MAX_CHUNK]
                for m in (blk[0], *pad)
            ]
            calls.append((True, rows, cls.SCAN_MAX_CHUNK * W))
        return calls

    def prewarm(self, scan: bool = True) -> None:
        """Compile (or cache-load) the wave evaluator executable for the
        shapes this engine will use, before the run loop starts.  The
        full-roster repair graph is the biggest compile the engine has;
        paying it inside the first wave stalls the whole first drain.
        Called by the service when ``prewarm=True`` — between informer
        sync and run().

        ``scan=False`` skips the sequential/blocked scan-lane warms (the
        biggest share of the wall for cross-pod-capable rosters: two
        schedulers × capacity tiers × schema corners): callers that KNOW
        their workload carries no cross-pod-constrained pods never run
        those lanes, and a workload that surprises them merely pays the
        compile at first use.

        Shapes must match the live waves exactly or the warm executable is
        wasted: pod capacity is the wave capacity (``_wave_cap``), node
        capacity is the table builder's for the current node count.  The
        scan lanes are warmed at the lowest combo capacity (up to 32
        selectors a call); a workload that holds more compiles them once
        more, at its first big flush (``_build_constraints``).
        A throwaway table builder keeps the real one's static-column cache
        out of it.
        """
        import jax

        from minisched_tpu.api.objects import make_node, make_pod
        from minisched_tpu.framework.nodeinfo import build_node_infos

        # shapes from the (already-synced) informer cache — store.list
        # would deep-clone every Node object just to take len().  The
        # PROFILE capacity must come from the real roster too: a cluster
        # with >64 label/taint signatures would otherwise warm at the
        # synthetic nodes' Dp=64 and recompile on the first live wave.
        from minisched_tpu.models.tables import node_profile_capacity

        live_nodes = self.informer_factory.informer_for("Node").lister()
        # mesh-aligned: the live builder quantizes node capacity to the
        # mesh node-axis multiple; a warm at plain pad_to would compile
        # the wrong shape and be wasted
        node_capacity = self._table_builder.node_capacity(
            max(len(live_nodes), 2)
        )
        prof_capacity = node_profile_capacity(live_nodes)
        # pod capacity quantizes to the mesh pod-axis multiple exactly
        # like the live _wave_cap — a plain pad_to warm would compile the
        # wrong full-tier shape on a non-128-divisor pod axis (e.g. 3)
        pod_capacity = pad_to(max(self.max_wave, 128), self._pod_cap_mult)
        # both wave tiers compile: the full max_wave shape and the small
        # one partial/requeue waves take (identical when max_wave is small)
        wave_caps = sorted({pod_capacity, self._wave_cap(1)})
        nodes = [make_node("warm0"), make_node("warm1")]
        pods = [make_pod("warmpod", requests={"cpu": "1"})]
        # pod tables have TWO packed schemas per capacity: the vectorized
        # fast path (simple pods; zero columns declared, not shipped) and
        # the full slow path (any pod with tolerations/selector/affinity)
        complex_pod = make_pod(
            "warmsel", requests={"cpu": "1"}, node_selector={"warm": "true"}
        )
        infos = build_node_infos(nodes, [])
        # warm the single-program packed entry points for BOTH pod
        # schemas a live wave can take, each a distinct executable keyed
        # on the packed metas.  The throwaway builder carries the mesh so
        # the warm statics are sharded exactly like the live ones.
        node_static, node_agg, _ = CachedNodeTableBuilder(
            mesh=self.mesh
        ).build_packed(
            infos, capacity=node_capacity, prof_capacity=prof_capacity
        )
        for wave_cap in wave_caps:
            for warm_pods in (pods, pods + [complex_pod]):
                pt, _ = build_pod_table(
                    warm_pods, capacity=wave_cap, device=False
                )
                extra = None
                if self._needs_extra:
                    extra = build_constraint_tables(
                        warm_pods, nodes, [],
                        pod_capacity=wave_cap,
                        node_capacity=node_capacity,
                        scan_planes=False, device=False,
                    )
                out = self._get_evaluator().call_packed(
                    pt, node_static, node_agg, extra
                )
                jax.block_until_ready(out[1])
        if self._has_cross_pod and scan:
            # cross-pod-constrained pods ride the sequential scan — warm
            # BOTH chunk capacities (_schedule_scan uses exactly these
            # two; a partial chunk would otherwise compile the small one
            # mid-run).  The blocked lane's wide layout has one extra
            # (bigger) tier than the exact lane, its narrow layout one
            # capacity of its own — warm each executable only at the caps
            # it runs
            scan_caps = {self.SCAN_MIN_CAP, self.SCAN_MAX_CHUNK}
            blocked_caps = (
                scan_caps | {self.BLOCKED_MAX_CHUNK}
                if self.SCAN_BLOCK_SIZE > 1
                else set()
            )
            all_caps = sorted(scan_caps | blocked_caps)
            # scan chunks carry cross-pod pods, which are never
            # "simple" — the live schema is the SLOW pod table; warm
            # exactly that packed entry per chunk capacity.  The
            # blocked lane's schema also depends on which
            # SCAN_ELIDE_GROUPS the chunk's workload leaves all-zero:
            # warm its two common corners — a spread-only burst
            # (affinity + volume groups elided) and the kitchen sink
            # (nothing elided); a mixed burst in between compiles
            # once mid-run and persists in the compile cache.
            from minisched_tpu.api.objects import (
                Affinity,
                LabelSelector,
                PodAffinity,
                PodAffinityTerm,
                PodAntiAffinity,
                TopologySpreadConstraint,
                WeightedPodAffinityTerm,
            )

            def _spread(name):
                p = make_pod(
                    name, requests={"cpu": "1"}, labels={"app": "warm"}
                )
                p.spec.topology_spread_constraints = [
                    TopologySpreadConstraint(
                        max_skew=1,
                        topology_key="warmzone",
                        when_unsatisfiable="DoNotSchedule",
                        label_selector=LabelSelector(
                            match_labels={"app": "warm"}
                        ),
                    )
                ]
                return p

            sink_pod = _spread("warmsink")
            sel = LabelSelector(match_labels={"app": "warm"})
            sink_pod.spec.affinity = Affinity(
                pod_affinity=PodAffinity(
                    required=[
                        PodAffinityTerm(
                            label_selector=sel, topology_key="warmzone"
                        )
                    ],
                    preferred=[
                        WeightedPodAffinityTerm(
                            weight=1,
                            term=PodAffinityTerm(
                                label_selector=sel,
                                topology_key="warmzone",
                            ),
                        )
                    ],
                ),
                pod_anti_affinity=PodAntiAffinity(
                    required=[
                        PodAffinityTerm(
                            label_selector=LabelSelector(
                                match_labels={"app": "other"}
                            ),
                            topology_key="warmzone",
                        )
                    ]
                ),
            )
            sink_pod.spec.volumes = ["warmclaim"]
            blocked_sets = ([_spread("warmspread")], [sink_pod])

            def warm_blocked(scheduler, cap):
                for warm_set in blocked_sets:
                    bp, _ = build_pod_table(
                        warm_set, capacity=cap, device=False
                    )
                    bx = build_constraint_tables(
                        warm_set, nodes, [],
                        pod_capacity=cap,
                        node_capacity=node_capacity,
                        scan_planes=True, device=False,
                        elide_zeros=False,
                        elide_groups=SCAN_ELIDE_GROUPS,
                    )
                    _, bc, _, _ = scheduler.call_packed(
                        bp, node_static, node_agg, bx
                    )
                    jax.block_until_ready(bc)

            for cap in all_caps:
                if cap in scan_caps:
                    scan_pods, _ = build_pod_table(
                        pods + [complex_pod], capacity=cap, device=False
                    )
                    scan_extra = build_constraint_tables(
                        pods + [complex_pod], nodes, [],
                        pod_capacity=cap,
                        node_capacity=node_capacity,
                        scan_planes=True, device=False,
                        elide_zeros=False,
                    )
                    _, choice, _ = self._get_scan_scheduler().call_packed(
                        scan_pods, node_static, node_agg, scan_extra
                    )
                    jax.block_until_ready(choice)
                if cap in blocked_caps:
                    warm_blocked(self._get_blocked_scheduler(), cap)
            if blocked_caps:
                warm_blocked(
                    self._get_narrow_scheduler(),
                    self.SCAN_MAX_CHUNK * self.SCAN_NARROW_WIDTH,
                )

    def _get_scan_scheduler(self):
        if self._scan_scheduler is None:
            from minisched_tpu.ops.sequential import SequentialScheduler

            self._scan_scheduler = SequentialScheduler(
                self.filter_plugins,
                self.pre_score_plugins,
                self.score_plugins,
                weights=self.score_weights,
                mesh=self.mesh,
            )
        return self._scan_scheduler

    def _new_blocked_scheduler(self, block_size: int):
        from minisched_tpu.ops.sequential import BlockedSequentialScheduler

        return BlockedSequentialScheduler(
            self.filter_plugins,
            self.pre_score_plugins,
            self.score_plugins,
            weights=self.score_weights,
            block_size=block_size,
            mesh=self.mesh,
        )

    def _get_blocked_scheduler(self):
        if self._blocked_scheduler is None:
            self._blocked_scheduler = self._new_blocked_scheduler(
                self.SCAN_BLOCK_SIZE
            )
        return self._blocked_scheduler

    def _get_narrow_scheduler(self):
        """The blocked kernel at SCAN_NARROW_WIDTH rows a step: same
        plugins, same static hoist, same program name (``scan_blocked``)."""
        if self._narrow_scheduler is None:
            self._narrow_scheduler = self._new_blocked_scheduler(
                self.SCAN_NARROW_WIDTH
            )
        return self._narrow_scheduler

    def dispatched_programs(self) -> dict:
        """lane → StableHLO text of every packed program that lane has
        dispatched so far (PackedCaller.lowered_texts; [] for a lane that
        never ran).  chip_smoke.py checks all four lanes ran and what
        their programs are made of."""
        lanes = {
            "wave": self._evaluator,
            "blocked_scan": self._blocked_scheduler,
            "narrow_scan": self._narrow_scheduler,
            "exact_scan": self._scan_scheduler,
        }
        return {
            lane: (
                ev._packed_caller.lowered_texts()
                if ev is not None and ev._packed_caller is not None
                else []
            )
            for lane, ev in lanes.items()
        }

    def _evaluate_or_park(self, qpis: List[QueuedPodInfo], build_fn):
        """The shared park-on-failure scaffold around a device evaluation:
        a ValueError means some pod exceeds a static table capacity — drop
        the offenders (parked individually) and retry once; any other
        failure requeues the whole batch via error_func.  Returns
        (surviving qpis, build_fn result or None)."""
        try:
            return qpis, build_fn(qpis)
        except ValueError:
            qpis = self._drop_unencodable(qpis)
            if not qpis:
                return qpis, None
            try:
                return qpis, build_fn(qpis)
            except Exception as err:
                self._note_park(err, len(qpis))
                for qpi in qpis:  # never lose a popped wave: requeue all
                    self.error_func(qpi, err)
                return qpis, None
        except Exception as err:
            self._note_park(err, len(qpis))
            for qpi in qpis:
                self.error_func(qpi, err)
            return qpis, None

    def _note_park(self, err: BaseException, n_pods: int) -> None:
        """Make a parked batch visible: ``wave.parked`` counters (total and
        per exception type), a ``wave_park`` trace span and ONE stderr line
        — unconditionally.  The requeue itself is the robustness contract
        (a transient fault costs a retry, not a wave), but a program the
        compiler refuses fails every retry the same way: without this the
        engine spins park → backoff → retry inside a process that is up,
        answers /healthz and exits 0."""
        import sys

        from minisched_tpu.observability import counters, trace

        cause = type(err).__name__
        counters.inc("wave.parked")
        counters.inc(f"wave.parked.{cause}")
        trace.span(
            "wave_park", wave=self._wave_seq, size=n_pods,
            cause=cause, error=str(err)[:200],
        )
        trace.flight_dump("wave-park")
        print(
            f"[wave] parked {n_pods} pods (wave {self._wave_seq}): "
            f"{cause}: {str(err)[-220:]}",
            file=sys.stderr,
            flush=True,
        )
        if _os.environ.get("MINISCHED_DEBUG_HEAL"):
            import traceback

            traceback.print_exception(err)

    def _schedule_scan(
        self,
        qpis: List[QueuedPodInfo],
        node_infos: List[Any],
        agg_delta: Any = None,
        assumed_pods: Any = (),
    ) -> None:
        """The cross-pod lane: blocked scan for throughput (disjoint
        interaction groups per kernel step), exact per-pod scan for the
        remainder and as the configured fallback."""
        if (
            self.SCAN_BLOCK_SIZE > 1
            and len(qpis) > self.SCAN_BLOCK_SIZE
        ):
            self._schedule_scan_blocked(
                qpis, node_infos, agg_delta, assumed_pods
            )
            return
        self._schedule_scan_exact(qpis, node_infos, agg_delta, assumed_pods)

    def _schedule_scan_blocked(
        self,
        qpis: List[QueuedPodInfo],
        node_infos: List[Any],
        agg_delta: Any,
        assumed_pods: Any,
    ) -> None:
        """Blocked lane: group → order → the kernel calls the fill asks
        for (_plan_blocked_calls: full blocks wide, the trailing one-pod
        blocks narrow); feasible pods that lose a same-node capacity race
        retry in later rounds (re-grouped against fresh state); leftovers
        after SCAN_BLOCK_RETRIES ride the exact per-pod scan — a
        sequential order never fails them, so neither may this lane."""
        from minisched_tpu.engine.scan_groups import (
            interaction_sets,
            order_into_blocks,
        )
        from minisched_tpu.observability import counters

        # wave-style dispatch gating (see _bind_batch): the previous
        # wave's thousands of bind events drain inside this lane's
        # GIL-free device calls, not against its host builds — ungated,
        # the grouping/build stretches ran ~10× slower under dispatch
        # GIL pressure.  Snapshots stay correct while gated: the
        # assume-cache folds not-yet-dispatched binds as numeric deltas.
        self.informer_factory.pause_dispatch()
        B = self.SCAN_BLOCK_SIZE
        pending = qpis
        fresh = (node_infos, agg_delta, assumed_pods)
        try:
            for _attempt in range(self.SCAN_BLOCK_RETRIES):
                with self.metrics.timed("scan_grouping"):
                    sets = interaction_sets([q.pod for q in pending])
                    blocks = order_into_blocks(pending, sets, B)
                    calls = self._plan_blocked_calls(blocks)
                counters.inc("scan.rows_live", len(pending))
                counters.inc(
                    "scan.rows_total", sum(len(part) for _, part, _ in calls)
                )
                counters.inc(
                    "scan.rows_narrow",
                    sum(len(part) for narrow, part, _ in calls if narrow)
                    // self.SCAN_NARROW_WIDTH,
                )
                retry: List[QueuedPodInfo] = []
                for narrow, part, cap in calls:
                    if fresh is None:
                        fresh = self._snapshot_for_wave()
                    retry += self._run_blocked_chunk(
                        part, cap, narrow, *fresh
                    )
                    fresh = None
                if not retry:
                    return
                pending = retry
        finally:
            # a raise anywhere above must not leave the dispatch gate
            # closed for good (events would stall until the next bind);
            # resume is idempotent, the success paths share this exit
            self.informer_factory.resume_dispatch()
        if pending:
            # capacity-race stragglers: the exact lane finishes them
            self._schedule_scan_exact(pending, *self._snapshot_for_wave())

    def _run_blocked_chunk(
        self,
        part: List[Optional[QueuedPodInfo]],
        cap: int,
        narrow: bool,
        node_infos: List[Any],
        agg_delta: Any,
        assumed_pods: Any,
    ) -> List[QueuedPodInfo]:
        """One blocked-kernel call over ``part`` (None = block padding)
        at pod capacity ``cap`` through the scheduler whose block size
        the rows were laid out for (``narrow``: SCAN_NARROW_WIDTH, else
        SCAN_BLOCK_SIZE).  Commits winners, parks infeasible pods,
        returns the capacity-race retries."""
        import jax

        from minisched_tpu.api.objects import make_pod
        from minisched_tpu.observability import counters

        counters.inc("scan.calls_narrow" if narrow else "scan.calls_wide")
        scheduler = (
            self._get_narrow_scheduler()
            if narrow
            else self._get_blocked_scheduler()
        )

        nodes = [ni.node for ni in node_infos]
        assigned = (
            ()
            if self.constraint_index is not None
            else [p for ni in node_infos for p in ni.pods]
            + list(assumed_pods)
        )
        dummy = make_pod("scan-pad")

        def build_and_scan(part_live):
            # the padded layout, restricted to the currently-live qpis —
            # _evaluate_or_park may retry after dropping unencodable pods,
            # and the dropped ones must leave the table too
            live_ids = {id(m) for m in part_live}
            cur = [
                m if (m is not None and id(m) in live_ids) else None
                for m in part
            ]
            pad_rows = [i for i, m in enumerate(cur) if m is None]
            pods_ = [m.pod if m is not None else dummy for m in cur]
            gang_view = self._gang_view(pods_)
            with self.metrics.timed("scan_build"):
                with self.metrics.timed("scan_build_nodes"):
                    node_static, node_agg, node_names = (
                        self._table_builder.build_packed(
                            node_infos, agg_delta=agg_delta
                        )
                    )
                with self.metrics.timed("scan_build_pods"):
                    pod_table, _ = build_pod_table(
                        pods_, capacity=cap, device=False,
                        invalid_rows=pad_rows, gang_view=gang_view,
                    )
                with self.metrics.timed("scan_build_constraints"):
                    extra = self._build_constraints(
                        pods_, nodes, assigned,
                        pod_capacity=cap,
                        node_capacity=node_agg.capacity,
                        scan_planes=True,
                        device=False,
                        # per-capacity schema discipline: full elision
                        # made every STATE-driven zero-set flip (combo
                        # counts appearing mid-run) a fresh executable
                        # to compile or load — but the
                        # WORKLOAD-driven groups (affinity terms, pod
                        # volumes, spread slots) elide as units, so a
                        # spread-only burst's program folds the other
                        # lanes entirely (~2× per-step)
                        elide_zeros=False,
                        elide_groups=SCAN_ELIDE_GROUPS,
                    )
            # gate opens for the device call: held event batches
            # drain against GIL-free device compute
            self.informer_factory.resume_dispatch()
            with self.metrics.timed(
                "scan_evaluate", call=self._scan_call, n=len(part_live)
            ):
                with self.metrics.timed("scan_dispatch"):
                    _, choice, _, accepted = scheduler.call_packed(
                        pod_table, node_static, node_agg, extra
                    )
                with self.metrics.timed("scan_fetch"):
                    choice, accepted = jax.device_get(
                        (choice, accepted)
                    )
            return node_names, choice.tolist(), accepted.tolist()

        live = [m for m in part if m is not None]
        live, result = self._evaluate_or_park(live, build_and_scan)
        if result is None:
            return []
        node_names, choice, accepted = result
        live_set = {id(m) for m in live}

        winners: List[Any] = []
        losers: List[Any] = []
        retry: List[QueuedPodInfo] = []
        for i, qpi in enumerate(part):
            if qpi is None or id(qpi) not in live_set:
                continue
            c = choice[i]
            if c >= 0 and accepted[i]:
                self._assume(qpi.pod, node_names[c])
                winners.append((qpi, qpi.pod, node_names[c]))
            elif c >= 0:
                retry.append(qpi)  # feasible; lost a same-node race
            else:
                losers.append((qpi, qpi.pod, set()))
        self._count_evaluated(len(live), len(losers))
        self._commit_winners(winners, "narrow" if narrow else "wide")
        # keep the next chunk's grouping/build gated: _bind_batch closes
        # the gate when it runs, but a chunk whose winners all parked in
        # permit-wait (or that had none) never reaches it — re-close
        # explicitly (idempotent Event) so victims' DELETE events from
        # the loser handling below drain in the next device call too
        self.informer_factory.pause_dispatch()
        if losers:
            self._handle_wave_losers(losers, node_infos, len(nodes))
        return retry

    def _schedule_scan_exact(
        self,
        qpis: List[QueuedPodInfo],
        node_infos: List[Any],
        agg_delta: Any = None,
        assumed_pods: Any = (),
    ) -> None:
        """Bind-exact path for cross-pod-constrained pods: chunks of the
        sequential device scan, committed chunk by chunk."""
        import jax

        # the scan interleaves host builds with device chunks too finely
        # for wave-style dispatch gating to pay — run it ungated
        self.informer_factory.resume_dispatch()
        chunk = self.SCAN_MAX_CHUNK
        for start in range(0, len(qpis), chunk):
            part = qpis[start : start + chunk]
            if start > 0:
                node_infos, agg_delta, assumed_pods = self._snapshot_for_wave()
            nodes = [ni.node for ni in node_infos]
            assigned = (
                ()
                if self.constraint_index is not None
                else [p for ni in node_infos for p in ni.pods]
                + list(assumed_pods)
            )
            cap = self._scan_cap(len(part))

            def build_and_scan(part_):
                pods_ = [qpi.pod for qpi in part_]
                gang_view = self._gang_view(pods_)
                # single-program chunk: flat host buffers unpacked
                # inside the scan executable (see pipeline.build_wave)
                with self.metrics.timed("scan_build"):
                    node_static, node_agg, node_names = (
                        self._table_builder.build_packed(
                            node_infos, agg_delta=agg_delta
                        )
                    )
                    pod_table, _ = build_pod_table(
                        pods_, capacity=cap, device=False,
                        gang_view=gang_view,
                    )
                    extra = self._build_constraints(
                        pods_, nodes, assigned,
                        pod_capacity=cap,
                        node_capacity=node_agg.capacity,
                        scan_planes=True,  # the scan's commits need it
                        device=False,
                        elide_zeros=False,  # one packed schema per cap
                    )
                # scan pods get the same per-plugin artifact as wave
                # pods (diagnostics against the pre-decision snapshot)
                self._record_wave(
                    part_, node_infos, assigned, agg_delta, cap
                )
                with self.metrics.timed(
                    "scan_evaluate", call=self._scan_call, n=len(pods_)
                ):
                    with self.metrics.timed("scan_dispatch"):
                        _, choice, _ = (
                            self._get_scan_scheduler().call_packed(
                                pod_table, node_static, node_agg, extra
                            )
                        )
                    with self.metrics.timed("scan_fetch"):
                        choice = jax.device_get(choice)
                return node_names, choice.tolist()[: len(pods_)]

            part, result = self._evaluate_or_park(part, build_and_scan)
            if result is None:
                continue
            node_names, placements = result

            losers: List[Any] = []
            winners: List[Any] = []
            for qpi, c in zip(part, placements):
                if c < 0:
                    # no per-plugin masks from the scan: fall back to the
                    # whole chain so event-gated requeue can't strand
                    losers.append((qpi, qpi.pod, set()))
                    continue
                self._assume(qpi.pod, node_names[c])
                winners.append((qpi, qpi.pod, node_names[c]))
            self._count_evaluated(len(part), len(losers))
            self._commit_winners(winners, "exact")
            # _bind_batch re-closed the gate; this lane stays ungated (the
            # next chunk's re-snapshot needs the bind events applied)
            self.informer_factory.resume_dispatch()
            if losers:
                self._handle_wave_losers(losers, node_infos, len(nodes))

    # -- GC discipline ------------------------------------------------------
    # At 100k-pod scale the process holds ~10⁶ tracked Python objects
    # (pods, containers, label dicts, caches); CPython's automatic
    # collections rescan them on allocation-heavy phases and cost more
    # than the phases themselves (a 16k-pod batch bind: 130ms of work,
    # ~340ms of GC).  The wave loop therefore freezes the stable heap,
    # turns the automatic collector off, and collects explicitly at wave
    # boundaries — young-gen every wave (bounds cyclic garbage), full
    # periodically (bounds promoted-cycle leaks in long-running services).
    FULL_GC_EVERY_WAVES = 64

    def stop(self) -> None:
        super().stop()
        # profiling: the trace exports on loop exit (~10-30s for a full
        # run) — the base stop()'s 2s join would let process exit kill
        # the daemon thread mid-write and truncate the trace
        if _os.environ.get("MINISCHED_JAX_PROFILE") and self._thread is not None:
            self._thread.join(timeout=120.0)

    def _loop(self) -> None:
        import gc

        from minisched_tpu.observability.profiling import device_trace

        gc.collect()
        gc.freeze()
        was_enabled = gc.isenabled()
        gc.disable()
        self._waves_since_full_gc = 0
        try:
            # MINISCHED_JAX_PROFILE=<dir>: JAX profiler trace of the whole
            # run loop (device kernels + host gaps) for TensorBoard/xprof
            with device_trace(_os.environ.get("MINISCHED_JAX_PROFILE")):
                super()._loop()
        finally:
            if was_enabled:
                gc.enable()
            gc.unfreeze()
            # a stop with constrained pods still deferred must not drop
            # them silently (advisor r4): park them through error_func so
            # the queue reflects their Pending state.  This runs ON the
            # loop thread — the backlog's owner — so it cannot race a
            # wave that would re-populate it (stop()'s 2s join can time
            # out mid-wave and a park from there could be overwritten).
            backlog, self._scan_backlog = self._scan_backlog, []
            if backlog:
                try:
                    self._park_scan_failures(
                        backlog,
                        RuntimeError("scheduler stopped with deferred pods"),
                    )
                except Exception:
                    pass  # shutdown path: queue/informers may be gone
            # pipelined shutdown: the build worker may hold popped waves
            # (in the handoff queue or mid-build) — park them through
            # error_func so the queue reflects their Pending state, same
            # contract as the backlog drain above.  Runs ON the loop
            # thread after the worker joined, so nothing races it.
            pipe = self._pipeline
            if pipe is not None:
                try:
                    pipe.stop()
                    for qpi in pipe.drain():
                        try:
                            self.error_func(
                                qpi,
                                RuntimeError(
                                    "scheduler stopped with pipelined "
                                    "wave pending"
                                ),
                            )
                        except Exception:
                            pass  # shutdown path: queue may be closed
                except Exception:
                    pass

    def _wave_gc(self) -> None:
        import gc

        if gc.isenabled():
            return  # not running under the loop's GC discipline
        self._waves_since_full_gc = getattr(self, "_waves_since_full_gc", 0) + 1
        if self._waves_since_full_gc >= self.FULL_GC_EVERY_WAVES:
            self._waves_since_full_gc = 0
            gc.collect()
        else:
            gc.collect(0)

    # the loop: one wave per iteration instead of one pod ------------------
    def schedule_one(self, timeout: Optional[float] = 0.5) -> bool:
        """One loop-thread turn of the two-stage pipeline: take the next
        item off the bounded handoff queue (the build worker pops,
        snapshots, and builds tables concurrently with this thread's
        device waits), evaluate it on device, re-arbitrate, commit.
        Handoff wait lands in ``loop_pop`` (the accounting identity
        pop+wave+scan_flush+gc ≈ loop wall must keep summing) and — when
        the item is a wave — in ``wave_pipeline_stall``: time the device
        sat idle because the next build wasn't ready (stall ≈ build is
        what a loop with no overlap looks like)."""
        from minisched_tpu.observability import counters

        pipe = self._pipeline
        if pipe is None:
            pipe = self._pipeline = WavePipeline(self)
            pipe.start()
        # the worker emits an item at least once per pop window, so this
        # wait is bounded by (pop timeout + one build) — block past the
        # caller's timeout rather than spuriously reporting idle mid-build
        with self.metrics.timed("loop_pop") as handoff:
            item = pipe.get(timeout=max(timeout or 0.5, 1.0) + 1.0)
        wait = handoff.wall_s
        prev_was_wave = getattr(self, "_pipe_prev_wave", False)
        self._pipe_prev_wave = item is not None and item[0] == "wave"
        if item is None or item[0] == "empty":
            if self._scan_backlog:
                # queue drained with constrained pods still deferred:
                # flush the lane now (the backlog, not the queue, holds
                # the remaining work)
                try:
                    self._flush_scan_backlog_timed()
                finally:
                    with self.metrics.timed("loop_gc"):
                        self._wave_gc()
                return True
            # idle: the gate a bind may have closed (see _bind_batch) must
            # not delay the events that will wake us; and with the
            # automatic collector off, idle churn (informer handlers,
            # exception cycles) still needs a periodic sweep.  Assume
            # leases must expire HERE too — with the queue drained, no
            # wave snapshot is coming to notice a lost bind's leak.
            self.informer_factory.resume_dispatch()
            self._expire_assume_leases()
            with self.metrics.timed("loop_gc"):
                self._wave_gc()
            return False
        partial = True
        try:
            if item[0] == "raw":
                # the worker handed the batch back (encode overflow, empty
                # roster, priority bypass, all-constrained batch, injected
                # build fault): schedule_wave owns every one of those
                _tag, qpis, partial, wave_id = item
                self.schedule_wave(qpis, wave_id)
            else:
                prepared = item[1]
                partial = prepared.partial
                if prev_was_wave:
                    # stall = device idle because the NEXT build wasn't
                    # ready while the pipeline was hot.  A wave starting
                    # from idle always waits its whole build (nothing to
                    # overlap with) — counting it would read cold starts
                    # as regressions, so only back-to-back waves count.
                    self.metrics.observe("wave_pipeline_stall", wait)
                counters.inc("wave_pipeline.waves")
                if prepared.constrained:
                    self._scan_backlog.extend(prepared.constrained)
                # priority-inversion bypass, re-checked HERE: the worker
                # peeked the backlog at build time, but the overlapped
                # previous wave (this very iteration's predecessor) may
                # have deferred a higher-priority constrained pod after
                # that peek.  Flushing first restores the order the queue
                # popped them in — the prepared wave then re-arbitrates
                # against whatever the flush committed.
                if self._scan_backlog and prepared.qpis:
                    hi = max(
                        q.pod.spec.priority for q in self._scan_backlog
                    )
                    if hi > min(
                        q.pod.spec.priority for q in prepared.qpis
                    ):
                        self._flush_scan_backlog_timed()
                self._run_prepared_wave(prepared)
            # a partial pop means the queue is (momentarily) drained —
            # don't sit on deferred constrained pods waiting for a burst
            # that may never come; the wave-count bound keeps a sustained
            # stream of full plain waves from starving them indefinitely
            if self._scan_backlog:
                self._scan_backlog_waves += 1
                if (
                    partial
                    or len(self._scan_backlog) >= self.BLOCKED_MAX_CHUNK
                    or self._scan_backlog_waves >= self.SCAN_DEFER_MAX_WAVES
                ):
                    self._flush_scan_backlog_timed()
        finally:
            # every exit path (incl. scan-only waves and early returns)
            # collects
            with self.metrics.timed("loop_gc"):
                self._wave_gc()
        return True

    def _run_prepared_wave(self, prepared: Any) -> None:
        """A wave the worker built: the loop thread's bookkeeping for it,
        then the finish."""
        from minisched_tpu.observability import counters, trace

        qpis = prepared.qpis
        # same metric contract as schedule_wave: every exit observes
        with self.metrics.timed("wave", wave=prepared.wave_id, n=len(qpis)):
            # the worker skips lease expiry (store probes would stall its
            # overlap window); the loop thread keeps the per-wave cadence
            self._expire_assume_leases()
            counters.inc("wave_pipeline.dirty_rows", prepared.dirty_rows)
            if prepared.build_skipped:
                # idle-wave gate fired: this wave reused the previous
                # tables wholesale (zero node-table build work)
                counters.inc("wave_pipeline.zero_build_waves")
            # the id the build worker drew at pop: its sched.wave_build
            # span, this thread's spans and the trace ring's carry the
            # same number
            self._wave_seq = prepared.wave_id
            trace.span(
                "wave_build", wave=prepared.wave_id, size=len(qpis),
                build_s=round(prepared.build_s, 6),
                skipped=prepared.build_skipped or None,
                dirty_rows=prepared.dirty_rows or None,
                mesh=self._mesh_shards,
            )
            self._finish_wave(prepared)

    def _finish_wave(self, prepared: Any) -> None:
        """The one wave body after the build, whoever built it:
        device-evaluate, sort winners from losers, re-arbitrate a wave
        built ahead against what the OVERLAPPED previous wave committed
        after its snapshot, and commit through the permit/bind tail
        (AlreadyBound / Conflict / OutOfCapacity still backstop at the
        store)."""
        import jax

        from minisched_tpu.observability import trace

        qpis = prepared.qpis
        wave_id = self._wave_seq
        # before the device call and before any bind of this wave lands:
        # the store's update hook flushes the record onto the pod then
        self._record_wave(
            qpis, prepared.node_infos, prepared.assigned,
            prepared.agg_delta, prepared.pod_table.capacity,
        )
        # gate opens for the device call: the previous wave's held bind
        # events drain against GIL-free device compute — and the build
        # worker gets the GIL for wave N+2's host stretch in this window
        self.informer_factory.resume_dispatch()
        try:
            with self.metrics.timed("wave_evaluate"):
                with self.metrics.timed(
                    "wave_device", wave=wave_id, n=len(qpis)
                ):
                    # dispatch: enqueue + H2D, returns before the device
                    # has run; fetch: the device's run + D2H + getting
                    # the interpreter lock back from whoever took it
                    with self.metrics.timed("wave_dispatch"):
                        _, choice, _, unsched = self._eval_packed_wave(
                            prepared.pod_table,
                            prepared.node_static,
                            prepared.node_agg,
                            prepared.extra,
                            len(qpis),
                            len(prepared.node_infos),
                        )
                    # where the wave's outputs live, BEFORE the fetch
                    # turns them into host arrays (wave_evaluate span)
                    out_devices = sorted(
                        f"{d.platform}:{d.id}" for d in choice.devices()
                    )
                    # ONE host fetch for both results (each device_get is
                    # a blocking device→host copy)
                    with self.metrics.timed("wave_fetch"):
                        choice, unsched = jax.device_get((choice, unsched))
                with self.metrics.timed("wave_postfetch"):
                    # bool[K, P] → per-pod failing-plugin sets
                    unsched = unsched.tolist()
                    plugin_names = [p.name() for p in self.filter_plugins]
                    fail_sets = [
                        {
                            name
                            for k, name in enumerate(plugin_names)
                            if unsched[k][i]
                        }
                        for i in range(len(qpis))
                    ]
                    placements = choice.tolist()[: len(qpis)]
        except Exception as err:
            # the tables are built, so no encode retry applies here —
            # never lose a popped wave: requeue all
            self._note_park(err, len(qpis))
            for qpi in qpis:
                self.error_func(qpi, err)
            return
        trace.span("wave_evaluate", wave=wave_id, size=len(qpis),
                   mesh=self._mesh_shards, devices=out_devices)
        node_names = prepared.node_names
        losers: List[Any] = []
        winners: List[Any] = []
        rejected: List[Any] = []
        with self.metrics.timed("wave_winners"):
            for qpi, c, fails in zip(qpis, placements, fail_sets):
                if c < 0:
                    losers.append((qpi, qpi.pod, fails))
                else:
                    winners.append((qpi, qpi.pod, node_names[c]))
            if prepared.built_ahead:
                winners, rejected = self._rearbitrate_winners(winners)
            for _qpi, pod, node_name in winners:
                self._assume(pod, node_name)
            for _qpi, pod, _node in rejected:
                # capacity the overlapped wave committed while this one
                # was on device: the pod is feasible, it just raced —
                # straight back through the active queue so the next
                # wave's FRESH snapshot re-places it (requeue: never
                # quota-held behind its tenant's newer arrivals)
                trace.span_pod(
                    "rearb_requeue", pod, wave=wave_id,
                    cause="capacity_raced",
                )
                self.queue.add(pod, requeue=True)
        self._count_evaluated(len(qpis), len(losers))
        self._commit_winners(winners, "wave")
        if losers:
            self._handle_wave_losers(
                losers, prepared.node_infos, len(prepared.node_infos)
            )
        if _WAVE_LOG:
            import sys

            print(
                f"[wave t={time.monotonic():.2f}] size={len(qpis)} "
                f"winners={len(winners)} losers={len(losers)} "
                f"requeued={len(rejected)}",
                file=sys.stderr,
                flush=True,
            )

    def _rearbitrate_winners(self, winners: List[Any]):
        """(kept, rejected) — validate each pipelined winner against the
        CURRENT capacity view (live cache NodeInfos + assume-cache, with
        double-count protection for assumptions whose bind events already
        landed), debiting locally so this wave's own winners arbitrate
        among themselves on the refreshed base.  Only chains that filter
        on capacity re-arbitrate (see _rearb_capacity); a node absent
        from the cache passes through — the bind transaction's commit-
        time validation is the final arbiter either way."""
        if not winners or not self._rearb_capacity:
            return winners, []
        from minisched_tpu.api.objects import MIB

        free, counted = self.cache.capacity_view(
            {node_name for _, _, node_name in winners}
        )
        with self._assumed_lock:
            for uid, assumed in self._assumed.items():
                node = assumed.spec.node_name
                b = free.get(node)
                if b is None or uid in counted.get(node, ()):
                    continue
                agg = self._assumed_agg[uid]
                b[0] -= agg[0]
                b[1] -= agg[1]
                b[2] -= agg[2]
                b[3] -= 1
        keep: List[Any] = []
        reject: List[Any] = []
        for win in winners:
            _qpi, pod, node_name = win
            b = free.get(node_name)
            if b is None:
                keep.append(win)
                continue
            req = pod.resource_requests()
            mem = req.memory // MIB
            eph = req.ephemeral_storage // MIB
            if (
                req.milli_cpu <= b[0]
                and mem <= b[1]
                and eph <= b[2]
                and b[3] >= 1
            ):
                b[0] -= req.milli_cpu
                b[1] -= mem
                b[2] -= eph
                b[3] -= 1
                keep.append(win)
            else:
                reject.append(win)
        if reject:
            from minisched_tpu.observability import counters

            # gang atomicity: a gang is released or kept WHOLE.  A member
            # rejected here means the overlapped wave took capacity the
            # build assumed free — keeping its siblings would admit a
            # partial gang that parks at Permit burning its TTL for a
            # member that cannot come.  Moving keepers to reject only
            # FREES locally-debited capacity, so earlier keep decisions
            # stay conservative-valid.
            from minisched_tpu.api.objects import gang_key

            hit = {gang_key(pod) for _q, pod, _n in reject}
            hit.discard(None)
            if hit:
                moved = [w for w in keep if gang_key(w[1]) in hit]
                if moved:
                    keep = [w for w in keep if gang_key(w[1]) not in hit]
                    reject = reject + moved
                    counters.inc("gang.rearb_atomic_release", len(moved))
            counters.inc("wave_pipeline.rearb_requeued", len(reject))
        return keep, reject

    def _flush_scan_backlog_timed(self) -> None:
        """The flush as the loop's own phase (``scan_flush``); the wave's
        priority bypass calls the flush bare, inside its ``wave`` phase."""
        self._scan_call += 1
        with self.metrics.timed(
            "scan_flush", call=self._scan_call, n=len(self._scan_backlog)
        ):
            self._flush_scan_backlog()

    def _flush_scan_backlog(self) -> None:
        """Run the deferred cross-pod lane over everything accumulated.
        Snapshots fresh state — the backlog outlives the wave snapshots
        it was deferred from."""
        backlog, self._scan_backlog = self._scan_backlog, []
        self._scan_backlog_waves = 0
        # the deferral window is minutes, not milliseconds: a pod can be
        # DELETED, RECREATED, or UPDATED while parked here, and the
        # queue's own update/delete handling can no longer reach it (it
        # was popped).  Re-validate every entry: drop the gone and the
        # renamed-uid recreations (the informer ADD already enqueued the
        # new incarnation), refresh the spec of the changed.
        live_backlog: List[QueuedPodInfo] = []
        for qpi, cur in self._revalidate_backlog(backlog):
            if (
                cur.metadata.resource_version
                != qpi.pod.metadata.resource_version
            ):
                qpi.pod_info.pod = cur
            live_backlog.append(qpi)
        if not live_backlog:
            return
        try:
            node_infos, agg_delta, assumed_pods = self._snapshot_for_wave()
            if not node_infos:
                for qpi in live_backlog:
                    self.error_func(qpi, FitError(qpi.pod, 0, Diagnosis()))
                return
            self._schedule_scan(
                live_backlog, node_infos, agg_delta, assumed_pods
            )
        except Exception as err:
            # advisor r4: the run loop's catch-all would swallow this and
            # the (already-swapped-out) backlog pods would sit Pending
            # until an unrelated event — the wave path parks its batch
            # via error_func on exception, this lane must too
            self._note_park(err, len(live_backlog))
            self._park_scan_failures(live_backlog, err)

    def _revalidate_backlog(self, qpis: List[QueuedPodInfo]):
        """The shared liveness rule for backlog entries: (qpi, current
        pod) pairs for those still present, same-uid, and unbound — one
        informer lock hold (get_many; no per-pod store round-trips in
        front of the single device call the deferral amortizes).  Flush
        schedules the survivors; park error_funcs them."""
        pod_inf = self.informer_factory.informer_for("Pod")
        keys = [
            f"{q.pod.metadata.namespace}/{q.pod.metadata.name}" for q in qpis
        ]
        out = []
        for qpi, cur in zip(qpis, pod_inf.get_many(keys)):
            if cur is None:
                continue  # deleted while deferred
            if cur.metadata.uid != qpi.pod.metadata.uid:
                continue  # recreated under the same name: not this entry
            if cur.spec.node_name:
                continue  # bound elsewhere while deferred
            out.append((qpi, cur))
        return out

    def _park_scan_failures(self, qpis: List[QueuedPodInfo], err) -> None:
        """Route the still-unplaced pods of a failed scan through
        error_func → unschedulableQ.  Pods the lane already committed
        before the raise (assumed and/or bound — chunks commit as they
        go) are skipped: error_func would forget a live assumption and
        requeue a pod that was in fact placed.  The assume snapshot is
        taken BEFORE the informer read: a pod leaves _assumed only after
        the informer reflects its bind, so this order can't miss a
        commit that confirms between the two reads (the reverse could).

        An assumption alone does NOT prove commitment: the batch bind can
        raise AFTER the assume (transport failure on a remote store) —
        for assumed-but-informer-unbound pods the AUTHORITATIVE store
        decides.  Bound there: a real commit whose event just hasn't
        dispatched — skip.  Unbound there: the bind never landed — park
        (error_func also forgets the assumption, releasing the capacity
        that would otherwise stay double-booked for the process life).
        Store UNREACHABLE: keep the assumption (the bind may be real) but
        re-defer the qpi instead of dropping it — a later flush retries
        the park decision; dropping it here left the pod Pending forever
        while its assumption double-booked the node (advisor r5)."""
        with self._assumed_lock:
            assumed = set(self._assumed)
        for qpi, cur_cache in self._revalidate_backlog(qpis):
            if qpi.pod.metadata.uid in assumed:
                try:
                    cur = self.client.pods().get(
                        qpi.pod.metadata.name, qpi.pod.metadata.namespace
                    )
                except KeyError:
                    continue  # deleted meanwhile: nothing to requeue
                except Exception:
                    self._scan_backlog.append(qpi)
                    continue
                if cur.spec.node_name:
                    continue  # committed by an earlier chunk
            # mirror _flush_scan_backlog: a pod updated while deferred must
            # be requeued with its REFRESHED spec — the update event
            # already fired and can't reach this popped copy (advisor r5)
            if (
                cur_cache.metadata.resource_version
                != qpi.pod.metadata.resource_version
            ):
                qpi.pod_info.pod = cur_cache
            self.error_func(qpi, err)

    def _next_wave_id(self) -> int:
        return next(self._wave_ids)

    def schedule_wave(
        self, qpis: List[QueuedPodInfo], wave_id: Optional[int] = None
    ) -> None:
        """A popped batch on the loop thread, from split to commit: what
        ``schedule_one`` runs for a batch the worker handed back raw, and
        what a caller without a loop (tests) runs directly.  ``wave_id``:
        the id the build worker drew when it popped the batch; a wave
        popped here draws its own."""
        self._wave_seq = wave_id or self._next_wave_id()
        from minisched_tpu.observability import trace

        trace.span(
            "wave_build", wave=self._wave_seq, size=len(qpis),
            serial=True, mesh=self._mesh_shards,
        )
        # the 'wave' metric must observe EVERY exit path (empty-node
        # return, parked batch, scan-only wave, a raise): pop + wave +
        # scan_flush + gc account for the loop thread's whole wall
        with self.metrics.timed("wave", wave=self._wave_seq, n=len(qpis)):
            self._schedule_wave_inner(qpis)

    def _schedule_wave_inner(self, qpis: List[QueuedPodInfo]) -> None:

        # cross-pod-constrained pods run on device via the sequential scan
        # (they see each other's commits in the carried combo planes —
        # bind-exact semantics the repair wave cannot give them).  They are
        # DEFERRED rather than run per wave: each lane call pays one
        # packed transfer + dispatch however few pods it carries, so
        # constrained pods accumulate in pop order across waves and the
        # lane is entered once per ~BLOCKED_MAX_CHUNK — or when the queue
        # drains (schedule_one).  The global order is thus
        # [plain…×k, constrained…] — per-group FIFO (the exactness contract) is untouched, and the
        # lane's acceptance/audit guarantees don't depend on WHEN it runs.
        # A chain WITHOUT cross-pod plugins never evaluates the constraints
        # at all (reference semantics with the plugin disabled) — no scan.
        # The split runs BEFORE the snapshot: the priority bypass below
        # may flush (and commit) the backlog, which a snapshot already in
        # hand would not see — capacity double-booking.
        if self._has_cross_pod:
            constrained = [qpi for qpi in qpis if _is_cross_pod(qpi.pod)]
            if constrained:
                self._scan_backlog.extend(constrained)
                plain = [qpi for qpi in qpis if not _is_cross_pod(qpi.pod)]
                if not plain:
                    return  # nothing for the wave: no snapshot, no build
                qpis = plain
            # priority-inversion bypass (advisor r4): deferral reorders
            # constrained pods behind up to SCAN_DEFER_MAX_WAVES full
            # waves of later-arriving plain pods.  Near capacity a plain
            # wave could consume resources that priority/FIFO pop order
            # had given an earlier, HIGHER-priority constrained pod — so
            # when any deferred pod outranks any plain pod about to run,
            # the backlog flushes first (restoring the order the queue
            # popped them in).  Same-priority workloads (the common case)
            # never trigger this and keep the amortized single-call lane.
            # The max is derived at the read site — the backlog is
            # bounded by ~BLOCKED_MAX_CHUNK, and cached state would need
            # resets at every site that mutates the backlog.
            if self._scan_backlog:
                hi = max(q.pod.spec.priority for q in self._scan_backlog)
                if hi > min(q.pod.spec.priority for q in qpis):
                    self._flush_scan_backlog()

        with self.metrics.timed("wave_snapshot"):
            # while a build worker exists it is the single ordered
            # consumer of the cache's dirty-set — draining it here too
            # would interleave two snapshot orders into one aggregate base
            # (stale-row overwrites).  Untracked builds never touch the
            # base; the accumulated dirt stays pending for the worker.
            snapshot = self._snapshot_for_tables(
                want_dirty=self._pipeline is None
            )
        if not snapshot[0]:
            for qpi in qpis:
                self.error_func(qpi, FitError(qpi.pod, 0, Diagnosis()))
            return
        qpis, prepared = self._evaluate_or_park(
            qpis, lambda qpis_: build_wave(self, qpis_, snapshot)
        )
        if prepared is not None:
            self._finish_wave(prepared)

    def _handle_wave_losers(
        self, losers: List[Any], node_infos: List[Any], n_nodes: int
    ) -> None:
        """Park every wave loser, then run the host-side PostFilter chain
        (preemption) for each preemption-ELIGIBLE one — like the scalar
        engine's failure path.

        Parking happens FIRST so victims' Pod/DELETE requeue events find
        the losers in the unschedulableQ.  Losers whose recorded failures
        are all node-static (NodeAffinity & co — eviction can't flip them,
        ``preemption_might_help``) skip the chain outright: a wave can park
        thousands of such pods and each PostFilter pass walks the whole
        snapshot.  Each eligible loser preempts against a snapshot adjusted
        for the wave: this wave's assumed winners, the victims earlier
        losers already evicted, and earlier losers' nominated pods (which
        will consume the capacity they freed) — otherwise several losers
        select the same victims and over-evict.
        """
        with self.metrics.timed("losers_handle"):
            self._handle_wave_losers_inner(losers, node_infos, n_nodes)

    def _handle_wave_losers_inner(
        self, losers: List[Any], node_infos: List[Any], n_nodes: int
    ) -> None:
        from minisched_tpu.plugins.defaultpreemption import preemption_might_help

        diagnoses = {}
        for qpi, pod, fails in losers:
            diagnosis = Diagnosis()
            # the fused evaluator's per-plugin masks name the actual
            # first-failing plugin(s) per pod (minisched.go:118-121,134
            # semantics); an empty set (e.g. empty-chain configs) falls
            # back to the whole chain so event-gated requeue can't strand
            diagnosis.unschedulable_plugins = set(fails) or {
                p.name() for p in self.filter_plugins
            }
            diagnoses[pod.metadata.uid] = diagnosis
            self.error_func(qpi, FitError(pod, n_nodes, diagnosis))
            if self.on_decision:
                self.on_decision(
                    pod, None, Status.unschedulable("no feasible node")
                )
        if not self.post_filter_plugins:
            return
        eligible = [
            (qpi, pod)
            for qpi, pod, _fails in losers
            if preemption_might_help(diagnoses[pod.metadata.uid])
        ]
        if not eligible:
            return
        # victim-availability gate: preemption can only evict pods with
        # priority BELOW the loser's, so a loser at or under the cluster's
        # lowest assigned priority has zero possible victims — running
        # DefaultPreemption for it would walk every node's pod list for
        # nothing.  A replay wave can strand thousands of equal-priority
        # losers at once (config5: ~2k losers × 10k nodes × ~10 pods each
        # ground the engine for minutes finding no victims); the floor
        # check skips the whole pass in O(assigned).
        prio_floor = None
        for ni in node_infos:
            for p in ni.pods:
                if prio_floor is None or p.spec.priority < prio_floor:
                    prio_floor = p.spec.priority
        with self._assumed_lock:
            for a in self._assumed.values():
                if prio_floor is None or a.spec.priority < prio_floor:
                    prio_floor = a.spec.priority
        eligible = [
            (qpi, pod)
            for qpi, pod in eligible
            if prio_floor is not None and pod.spec.priority > prio_floor
        ]
        if not eligible:
            return
        # ONE full merged snapshot (informer state + this wave's assumed
        # winners); per-loser deltas (evictions, phantoms) are applied
        # incrementally to just the touched NodeInfos
        base = self._merged_infos(node_infos)
        by_name = {ni.name: ni for ni in base}
        # a wave processes at most MAX_PREEMPT_PER_WAVE losers through the
        # PostFilter chain (each pass is O(nodes × pods) host work; upstream
        # runs preemption once per scheduling cycle, so its throughput is
        # naturally bounded — an 8k-pod wave's losers are not).  Budget
        # goes to the HIGHEST-priority losers (stable within a class), so
        # truncation can never starve a high-priority pod behind a crowd
        # of lower ones; the skipped rest are already parked and retry.
        if len(eligible) > self.MAX_PREEMPT_PER_WAVE:
            eligible = sorted(
                eligible, key=lambda e: -e[1].spec.priority
            )[: self.MAX_PREEMPT_PER_WAVE]
        for qpi, pod in eligible:
            nominated = self.run_post_filter(
                CycleState(), pod, base, diagnoses[pod.metadata.uid]
            )
            # victims reported by the plugins (DefaultPreemption records
            # them) — diffing full store listings per loser would clone
            # the whole pod population each time
            for pl in self.post_filter_plugins:
                # consume-on-read: run_post_filter short-circuits on the
                # first Success, so a plugin NOT invoked for this loser
                # must not replay victims recorded for an earlier one
                victims = getattr(pl, "last_victims", ())
                if victims:
                    pl.last_victims = []
                for victim in victims:
                    ni = by_name.get(victim.spec.node_name)
                    if ni is not None:
                        ni.remove_pod(victim)
            if nominated:
                # the phantom consumes the freed capacity so later losers
                # can't select the same victims and over-evict
                ph = pod.clone()
                ph.spec.node_name = nominated
                target = by_name.get(nominated)
                if target is not None:
                    target.add_pod(ph)

    def _merged_infos(self, node_infos: List[Any]) -> List[Any]:
        """Clone of the wave snapshot with the assume-cache folded in —
        the preemption base: capacity this wave's winners just took must
        not be offered to victims' replacements."""
        known = {
            p.metadata.uid for ni in node_infos for p in ni.pods
        }
        with self._assumed_lock:
            assumed = [
                a for a in self._assumed.values() if a.metadata.uid not in known
            ]
        merged = [ni.clone() for ni in node_infos]
        by_name = {ni.name: ni for ni in merged}
        for a in assumed:
            ni = by_name.get(a.spec.node_name)
            if ni is not None:
                ni.add_pod(a)
        return merged

    def _drop_unencodable(self, qpis: List[QueuedPodInfo]) -> List[QueuedPodInfo]:
        """Park pods whose specs exceed the static table capacities (they
        can never be device-scheduled; the scalar engine could still take
        them).  Each offender goes through error_func with its encode
        error; the rest of the wave proceeds."""
        good: List[QueuedPodInfo] = []
        for qpi in qpis:
            try:
                build_pod_table([qpi.pod], capacity=128)
                if self._needs_extra:  # only caps the wave actually encodes
                    build_constraint_tables([qpi.pod], [], [], pod_capacity=128,
                                            node_capacity=128,
                                            scan_planes=False)
            except ValueError as err:
                self.error_func(qpi, err)
                if self.on_decision:
                    self.on_decision(qpi.pod, None, Status.from_error(err))
                continue
            good.append(qpi)
        return good

    def _record_wave(
        self, qpis, node_infos, assigned, agg_delta, pod_capacity
    ) -> None:
        """record_results support: one diagnostics-enabled fused
        evaluation of the wave against its snapshot (the decision basis),
        ingested via ``Store.record_batch_result`` — the wave emits the
        same per-plugin artifact the scalar recorders produce (SURVEY §2
        row 10): same annotation keys, same canonical rejection strings —
        flushed onto pod annotations by the store's update hook when the
        binds land.

        The engine's tables are packed host buffers; the diagnostics
        evaluator wants device tables, so this builds its own from the
        same snapshot, with a builder of its own and untracked: it never
        drains the cache's dirty set nor touches ``_table_builder``'s
        aggregate base."""
        if self.result_store is None:
            return
        pods_ = [qpi.pod for qpi in qpis]
        from minisched_tpu.ops.fused import FusedEvaluator
        from minisched_tpu.plugins.registry import canonical_filter_reasons

        if self._diag_evaluator is None:
            self._diag_evaluator = FusedEvaluator(
                self.filter_plugins,
                self.pre_score_plugins,
                self.score_plugins,
                weights=self.score_weights,
                with_diagnostics=True,
            )
            self._record_builder = CachedNodeTableBuilder()
        try:
            node_table, node_names = self._record_builder.build(
                node_infos, agg_delta=agg_delta
            )
            pod_table, _ = build_pod_table(
                pods_, capacity=pod_capacity,
                gang_view=self._gang_view(pods_),
            )
            extra = None
            if self._needs_extra:
                extra = self._build_constraints(
                    pods_, [ni.node for ni in node_infos], assigned,
                    pod_capacity=pod_capacity,
                    node_capacity=node_table.capacity,
                    scan_planes=False,  # a fused evaluation, never a scan
                )
            result = self._diag_evaluator(pod_table, node_table, extra)
        except Exception:
            import traceback

            traceback.print_exc()
            return

        def unwrap(pl) -> str:
            return getattr(pl, "original_name", None) or pl.name()

        self.result_store.record_batch_result(
            result,
            [p.metadata.key for p in pods_],
            node_names,
            [unwrap(pl) for pl in self.filter_plugins],
            [unwrap(pl) for pl in self.score_plugins],
            reasons=canonical_filter_reasons(),
        )

    @staticmethod
    def _count_evaluated(evaluated: int, unschedulable: int) -> None:
        """Once an evaluate, whichever lane ran it: the pods it was handed
        and those it returned without a node (a feasible pod that lost a
        capacity race inside a blocked call is neither: it retries)."""
        from minisched_tpu.observability import counters

        counters.inc("sched.evaluated_pods", evaluated)
        counters.inc("sched.unschedulable_pods", unschedulable)

    def _commit_winners(self, winners: List[Any], lane: str) -> None:
        """Host-side tail of the wave for every placed pod: reserve →
        permit per pod (host plugin chains, minisched.go:89-112), then ONE
        batched bind transaction for all immediately-bindable pods — a
        wave commits thousands of placements and a store round-trip per
        bind dominated the e2e profile.  Pods a permit plugin parked in
        Wait still get a detached binding cycle (the wait can be seconds).

        ``winners``: (qpi, pod, node_name) triples, already assumed;
        ``lane`` is the program that placed them: ``wave``, the blocked
        scan's ``wide`` or ``narrow`` layout, or ``exact`` (counted by
        ``sched.lane_pods.<lane>``, counters.LANE_COUNTERS).
        """
        from minisched_tpu.observability import counters

        counters.inc("sched.lane_pods." + lane, len(winners))
        with self.metrics.timed("commit", wave=self._wave_seq, n=len(winners)):
            self._commit_winners_inner(winners)

    def _commit_winners_inner(self, winners: List[Any]) -> None:
        from minisched_tpu.framework.types import CycleState

        ready: List[Any] = []
        if not self.reserve_plugins and not self.permit_plugins:
            # both chains empty (the default full roster): nothing to run
            # per pod — go straight to the batched bind.  One shared
            # CycleState is safe: it is only consulted by unreserve on a
            # failed bind, and there is nothing to unreserve.
            state = CycleState()
            ready = [(qpi, pod, node_name, state) for qpi, pod, node_name in winners]
            winners = []
        if winners:
            # one span over the wave's reserve and permit chains, never
            # one a pod (the default full roster has neither chain)
            with self.metrics.timed("permit", n=len(winners)):
                ready += self._reserve_and_permit(winners)
        if not ready:
            return
        # the batch bind runs ON the engine thread: a worker-thread
        # pipeline was tried and regressed ~40% — the bind is pure-Python
        # host work, so overlapping it with the next wave's (also
        # Python) snapshot/build just thrashes the GIL.  The informer
        # dispatch of its events naturally overlaps the next wave's
        # GIL-free device call instead.
        self._bind_batch(ready)

    def _reserve_and_permit(self, winners: List[Any]) -> List[Any]:
        """Reserve → permit for each placed pod; returns the ones ready to
        bind now.  A pod a permit plugin parked in Wait gets its detached
        binding cycle here; a refused one goes through error_func."""
        ready: List[Any] = []
        for qpi, pod, node_name in winners:
            state = CycleState()
            status = self.run_reserve_plugins(state, pod, node_name)
            if not status.is_success():
                self.error_func(qpi, status.as_error(), plugin=status.plugin)
                if self.on_decision:
                    self.on_decision(pod, None, status)
                continue
            status = self.run_permit_plugins(state, pod, node_name)
            if not status.is_success() and not status.is_wait():
                self.run_unreserve_plugins(state, pod, node_name)
                self.error_func(qpi, status.as_error(), plugin=status.plugin)
                if self.on_decision:
                    self.on_decision(pod, None, status)
                continue
            if status.is_wait():
                from minisched_tpu.observability import trace

                trace.span_pod(
                    "permit_wait", pod, wave=self._wave_seq,
                    node=node_name, plugin=status.plugin,
                )
                t = threading.Thread(
                    target=self._binding_cycle,
                    args=(qpi, pod, node_name, state),
                    name=f"bind-{pod.metadata.name}",
                    daemon=True,
                )
                with self._bind_lock:
                    self._bind_threads.add(t)
                t.start()
                continue
            ready.append((qpi, pod, node_name, state))
        return ready

    def _bind_batch(self, ready: List[Any]) -> None:
        from minisched_tpu.api.objects import Binding

        # expected_rv: the optimistic-concurrency precondition — bind only
        # if the pod is STILL at the version this wave evaluated (a spec
        # changed under us must re-evaluate, not land on stale
        # requirements).  The unset-node_name guard remains the wire-level
        # double-bind backstop; a Conflict comes back per-item and rides
        # the normal error_func → requeue path, where the refreshed pod
        # re-enters a later wave.
        bindings = [
            Binding(
                pod.metadata.name, pod.metadata.namespace, node_name,
                expected_rv=pod.metadata.resource_version or None,
            )
            for _, pod, node_name, _ in ready
        ]
        # close the dispatch gate BEFORE the events fan out: the informer
        # threads then hold this wave's thousands of bind events through
        # the next wave's host stretch (pop/snapshot/build) and process
        # them inside its GIL-free device call — _finish_wave reopens
        # the gate, schedule_one reopens it when the queue idles.
        # The handler work is identical either way (the assume-cache
        # carries placements until the events land); only WHEN it contends
        # for the GIL changes.
        self.informer_factory.pause_dispatch()
        with self.metrics.timed("bind", n=len(ready)):
            try:
                if self.faults is not None:
                    self.faults.check("engine.bind", str(len(ready)))
                # return_objects=False: the engine only inspects failures —
                # cloning 8k bound pods back to a caller that drops them
                # was a third of the bind's copy cost
                results = self.client.pods().bind_many(
                    bindings, return_objects=False
                )
            except Exception as err:
                # the TRANSACTION failed (store unreachable after the
                # remote client's own retries, WAL refusal, injected
                # fault) — before this catch the raise escaped through
                # schedule_one to the loop's catch-all and the whole
                # wave's winners were stranded: popped, assumed, in no
                # queue.  Fail every item instead: error_func forgets the
                # assumption and requeues; if the commit actually landed
                # server-side (response lost), the retried pod's next
                # bind returns AlreadyBound and the informer's bind event
                # settles it — converges either way, and the assume-lease
                # TTL backstops anything this path itself loses.
                from minisched_tpu.controlplane.store import StorageDegraded
                from minisched_tpu.observability import counters

                counters.inc("engine.bind_batch_failed")
                results = [err] * len(ready)
        # the binds changed cluster state NOW; the informer events land on
        # the dispatch thread later.  Record the move request so losers
        # whose attempts overlapped the commit re-queue through backoff
        # instead of parking past the event (the event-to-park race).
        from minisched_tpu.framework.events import ActionType, ClusterEvent, GVK

        self.queue.note_move_request(ClusterEvent(GVK.POD, ActionType.UPDATE))
        from minisched_tpu.observability import trace

        degraded_dumped = False
        for (qpi, pod, node_name, state), res in zip(ready, results):
            if isinstance(res, BaseException):
                from minisched_tpu.controlplane.store import StorageDegraded

                trace.span_pod(
                    "bind_failed", pod, wave=self._wave_seq,
                    node=node_name, cause=type(res).__name__,
                )
                if isinstance(res, StorageDegraded):
                    # the control plane's DISK gave out (ENOSPC/EIO, or
                    # HTTP 507 outlasting the remote client's backoff):
                    # the wave PARKS instead of crashing — error_func
                    # below forgets the assumption (releasing the
                    # capacity) and requeues, so the pod retries once
                    # the store's recovery probe re-arms appends
                    from minisched_tpu.observability import counters

                    counters.inc("storage.degraded_parks")
                    if not degraded_dumped:
                        degraded_dumped = True
                        trace.flight_dump("storage-degraded-park")
                self.run_unreserve_plugins(state, pod, node_name)
                if self._is_bind_race(res) and self._bind_race_refresh(qpi):
                    # bound by a peer / deleted while in-flight: drop
                    # instead of requeue (see Scheduler._bind_race_refresh
                    # — a re-parked stale copy would conflict forever),
                    # releasing the assumed capacity
                    self._forget(pod.metadata.uid)
                    if self.on_decision:
                        self.on_decision(pod, None, Status.from_error(res))
                    continue
                self.error_func(qpi, res)
                if self.on_decision:
                    self.on_decision(pod, None, Status.from_error(res))
            else:
                trace.span_pod(
                    "bind", pod, wave=self._wave_seq, node=node_name,
                )
                self.queue.observe_bind(pod, node_name)
                if self.on_decision:
                    self.on_decision(pod, node_name, Status.success())


def new_device_scheduler(
    client: Any,
    informer_factory: Any,
    cfg: Any = None,
    max_wave: int = 1024,
    mesh: Any = None,
) -> DeviceScheduler:
    """Build a DeviceScheduler from a SchedulerConfig (default: the full
    roster) — the device-mode analog of service.build_scheduler_from_config.
    ``mesh``: evaluate waves sharded over a jax.sharding.Mesh; None defers
    to the config's ``mesh_devices`` pin, then the MINISCHED_MESH startup
    policy (auto-shard when >1 device; see parallel/sharding.resolve_mesh)."""
    from minisched_tpu.plugins.registry import build_plugins
    from minisched_tpu.service.config import default_full_roster_config

    cfg = cfg or default_full_roster_config()
    if mesh is None and (cfg.mesh_devices or cfg.mesh_pod_shards):
        from minisched_tpu.parallel.sharding import make_mesh

        mesh = make_mesh(
            cfg.mesh_devices or None, pod_shards=cfg.mesh_pod_shards
        )
    chains = build_plugins(cfg)
    sched = DeviceScheduler(
        client,
        informer_factory,
        filter_plugins=chains.filter,
        post_filter_plugins=chains.post_filter,
        pre_score_plugins=chains.pre_score,
        score_plugins=chains.score,
        permit_plugins=chains.permit,
        reserve_plugins=chains.reserve,
        score_weights=cfg.score_weights(),
        queue_opts=cfg.queue_opts,
        max_wave=max_wave,
        mesh=mesh,
    )
    from minisched_tpu.service.service import _inject

    for p in chains.needs_handle:
        _inject(p, "h", sched)
    for p in chains.needs_client:
        _inject(p, "store_client", client)
    return sched
