"""The core scheduling engine: the scheduleOne loop and plugin runners.

Re-creates ``minisched/minisched.go`` + ``minisched/initialize.go``: the
four plugin chains (initialize.go:25-28), the per-pod
filter → pre-score → score → normalize → select-host → permit → bind cycle
(minisched.go:32-113), the detached binding goroutine per pod
(minisched.go:96-112), ``ErrorFunc`` requeueing (minisched.go:283-298), and
the waiting-pod registry (minisched.go:300-302).

This scalar engine is also the **parity oracle** (SURVEY.md §7 stage 4):
the TPU batch path must place pods identically, so every semantic here —
plugin order short-circuiting (minisched.go:130-137), score summation with
weights, the deterministic tie-break — is the ground truth the fused kernel
is tested against.

Fixed reference bugs (SURVEY.md §7): real errors passed to ErrorFunc
(vs stale/nil at minisched.go:64,73,92), score-plugin weights applied
(the TODO at minisched.go:187), nodes snapshotted from the informer cache
instead of a full re-list per cycle (minisched.go:40).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from minisched_tpu.api.objects import Binding, Pod
from minisched_tpu.controlplane.client import Client
from minisched_tpu.controlplane.informer import SharedInformerFactory
from minisched_tpu.engine import eventhandlers
from minisched_tpu.engine.tiebreak import select_host
from minisched_tpu.engine.waitingpod import WaitingPod
from minisched_tpu.framework.events import (
    ClusterEventMap,
    merge_event_registrations,
    unioned_gvks,
)
from minisched_tpu.framework.nodeinfo import NodeInfo
from minisched_tpu.framework.plugin import implements_enqueue, implements_pre_filter
from minisched_tpu.framework.types import (
    CycleState,
    Diagnosis,
    FitError,
    MAX_NODE_SCORE,
    QueuedPodInfo,
    Status,
    is_success,
)
from minisched_tpu.models.tables import pod_seed
from minisched_tpu.observability import profiling
from minisched_tpu.queue.queue import SchedulingQueue

# the scalar cycle's spans (one pod is its whole wave); permit and bind
# are also the wave engine's children of sched.wave_commit
profiling.register_phases(
    "snapshot", "schedule", "permit", "wait_on_permit", "bind"
)
profiling.register_phases("cycle", "cycle_failed", cpu=False)


# ---------------------------------------------------------------------------
# Pure extension-point runners (minisched.go:115-199) — module-level so the
# live engine and the stateless parity oracle share ONE implementation
# ---------------------------------------------------------------------------


def run_pre_filter_plugins(
    filter_plugins: List[Any], state: CycleState, pod: Pod, node_infos: List[NodeInfo]
) -> Tuple[Status, str]:
    """Once-per-pod PreFilter pass (upstream framework.PreFilterPlugin) for
    filter plugins that aggregate cluster-wide state.  Returns the first
    non-success status and the plugin that produced it."""
    for pl in filter_plugins:
        if implements_pre_filter(pl):
            status = pl.pre_filter(state, pod, node_infos)
            if not is_success(status):
                return status.with_plugin(status.plugin or pl.name()), pl.name()
    return Status.success(), ""


def run_filter_plugins(
    filter_plugins: List[Any], state: CycleState, pod: Pod, node_infos: List[NodeInfo]
) -> Tuple[List[NodeInfo], Diagnosis]:
    """Per node × per plugin with short-circuit on first failure
    (minisched.go:115-151); collects Diagnosis for event-gated requeue."""
    feasible: List[NodeInfo] = []
    diagnosis = Diagnosis()
    for ni in node_infos:
        ok = True
        for pl in filter_plugins:
            status = pl.filter(state, pod, ni)
            if not is_success(status):
                ok = False
                status.with_plugin(status.plugin or pl.name())
                diagnosis.node_to_status[ni.name] = status
                diagnosis.unschedulable_plugins.add(pl.name())
                if status.code.name == "ERROR":
                    raise status.as_error()
                break  # short-circuit this node (minisched.go:136)
        if ok:
            feasible.append(ni)
    return feasible, diagnosis


def run_post_filter_plugins(
    post_filter_plugins: List[Any],
    state: CycleState,
    pod: Pod,
    node_infos: List[NodeInfo],
    diagnosis: Diagnosis,
) -> Tuple[Optional[str], Status]:
    """Upstream RunPostFilterPlugins: runs after filtering leaves no
    feasible node; the first plugin returning Success wins (its nominated
    node is the result), an Error aborts, otherwise Unschedulable."""
    for pl in post_filter_plugins:
        nominated, status = pl.post_filter(state, pod, node_infos, diagnosis)
        if status.is_success():
            return nominated, status
        if status.code.name == "ERROR":
            return None, status.with_plugin(status.plugin or pl.name())
    return None, Status.unschedulable("no postFilter plugin made the pod schedulable")


def run_pre_score_plugins(
    pre_score_plugins: List[Any], state: CycleState, pod: Pod, nodes: List[Any]
) -> Status:
    for pl in pre_score_plugins:
        status = pl.pre_score(state, pod, nodes)
        if not is_success(status):
            return status.with_plugin(status.plugin or pl.name())
    return Status.success()


def run_score_plugins(
    score_plugins: List[Any],
    score_weights: Dict[str, int],
    state: CycleState,
    pod: Pod,
    node_names: List[str],
) -> Dict[str, int]:
    """Score + normalize + weighted sum (minisched.go:164-199 — with the
    weight TODO at :187 actually implemented)."""
    totals: Dict[str, int] = {name: 0 for name in node_names}
    for pl in score_plugins:
        scores: List[int] = []
        for name in node_names:
            s, status = pl.score(state, pod, name)
            if not is_success(status):
                raise status.as_error()
            scores.append(s)
        ext = pl.score_extensions() if hasattr(pl, "score_extensions") else None
        if ext is not None:
            from minisched_tpu.framework.types import NodeScore

            lst = [NodeScore(n, s) for n, s in zip(node_names, scores)]
            status = ext.normalize_score(state, pod, lst)
            if not is_success(status):
                raise status.as_error()
            scores = [ns.score for ns in lst]
        weight = score_weights.get(pl.name(), 1)
        for name, s in zip(node_names, scores):
            totals[name] += s * weight
    return totals


def schedule_pod_once(
    filter_plugins: List[Any],
    pre_score_plugins: List[Any],
    score_plugins: List[Any],
    score_weights: Dict[str, int],
    pod: Pod,
    node_infos: List[NodeInfo],
    state: Optional[CycleState] = None,
) -> str:
    """One stateless scheduling decision: filter → pre-score → score →
    select host (minisched.go:50-80).  Raises FitError/plugin errors on
    failure; returns the chosen node name.

    This is the **parity oracle** the fused TPU kernel
    (minisched_tpu.ops.fused) is tested against — the live engine's
    ``_schedule_pod`` is this exact code path.
    """
    state = state if state is not None else CycleState()
    # snapshot lister: plugins read per-node aggregates from CycleState under
    # "nodeinfo/<name>" and the full snapshot under "nodeinfos" (the role of
    # upstream's SnapshotSharedLister handle)
    for ni in node_infos:
        state.write("nodeinfo/" + ni.name, ni)
    state.write("nodeinfos", node_infos)
    pf_status, pf_plugin = run_pre_filter_plugins(
        filter_plugins, state, pod, node_infos
    )
    if not is_success(pf_status):
        if pf_status.code.name == "ERROR":
            raise pf_status.as_error()
        diagnosis = Diagnosis()
        diagnosis.unschedulable_plugins.add(pf_plugin)
        raise FitError(pod, len(node_infos), diagnosis)
    feasible, diagnosis = run_filter_plugins(filter_plugins, state, pod, node_infos)
    if not feasible:
        raise FitError(pod, len(node_infos), diagnosis)

    status = run_pre_score_plugins(
        pre_score_plugins, state, pod, [ni.node for ni in feasible]
    )
    if not is_success(status):
        raise status.as_error()

    totals = run_score_plugins(
        score_plugins, score_weights, state, pod, [ni.name for ni in feasible]
    )

    # deterministic seeded argmax (replaces reservoir sampling,
    # minisched.go:304-325).  The tie-break hash is keyed on the node's
    # GLOBAL index in the name-sorted snapshot — the same indexing the
    # fused batch kernel uses (ops/fused.py) — so oracle and kernel
    # agree bit-exactly even though scoring only ran on feasible nodes.
    seed = pod_seed(pod.metadata.uid or pod.metadata.name)
    feasible_names = {ni.name for ni in feasible}
    idx = select_host(
        [totals.get(ni.name, 0) for ni in node_infos],
        [ni.name in feasible_names for ni in node_infos],
        seed,
    )
    return node_infos[idx].name


def schedule_pods_sequentially(
    filter_plugins: List[Any],
    pre_score_plugins: List[Any],
    score_plugins: List[Any],
    score_weights: Dict[str, int],
    pods: List[Pod],
    node_infos: List[NodeInfo],
) -> List[str]:
    """Scalar oracle with sequential-bind semantics: each placement is
    committed into the NodeInfo snapshot before the next pod — exactly the
    reference loop's visibility (minisched.go:32-113, one pod per cycle).
    Returns one node name per pod ('' = unschedulable).  This is the
    parity ground truth for the device scan engine (ops/sequential.py).
    """
    by_name = {ni.name: ni for ni in node_infos}
    out: List[str] = []
    for pod in pods:
        try:
            name = schedule_pod_once(
                filter_plugins,
                pre_score_plugins,
                score_plugins,
                score_weights,
                pod,
                node_infos,
            )
        except FitError:
            out.append("")
            continue
        out.append(name)
        bound = pod.clone()
        bound.spec.node_name = name
        by_name[name].add_pod(bound)
    return out


class Scheduler:
    """The engine (minisched/initialize.go:18-29's Scheduler struct)."""

    def __init__(
        self,
        client: Client,
        informer_factory: SharedInformerFactory,
        filter_plugins: List[Any],
        pre_score_plugins: List[Any],
        score_plugins: List[Any],
        permit_plugins: List[Any],
        score_weights: Optional[Dict[str, int]] = None,
        queue_opts: Optional[dict] = None,
        reserve_plugins: Optional[List[Any]] = None,
        post_filter_plugins: Optional[List[Any]] = None,
    ):
        self.client = client
        self.informer_factory = informer_factory
        self.filter_plugins = filter_plugins
        self.post_filter_plugins = post_filter_plugins or []
        self.pre_score_plugins = pre_score_plugins
        self.score_plugins = score_plugins
        self.permit_plugins = permit_plugins
        self.reserve_plugins = reserve_plugins or []
        self.score_weights = score_weights or {}

        # EventsToRegister → ClusterEventMap (initialize.go:68-75)
        self.event_map: ClusterEventMap = {}
        all_plugins = {
            id(p): p
            for p in filter_plugins
            + pre_score_plugins
            + score_plugins
            + self.reserve_plugins
            + permit_plugins
        }
        merge_event_registrations(
            (
                (p.name(), p.events_to_register())
                for p in all_plugins.values()
                if implements_enqueue(p)
            ),
            self.event_map,
        )
        self.queue = SchedulingQueue(event_map=self.event_map, **(queue_opts or {}))

        #: HA shard filter (ha/membership.Membership.owns_pod): when set,
        #: the event handlers admit only this engine's shard into the
        #: queue — None (the default) admits everything (single-engine
        #: mode is a plane of one).  Installed BEFORE the informers start
        #: (service.start_scheduler) so the initial replay is filtered.
        self.shard_filter: Optional[Callable[[Pod], bool]] = None

        self._waiting_pods: Dict[str, WaitingPod] = {}
        self._waiting_lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._bind_lock = threading.Lock()
        self._bind_threads: set = set()
        # observability hooks: fn(pod, node_name_or_None, status), and
        # per-phase timing — a REAL CycleMetrics by default (ISSUE 11):
        # every phase is a span (observability/profiling) whose histograms
        # /metrics serves, and live telemetry must not depend on a bench
        # attaching a collector.  Assign NULL_METRICS to opt out.
        self.on_decision: Optional[Callable[[Any, Optional[str], Status], None]] = None
        self.metrics: Any = profiling.CycleMetrics()

        # incremental NodeInfo cache (upstream cache.Cache analog) — wired
        # BEFORE the queue handlers so a requeued pod's next snapshot
        # already reflects the event that woke it (same dispatch thread,
        # registration order = invocation order)
        from minisched_tpu.engine.cache import SchedulerCache

        # engine-specific handlers that must register before the cache's
        # (the device engine's ConstraintIndex: the assume-cache is pruned
        # against the cache, so the index may never lag it)
        self._wire_pre_cache(informer_factory)
        self.cache = SchedulerCache()
        self.cache.wire(informer_factory)

        eventhandlers.add_all_event_handlers(
            self, informer_factory, unioned_gvks(self.event_map)
        )

        # gang-aware permit plugins (Coscheduling) count a gang's
        # already-BOUND members toward admission; inject the engine's
        # placed-member lookup (the device engine overrides it with its
        # incremental GangIndex)
        for p in permit_plugins:
            if hasattr(p, "gang_lister") and p.gang_lister is None:
                p.gang_lister = self._gang_placed_count

    def _gang_placed_count(self, key: str, exclude=()) -> int:
        """Bound members of gang ``key`` (uid-distinct, minus
        ``exclude``) from the informer cache — O(pods), fine at scalar-
        engine scale; DeviceScheduler overrides with its GangIndex."""
        from minisched_tpu.api.objects import gang_key

        try:
            pods = self.informer_factory.informer_for("Pod").lister()
        except Exception:
            return 0
        ex = set(exclude)
        return sum(
            1
            for p in pods
            if p.spec.node_name
            and p.metadata.uid not in ex
            and gang_key(p) == key
        )

    def _wire_pre_cache(self, informer_factory: Any) -> None:
        """Hook for subclasses that need informer handlers registered
        BEFORE the NodeInfo cache's (see __init__)."""

    def admits(self, pod: Pod) -> bool:
        """Queue-admission predicate: does this engine schedule ``pod``?
        The event handlers consult it on every pending-pod event; an HA
        plane sets ``shard_filter`` so N engines partition the keyspace."""
        f = self.shard_filter
        return True if f is None else f(pod)

    # ------------------------------------------------------------------
    # lifecycle (minisched.go:28-30)
    # ------------------------------------------------------------------
    def run(self) -> None:
        self._thread = threading.Thread(
            target=self._loop, name="scheduleOne-loop", daemon=True
        )
        self._thread.start()

    #: cadence of the unschedulableQ leftover flush (upstream runs
    #: flushUnschedulableQLeftover every 30s; pods parked longer than the
    #: queue's unschedulable_timeout_s replay even with no helping event)
    UNSCHEDULABLE_FLUSH_INTERVAL_S = 30.0

    def _loop(self) -> None:
        last_flush = time.monotonic()
        while not self._stop.is_set():
            try:
                now = time.monotonic()
                if now - last_flush >= self.UNSCHEDULABLE_FLUSH_INTERVAL_S:
                    last_flush = now
                    self.queue.flush_unschedulable_leftover()
                self.schedule_one()
            except Exception:  # the loop must survive anything
                import traceback

                traceback.print_exc()

    def stop(self) -> None:
        self._stop.set()
        self.queue.close()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
        with self._bind_lock:
            binds = list(self._bind_threads)
        for t in binds:
            t.join(timeout=2.0)

    # ------------------------------------------------------------------
    # the hot loop (minisched.go:32-113)
    # ------------------------------------------------------------------
    def snapshot_nodes(self) -> List[NodeInfo]:
        """Name-sorted NodeInfo snapshot from the incremental cache —
        O(nodes) clones per cycle instead of the reference's full re-list
        + re-wrap of every node AND pod (minisched.go:40,126-127)."""
        return self.cache.snapshot()

    def schedule_one(self, timeout: Optional[float] = 0.5) -> bool:
        qpi = self.queue.pop(timeout=timeout)
        if qpi is None:
            return False
        pod = qpi.pod
        state = CycleState()
        t_cycle = time.monotonic()
        with self.metrics.timed("snapshot"):
            node_infos = self.snapshot_nodes()

        try:
            with self.metrics.timed("schedule"):
                node_name = self._schedule_pod(state, pod, node_infos, qpi)
        except Exception as err:
            # park the pod BEFORE preempting: the victims' Pod/DELETE
            # requeue events must find it in the unschedulableQ — deleting
            # first opens a window where the only wake-up event fires while
            # the pod is in neither queue (upstream closes the same window
            # with moveRequestCycle)
            self.error_func(qpi, err)
            if isinstance(err, FitError):
                # PostFilter runs when filtering fails (upstream
                # RunPostFilterPlugins) — preemption may free a node; the
                # parked pod lands once the victims' DELETE events replay it
                self.run_post_filter(state, pod, node_infos, err.diagnosis)
            if self.on_decision:
                self.on_decision(pod, None, Status.from_error(err))
            self.metrics.observe("cycle_failed", time.monotonic() - t_cycle)
            return True

        forked = self._reserve_permit_and_fork(qpi, pod, node_name, state)
        self.metrics.observe(
            "cycle" if forked else "cycle_failed", time.monotonic() - t_cycle
        )
        return True

    def _reserve_permit_and_fork(
        self,
        qpi: QueuedPodInfo,
        pod: Pod,
        node_name: str,
        state: CycleState,
        inline: bool = False,
    ) -> bool:
        """The host-side tail every engine shares: reserve (upstream
        RunReservePlugins — rolled back on any later failure) → permit
        (minisched.go:89-94) → detach the binding cycle (minisched.go:96-112).
        Returns False when the pod failed (already sent through error_func).

        ``inline=True`` runs the binding cycle on the calling thread when no
        permit plugin asked to Wait — the wave engine binds thousands of
        pods per wave and a thread per bind is pure overhead there; with a
        Wait pending the cycle still detaches (the wait can be seconds).
        """
        status = self.run_reserve_plugins(state, pod, node_name)
        if not status.is_success():
            self.error_func(qpi, status.as_error(), plugin=status.plugin)
            if self.on_decision:
                self.on_decision(pod, None, status)
            return False

        with self.metrics.timed("permit"):
            status = self.run_permit_plugins(state, pod, node_name)
        if not status.is_success() and not status.is_wait():
            self.run_unreserve_plugins(state, pod, node_name)
            self.error_func(qpi, status.as_error(), plugin=status.plugin)
            if self.on_decision:
                self.on_decision(pod, None, status)
            return False

        if inline and not status.is_wait():
            self._binding_cycle(qpi, pod, node_name, state)
            return True
        t = threading.Thread(
            target=self._binding_cycle,
            args=(qpi, pod, node_name, state),
            name=f"bind-{pod.metadata.name}",
            daemon=True,
        )
        with self._bind_lock:
            self._bind_threads.add(t)
        t.start()
        return True

    def _schedule_pod(
        self,
        state: CycleState,
        pod: Pod,
        node_infos: List[NodeInfo],
        qpi: QueuedPodInfo,
    ) -> str:
        return schedule_pod_once(
            self.filter_plugins,
            self.pre_score_plugins,
            self.score_plugins,
            self.score_weights,
            pod,
            node_infos,
            state=state,
        )

    def run_post_filter(
        self,
        state: CycleState,
        pod: Pod,
        node_infos: List[NodeInfo],
        diagnosis: Diagnosis,
    ) -> Optional[str]:
        """Run the PostFilter chain on a scheduling failure; on success the
        nominated node lands in status.nominated_node_name through the
        API (upstream's nominatedNodeName).  Never raises — a preemption
        failure must not mask the original FitError path."""
        if not self.post_filter_plugins:
            return None
        try:
            nominated, status = run_post_filter_plugins(
                self.post_filter_plugins, state, pod, node_infos, diagnosis
            )
        except Exception:
            import traceback

            traceback.print_exc()
            return None
        if status.is_success() and nominated:
            # the nomination goes through the API ONLY (upstream patches
            # status.nominatedNodeName); the informer MODIFIED event then
            # refreshes the parked pod in the queue.  Never write the
            # local object in place: pods flow into the engine as watch-
            # event objects, which since the fanout-clone removal ARE the
            # store's canonical objects — an in-place write would mutate
            # the store outside its lock, unversioned and un-WAL-logged.
            def set_nominated(p):
                p.status.nominated_node_name = nominated
                return p

            try:
                self.client.pods(pod.metadata.namespace).mutate(
                    pod.metadata.name, set_nominated
                )
            except KeyError:
                pass  # pod deleted meanwhile
            return nominated
        return None

    # -- extension-point runners (thin wrappers over the module fns) ----
    def run_filter_plugins(
        self, state: CycleState, pod: Pod, node_infos: List[NodeInfo]
    ) -> Tuple[List[NodeInfo], Diagnosis]:
        return run_filter_plugins(self.filter_plugins, state, pod, node_infos)

    def run_pre_score_plugins(
        self, state: CycleState, pod: Pod, nodes: List[Any]
    ) -> Status:
        return run_pre_score_plugins(self.pre_score_plugins, state, pod, nodes)

    def run_score_plugins(
        self, state: CycleState, pod: Pod, node_names: List[str]
    ) -> Dict[str, int]:
        return run_score_plugins(
            self.score_plugins, self.score_weights, state, pod, node_names
        )

    def run_permit_plugins(
        self, state: CycleState, pod: Pod, node_name: str
    ) -> Status:
        """minisched.go:201-237: statuses Wait are pooled into one
        WaitingPod with per-plugin timeouts.

        The WaitingPod is registered BEFORE plugins run so a plugin that
        fires Allow during its own Permit call (NodeNumber with a 0-suffix
        node arms a zero-delay timer, nodenumber.go:112) cannot lose the
        signal — the race the reference has (see waitingpod.py docstring).
        """
        if not self.permit_plugins:
            # empty chain: nothing could ever Allow/Reject — skip the
            # WaitingPod registration (per-pod lock + allocation; a wave
            # commits thousands)
            return Status.success()
        wp = WaitingPod(pod)
        with self._waiting_lock:
            self._waiting_pods[pod.metadata.uid] = wp
        any_wait = False
        for pl in self.permit_plugins:
            status, timeout_s = pl.permit(state, pod, node_name)
            if status is None or status.is_success():
                continue
            if status.is_wait():
                any_wait = True
                wp.add_pending(pl.name(), timeout_s)
            else:
                with self._waiting_lock:
                    self._waiting_pods.pop(pod.metadata.uid, None)
                return status.with_plugin(status.plugin or pl.name())
        wp.seal()
        if not any_wait:
            with self._waiting_lock:
                self._waiting_pods.pop(pod.metadata.uid, None)
            return Status.success()
        return Status.wait()

    def run_reserve_plugins(
        self, state: CycleState, pod: Pod, node_name: str
    ) -> Status:
        """Upstream RunReservePlugins: first failure unreserves, in reverse,
        every plugin that already reserved (including the failing one)."""
        done: List[Any] = []
        for pl in self.reserve_plugins:
            done.append(pl)
            status = pl.reserve(state, pod, node_name)
            if status is not None and not status.is_success():
                for prev in reversed(done):
                    prev.unreserve(state, pod, node_name)
                return status.with_plugin(status.plugin or pl.name())
        return Status.success()

    def run_unreserve_plugins(
        self, state: CycleState, pod: Pod, node_name: str
    ) -> None:
        for pl in reversed(self.reserve_plugins):
            pl.unreserve(state, pod, node_name)

    def get_waiting_pod(self, uid: str) -> Optional[WaitingPod]:
        with self._waiting_lock:
            return self._waiting_pods.get(uid)

    # -- binding cycle (minisched.go:96-112,240-277) --------------------
    def wait_on_permit(self, pod: Pod) -> Status:
        wp = self.get_waiting_pod(pod.metadata.uid)
        if wp is None:
            return Status.success()
        try:
            return wp.get_signal()
        finally:
            with self._waiting_lock:
                self._waiting_pods.pop(pod.metadata.uid, None)

    def bind(self, pod: Pod, node_name: str) -> None:
        # expected_rv: the optimistic-concurrency precondition the device
        # wave path already stamps (_bind_batch) — bind only if the pod is
        # STILL at the version this cycle evaluated.  A Conflict rides the
        # normal error_func → requeue path, where the MODIFIED event's
        # queue.update has already refreshed the parked pod.  In an HA
        # plane this is also the cross-engine arbitration: two engines
        # racing one pod commit exactly one bind.
        self.client.pods().bind(
            Binding(
                pod.metadata.name,
                pod.metadata.namespace,
                node_name,
                expected_rv=pod.metadata.resource_version or None,
            )
        )

    def _bind_race_refresh(self, qpi: QueuedPodInfo) -> bool:
        """A bind lost a race (Conflict on ``expected_rv``, AlreadyBound
        from a peer engine).  The MODIFIED event that made our copy stale
        was delivered while the pod was IN-FLIGHT — invisible to
        queue.update (pop had discarded the uid) — so a re-parked qpi
        would carry the stale resource_version forever and every retry
        would conflict again (livelock).  Consult the informer cache,
        which DID apply that event: returns True when the pod left the
        schedulable population (bound by anyone / deleted / recreated) —
        drop it instead of requeueing; False when it is still pending —
        the queued copy was refreshed so the retry carries the current
        version."""
        try:
            cur = self.informer_factory.informer_for("Pod").get(
                qpi.pod.metadata.key
            )
        except Exception:
            return False  # no cache view: park as before, retry later
        if (
            cur is None
            or cur.metadata.uid != qpi.pod.metadata.uid
            or cur.spec.node_name
        ):
            return True
        qpi.pod_info.pod = cur
        return False

    @staticmethod
    def _is_bind_race(err: BaseException) -> bool:
        from minisched_tpu.controlplane.client import (
            AlreadyBound,
            OutOfCapacity,
        )
        from minisched_tpu.controlplane.store import Conflict

        # OutOfCapacity included: the pod itself may be stale too, and
        # the refresh costs one cache lookup
        return isinstance(err, (AlreadyBound, Conflict, OutOfCapacity))

    def _binding_cycle(
        self,
        qpi: QueuedPodInfo,
        pod: Pod,
        node_name: str,
        state: Optional[CycleState] = None,
    ) -> None:
        state = state if state is not None else CycleState()
        try:
            with self.metrics.timed("wait_on_permit"):
                status = self.wait_on_permit(pod)
            if not status.is_success():
                self.run_unreserve_plugins(state, pod, node_name)
                from minisched_tpu.plugins.coscheduling import (
                    is_gang_ttl_status,
                )

                if is_gang_ttl_status(status):
                    # gang TTL release: the member was FEASIBLE — its
                    # peers just never arrived.  No cluster event is
                    # coming to wake it from the unschedulableQ, so the
                    # assume lease is forgotten (capacity released) and
                    # the member requeues through the ACTIVE queue for a
                    # prompt retry; the queue's gang-adjacent pop order
                    # then serializes competing gangs instead of
                    # re-interleaving them (deadlock-freedom).
                    forget = getattr(self, "_forget", None)
                    if forget is not None:
                        forget(pod.metadata.uid)
                    from minisched_tpu.observability import counters

                    counters.inc("gang.ttl_requeued")
                    # requeue: a TTL-released member retries promptly,
                    # never quota-held behind its tenant's arrivals
                    self.queue.add(qpi.pod, requeue=True)
                    if self.on_decision:
                        self.on_decision(pod, None, status)
                    return
                self.error_func(qpi, status.as_error(), plugin=status.plugin)
                if self.on_decision:
                    self.on_decision(pod, None, status)
                return
            with self.metrics.timed("bind"):
                self.bind(pod, node_name)
            from minisched_tpu.observability import trace

            trace.span_pod(
                "bind", pod, node=node_name,
                wave=getattr(self, "_wave_seq", None),
            )
            self.queue.observe_bind(pod, node_name)
            if self.on_decision:
                self.on_decision(pod, node_name, Status.success())
        except Exception as err:
            self.run_unreserve_plugins(state, pod, node_name)
            from minisched_tpu.controlplane.client import OutOfCapacity

            if isinstance(err, OutOfCapacity) and "budget-mirror" in str(err):
                # refused by a non-home shard's capacity MIRROR
                # (DESIGN.md §31): the cross-shard budget view said no —
                # counted apart from local OutOfCapacity races because a
                # stale mirror rv is a sync-lag signal, not contention
                from minisched_tpu.observability import counters

                counters.inc("sched.bind_mirror_refusals")
            if self._is_bind_race(err) and self._bind_race_refresh(qpi):
                # bound elsewhere or gone: no longer schedulable work —
                # requeueing would retry (and re-conflict) forever.  A
                # device engine's assumption must still release (the
                # authoritative state owns the capacity now).
                forget = getattr(self, "_forget", None)
                if forget is not None:
                    forget(pod.metadata.uid)
                if self.on_decision:
                    self.on_decision(pod, None, Status.from_error(err))
                return
            from minisched_tpu.controlplane.store import StorageDegraded

            if isinstance(err, StorageDegraded):
                # degraded WAL (ENOSPC/EIO latch): park-and-retry, the
                # same path the device engine's wave takes — capacity
                # releases with the requeue, and the retry lands once
                # the store's recovery probe re-arms appends
                from minisched_tpu.observability import counters

                counters.inc("storage.degraded_parks")
            self.error_func(qpi, err)
            if self.on_decision:
                self.on_decision(pod, None, Status.from_error(err))
        finally:
            with self._bind_lock:
                self._bind_threads.discard(threading.current_thread())

    # -- failure path (minisched.go:283-298) ----------------------------
    def error_func(
        self, qpi: QueuedPodInfo, err: Optional[BaseException], plugin: str = ""
    ) -> None:
        if isinstance(err, FitError):
            qpi.unschedulable_plugins = set(err.diagnosis.unschedulable_plugins)
        elif plugin:
            qpi.unschedulable_plugins = {plugin}
        self.queue.add_unschedulable(qpi)


# ---------------------------------------------------------------------------
# wiring (minisched/initialize.go:35-78's New)
# ---------------------------------------------------------------------------


def new_scheduler(
    client: Client,
    informer_factory: SharedInformerFactory,
    time_scale: float = 1.0,
    queue_opts: Optional[dict] = None,
) -> Scheduler:
    """Default wiring: filter=[NodeUnschedulable],
    pre-score/score/permit=[NodeNumber] (initialize.go:44-66)."""
    from minisched_tpu.plugins.nodenumber import NodeNumber
    from minisched_tpu.plugins.nodeunschedulable import NodeUnschedulable

    node_number = NodeNumber(time_scale=time_scale)
    sched = Scheduler(
        client,
        informer_factory,
        filter_plugins=[NodeUnschedulable()],
        pre_score_plugins=[node_number],
        score_plugins=[node_number],
        permit_plugins=[node_number],
        queue_opts=queue_opts,
    )
    node_number.h = sched  # Scheduler implements the waitingpod Handle
    return sched
