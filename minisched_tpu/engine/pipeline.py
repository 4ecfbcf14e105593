"""Two-stage wave pipeline: host build overlapped with device evaluate.

BENCH_r05 showed the device kernel placing 70M pods/s while the full
chain landed at 10.4k: of a 7.9s wave loop, device evaluate was 4.0s and
the host-side phases (snapshot, pack tables, build constraints, commit,
gc) ran strictly serially around it — the TPU sat idle for most of every
wave.  This module overlaps them: a BUILD WORKER thread pops wave N+1
from the scheduling queue, snapshots, and packs its tables while the
loop thread blocks (GIL released) in wave N's device call; a bounded
handoff queue (depth 1) is the backpressure between the stages, and the
loop thread's commit/losers handling for wave N overlaps the worker's
build of wave N+2 the same way.

Correctness: wave N+1's snapshot predates wave N's commits, so its
winners are RE-ARBITRATED on the loop thread against the current
capacity view before assume/commit (DeviceScheduler._rearbitrate_winners
— losers requeue and re-place against a fresh snapshot), and the bind
transaction's AlreadyBound / Conflict / OutOfCapacity preconditions
remain the store-side backstop, unchanged.  Anything the build stage
cannot handle (encode overflow, an empty roster, the cross-pod priority
bypass, an injected build fault) is handed back RAW and takes the exact
serial wave path.

Mesh composition (ISSUE 7): the build stage's output is packed HOST
buffers, so the same pipeline drives the mesh-sharded evaluator
unchanged — the shared table builder pads capacities to the mesh-axis
multiples and keeps the static node columns device-resident sharded;
the loop thread's device call dispatches the sharded program
(DeviceScheduler._eval_packed_wave, with its own per-wave single-device
fallback ladder).  Nothing in this module is mesh-aware by design.

``MINISCHED_PIPELINE=0`` disables the whole stage — the engine then runs
the untouched serial loop (DeviceScheduler._schedule_one_serial).
"""

from __future__ import annotations

import queue as _queue
import threading
from typing import Any, List, Optional

from minisched_tpu.observability import profiling

profiling.register_phases(
    "pipeline_pop",
    "wave_pipeline_build",
    "wave_snapshot",
    "wave_assigned_list",
    "wave_build_tables",
    "wave_build_constraints",
)


class PreparedWave:
    """One wave's build-stage output, handed loop-ward over the queue."""

    __slots__ = (
        "qpis",
        "constrained",
        "partial",
        "node_infos",
        "node_names",
        "node_static",
        "node_agg",
        "pod_table",
        "extra",
        "build_s",
        "dirty_rows",
        "build_skipped",
        "wave_id",
    )

    def __init__(self) -> None:
        self.qpis: List[Any] = []
        self.constrained: List[Any] = []
        self.partial = True
        self.node_infos: List[Any] = []
        self.node_names: List[str] = []
        self.node_static: Any = None
        self.node_agg: Any = None
        self.pod_table: Any = None
        self.extra: Any = None
        self.build_s = 0.0
        self.dirty_rows = 0
        #: the node-table build was skipped wholesale (idle-wave gate:
        #: nothing dirty, roster epoch unchanged, same assume-delta —
        #: ISSUE 8); the loop thread counts these per wave
        self.build_skipped = False
        #: one id for this wave on every thread, assigned at pop: the
        #: build worker's spans, the loop's, and the trace ring's carry it
        self.wave_id = 0


class _BuildFallback(Exception):
    """Internal: this batch must take the serial wave path."""


class WavePipeline:
    """The build worker + bounded handoff for one DeviceScheduler.

    Items on the handoff queue:

    * ``("wave", PreparedWave)`` — tables built, ready for the device.
    * ``("raw", qpis, partial, wave_id)`` — build-stage fallback; the loop
      thread runs the serial ``schedule_wave`` over the original batch.
    * ``("empty",)`` — a pop window elapsed with nothing to do; the loop
      thread runs its idle path (lease expiry, backlog flush, gc).

    The worker is the ONLY queue popper while the pipeline is active, so
    pop order (priority/FIFO) is preserved; the handoff depth of 1 means
    at most two waves' pods are ever outside the queues (one on device,
    one built/building), and ``drain()`` hands any stranded ones back to
    the loop thread at shutdown.
    """

    def __init__(self, sched: Any, depth: int = 1, pop_timeout: float = 0.5):
        self._sched = sched
        self._handoff: _queue.Queue = _queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._pop_timeout = pop_timeout
        self._thread: Optional[threading.Thread] = None
        #: qpis popped but never handed over (stop raced the put) — the
        #: loop thread's shutdown drain parks them through error_func
        self._leftover: List[Any] = []

    # -- loop-thread surface -----------------------------------------------
    def start(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            return
        self._thread = threading.Thread(
            target=self._run, name="wave-build", daemon=True
        )
        self._thread.start()

    def get(self, timeout: Optional[float] = None):
        """Next item, or None on timeout (the worker emits at least one
        item per pop window, so None means it is stopping or wedged)."""
        try:
            return self._handoff.get(timeout=timeout)
        except _queue.Empty:
            return None

    def stop(self, join_timeout: float = 2.0) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=join_timeout)

    def drain(self) -> List[Any]:
        """Popped-but-unscheduled qpis after stop() — cross-pod deferrals
        included; the caller parks them so no pod is silently lost."""
        out = list(self._leftover)
        self._leftover = []
        while True:
            try:
                item = self._handoff.get_nowait()
            except _queue.Empty:
                return out
            if item[0] == "wave":
                out.extend(item[1].qpis)
                out.extend(item[1].constrained)
            elif item[0] == "raw":
                out.extend(item[1])

    # -- worker ------------------------------------------------------------
    def _put(self, item) -> bool:
        while not self._stop.is_set():
            try:
                self._handoff.put(item, timeout=0.2)
                return True
            except _queue.Full:
                continue
        return False

    def _strand(self, item) -> None:
        if item[0] == "wave":
            self._leftover.extend(item[1].qpis)
            self._leftover.extend(item[1].constrained)
        elif item[0] == "raw":
            self._leftover.extend(item[1])

    def _run(self) -> None:
        sched = self._sched
        while not self._stop.is_set():
            try:
                with sched.metrics.timed("pipeline_pop"):
                    qpis = sched.queue.pop_batch(
                        sched.max_wave, timeout=self._pop_timeout
                    )
            except Exception:
                # a closing queue mid-shutdown must not kill the worker
                # before stop() is observed
                if self._stop.is_set():
                    return
                qpis = None
            if self._stop.is_set():
                self._leftover.extend(qpis or ())
                return
            if not qpis:
                self._put(("empty",))
                continue
            item = self._build_item(
                qpis, len(qpis) < sched.max_wave, sched._next_wave_id()
            )
            if not self._put(item):
                self._strand(item)
                return

    def _build_item(self, qpis: List[Any], partial: bool, wave_id: int):
        from minisched_tpu.observability import counters

        try:
            with self._sched.metrics.timed(
                "wave_pipeline_build", wave=wave_id, n=len(qpis)
            ) as sp:
                prepared = self._build(qpis)
            prepared.partial = partial
            prepared.wave_id = wave_id
            prepared.build_s = sp.wall_s
            return ("wave", prepared)
        except _BuildFallback:
            return ("raw", qpis, partial, wave_id)
        except Exception:
            # encode overflow (ValueError), an injected store fault in
            # the constraint build, anything unforeseen: the serial path
            # owns the retry/park machinery for all of them
            counters.inc("wave_pipeline.build_fallback")
            return ("raw", qpis, partial, wave_id)

    def _build(self, qpis: List[Any]) -> PreparedWave:
        from minisched_tpu.engine.device_scheduler import _is_cross_pod
        from minisched_tpu.models.tables import build_pod_table

        sched = self._sched
        prepared = PreparedWave()
        prepared.qpis = qpis
        if sched._has_cross_pod:
            constrained = [q for q in qpis if _is_cross_pod(q.pod)]
            if constrained:
                prepared.constrained = constrained
                prepared.qpis = [
                    q for q in qpis if not _is_cross_pod(q.pod)
                ]
            # priority-inversion bypass (see _schedule_wave_inner): when
            # a deferred constrained pod outranks a plain pod about to
            # run, the backlog must flush FIRST — backlog flushing is
            # loop-thread work, so hand the batch back raw.  The backlog
            # read is a cross-thread peek; the GIL makes it safe and the
            # loop re-checks authoritatively on the serial path.
            pool = list(sched._scan_backlog) + prepared.constrained
            if pool and prepared.qpis:
                hi = max(q.pod.spec.priority for q in pool)
                if hi > min(q.pod.spec.priority for q in prepared.qpis):
                    raise _BuildFallback()
        if not prepared.qpis:
            raise _BuildFallback()  # all-constrained batch: serial path
        pods_ = [q.pod for q in prepared.qpis]
        # leases expire on the loop thread (store probes must not stall
        # the overlap window); the dirty-set drain is atomic with the
        # snapshot and this worker is the only wave-path snapshotter
        with sched.metrics.timed("wave_snapshot"):
            node_infos, agg_delta, assumed_pods, dirty, epoch = (
                sched._snapshot_for_tables(expire_leases=False)
            )
        if not node_infos:
            raise _BuildFallback()  # empty roster: serial error path
        prepared.node_infos = node_infos
        nodes = [ni.node for ni in node_infos]
        with sched.metrics.timed("wave_assigned_list"):
            assigned = (
                ()
                if sched.constraint_index is not None
                else [p for ni in node_infos for p in ni.pods]
                + assumed_pods
            )
        pod_capacity = sched._wave_cap(len(pods_))
        # placed-gang aggregates for this wave's members (assume-cache
        # folded in): computed on the worker against the same snapshot
        # the tables encode; the loop thread's re-arbitration handles
        # anything the overlapped wave commits after this
        gang_view = sched._gang_view(pods_)
        with sched.metrics.timed("wave_build_tables"):
            node_static, node_agg, node_names = (
                sched._table_builder.build_packed(
                    node_infos, agg_delta=agg_delta, dirty=dirty,
                    epoch=epoch,
                )
            )
            prepared.dirty_rows = sched._table_builder.last_dirty_rows
            prepared.build_skipped = (
                sched._table_builder.last_build_skipped
            )
            pod_table, _ = build_pod_table(
                pods_, capacity=pod_capacity, device=False,
                gang_view=gang_view,
            )
        prepared.node_static = node_static
        prepared.node_agg = node_agg
        prepared.node_names = node_names
        prepared.pod_table = pod_table
        if sched._needs_extra:
            with sched.metrics.timed("wave_build_constraints"):
                prepared.extra = sched._build_constraints(
                    pods_, nodes, assigned,
                    pod_capacity=pod_capacity,
                    node_capacity=node_agg.capacity,
                    scan_planes=False,  # wave mode never runs the scan
                    device=False,
                )
        return prepared
