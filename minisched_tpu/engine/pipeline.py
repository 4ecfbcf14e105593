"""Two-stage wave pipeline: host build overlapped with device evaluate.

A BUILD WORKER thread pops wave N+1 from the scheduling queue, snapshots,
and packs its tables while the loop thread blocks (GIL released) in wave
N's device call; a bounded handoff queue (depth 1) is the backpressure
between the stages, and the loop thread's commit/losers handling for wave
N overlaps the worker's build of wave N+2 the same way.

The build itself is ``build_wave``: one function, called by the worker
for a wave it builds ahead and by the loop thread
(``DeviceScheduler.schedule_wave``) for a batch the worker hands back.
Either way the result is a ``PreparedWave`` and the loop thread finishes
it in ``DeviceScheduler._finish_wave``.

Correctness: a wave built ahead has a snapshot that predates wave N's
commits, so its winners are RE-ARBITRATED on the loop thread against the
current capacity view before assume/commit
(DeviceScheduler._rearbitrate_winners — losers requeue and re-place
against a fresh snapshot), and the bind transaction's AlreadyBound /
Conflict / OutOfCapacity preconditions remain the store-side backstop,
unchanged.  Anything the worker cannot handle (encode overflow, an empty
roster, the cross-pod priority bypass, an injected build fault) is handed
back RAW: the loop thread owns the backlog, the retry and the park.

Mesh composition: the build's output is packed HOST buffers, so the same
pipeline drives the mesh-sharded evaluator unchanged — the shared table
builder pads capacities to the mesh-axis multiples and keeps the static
node columns device-resident sharded; the loop thread's device call
dispatches the sharded program (DeviceScheduler._eval_packed_wave, with
its own per-wave single-device fallback ladder).  Nothing in this module
is mesh-aware by design.
"""

from __future__ import annotations

import dataclasses
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


@dataclasses.dataclass(slots=True)
class PreparedWave:
    """One wave's build output: what ``build_wave`` hands the loop
    thread's finish (over the handoff queue when the worker built it)."""

    qpis: List[Any]
    #: the snapshot the tables encode — the losers' preemption base, and
    #: with ``agg_delta`` / ``assigned`` what the record_results recorder
    #: rebuilds device tables from
    node_infos: List[Any]
    agg_delta: Any
    assigned: Any = ()
    node_names: Any = ()
    node_static: Any = None
    node_agg: Any = None
    pod_table: Any = None
    extra: Any = None
    #: the cross-pod pods of the popped batch, for the loop's backlog
    constrained: Any = ()
    partial: bool = True
    #: the worker built this wave while an earlier one was still in
    #: flight: its snapshot predates commits the loop thread has made
    #: since, so the finish re-arbitrates its winners.  A wave built on
    #: the loop thread sees every commit and keeps them all.
    built_ahead: bool = False
    build_s: float = 0.0
    dirty_rows: int = 0
    #: the node-table build was skipped wholesale (idle-wave gate: nothing
    #: dirty, roster epoch unchanged, same assume-delta); the loop thread
    #: counts these per wave
    build_skipped: bool = False
    #: one id for this wave on every thread, assigned at pop: the build
    #: worker's spans, the loop's, and the trace ring's carry it
    wave_id: int = 0


def build_wave(sched: Any, qpis: List[Any], snapshot: Any) -> PreparedWave:
    """One wave's tables from the snapshot the caller took: packed node
    tables → pod table → constraints, all flat HOST buffers the
    evaluator unpacks inside its one jitted program.

    ``snapshot`` is ``DeviceScheduler._snapshot_for_tables``' five.  The
    cache's dirty set has ONE ordered consumer — the worker while it
    exists, else the loop thread — and only that consumer passes a
    tracked (dirty, epoch); anyone else passes ``DIRTY_UNTRACKED`` and
    the builder's aggregate base is left alone.  An encode overflow
    raises ValueError (the callers own the retry)."""
    from minisched_tpu.models.tables import build_pod_table

    node_infos, agg_delta, assumed_pods, dirty, epoch = snapshot
    prepared = PreparedWave(qpis, node_infos, agg_delta)
    pods_ = [q.pod for q in qpis]
    with sched.metrics.timed("wave_assigned_list"):
        nodes = [ni.node for ni in node_infos]  # name-sorted by snapshot
        # with a live index the build never walks the population; the
        # index-less build must see the assumed pods explicitly (the
        # snapshot carries them as a numeric delta, not in the NodeInfos)
        prepared.assigned = assigned = (
            ()
            if sched.constraint_index is not None
            else [p for ni in node_infos for p in ni.pods] + assumed_pods
        )
    pod_capacity = sched._wave_cap(len(pods_))
    # placed-gang aggregates for this wave's members (assume-cache folded
    # in), against the same snapshot the tables encode
    gang_view = sched._gang_view(pods_)
    with sched.metrics.timed("wave_build_tables"):
        prepared.node_static, prepared.node_agg, prepared.node_names = (
            sched._table_builder.build_packed(
                node_infos, agg_delta=agg_delta, dirty=dirty, epoch=epoch
            )
        )
        prepared.pod_table, _ = build_pod_table(
            pods_, capacity=pod_capacity, device=False, gang_view=gang_view
        )
    if sched._needs_extra:
        with sched.metrics.timed("wave_build_constraints"):
            prepared.extra = sched._build_constraints(
                pods_, nodes, assigned,
                roster=sched._table_builder.roster_epoch(),
                pod_capacity=pod_capacity,
                node_capacity=prepared.node_agg.capacity,
                scan_planes=False,  # wave mode never runs the scan
                device=False,
            )
    return prepared


class _BuildFallback(Exception):
    """Internal: the loop thread must take this batch (hand it back raw)."""


class WavePipeline:
    """The build worker + bounded handoff for one DeviceScheduler.

    Items on the handoff queue:

    * ``("wave", PreparedWave)`` — tables built, ready for the device.
    * ``("raw", qpis, partial, wave_id)`` — the worker could not build
      it; the loop thread runs ``schedule_wave`` over the original batch.
    * ``("empty",)`` — a pop window elapsed with nothing to do; the loop
      thread runs its idle path (lease expiry, backlog flush, gc).

    The worker is the ONLY queue popper, so
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
        # batches popped (the worker writes it) and batches the loop
        # thread took off the handoff (it writes that): one writer each,
        # so ``holds_batch`` needs no lock
        self._popped = 0
        self._taken = 0

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
            item = self._handoff.get(timeout=timeout)
        except _queue.Empty:
            return None
        if item[0] != "empty":
            self._taken += 1
        return item

    @property
    def holds_batch(self) -> bool:
        """The worker holds a popped batch the loop thread has not taken:
        set from the pop's return until the handoff's get, whether the
        batch is still building or built and waiting (with a handoff of
        depth 1 that can be two).  A bind then knows a wave is coming and
        closes the dispatch gate (DeviceScheduler._bind_batch)."""
        return self._popped != self._taken

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
            self._popped += 1
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
            # the constraint build, anything unforeseen: schedule_wave
            # owns the retry/park machinery for all of them
            counters.inc("wave_pipeline.build_fallback")
            return ("raw", qpis, partial, wave_id)

    def _build(self, qpis: List[Any]) -> PreparedWave:
        """The worker's front — what must not run here raises
        ``_BuildFallback`` — then ``build_wave`` on a tracked snapshot."""
        from minisched_tpu.engine.device_scheduler import _is_cross_pod

        sched = self._sched
        plain, constrained = qpis, []
        if sched._has_cross_pod:
            constrained = [q for q in qpis if _is_cross_pod(q.pod)]
            if constrained:
                plain = [q for q in qpis if not _is_cross_pod(q.pod)]
            # priority-inversion bypass (see _schedule_wave_inner): when
            # a deferred constrained pod outranks a plain pod about to
            # run, the backlog must flush FIRST — backlog flushing is
            # loop-thread work, so hand the batch back raw.  The backlog
            # read is a cross-thread peek; the GIL makes it safe and the
            # loop re-checks authoritatively in schedule_one.
            pool = list(sched._scan_backlog) + constrained
            if pool and plain:
                hi = max(q.pod.spec.priority for q in pool)
                if hi > min(q.pod.spec.priority for q in plain):
                    raise _BuildFallback()
        if not plain:
            raise _BuildFallback()  # all-constrained batch: backlog work
        # leases expire on the loop thread (store probes must not stall
        # the overlap window); the dirty-set drain is atomic with the
        # snapshot and this worker is its only consumer
        with sched.metrics.timed("wave_snapshot"):
            snapshot = sched._snapshot_for_tables(expire_leases=False)
        if not snapshot[0]:
            raise _BuildFallback()  # empty roster: the loop's error path
        prepared = build_wave(sched, plain, snapshot)
        prepared.constrained = constrained
        prepared.built_ahead = True
        prepared.dirty_rows = sched._table_builder.last_dirty_rows
        prepared.build_skipped = sched._table_builder.last_build_skipped
        return prepared
