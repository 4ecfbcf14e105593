"""Client facade over the control plane — the client-go surface.

Mirrors exactly the clientset calls the reference makes: ``Nodes().Create /
List`` (sched.go:84,121; minisched/minisched.go:40), ``Pods().Create / Get /
Update`` (sched.go:91,111; resultstore store.go:120-128) and the binding
subresource ``Pods().Bind`` (minisched/minisched.go:267-273), plus the
client-side QPS/Burst rate limiter the reference configures at 5000/5000
(k8sapiserver.go:57-62) — off by default, enabled per client.
"""

from __future__ import annotations

import queue
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from minisched_tpu.api.objects import (
    Binding,
    Event,
    Node,
    ObjectMeta,
    Pod,
    PodStatus,
)
from minisched_tpu.controlplane.store import (
    Conflict,
    ObjectStore,
    StorageDegraded,
)
from minisched_tpu.observability import counters, profiling

profiling.register_spans("events.write")

#: what the event writer counts (observability/counters), registered at 0
#: when a recorder is given a store
_EVENT_COUNTERS = ("events.written", "events.trimmed", "events.batches")

#: the reference's client limits (k8sapiserver.go:60-61)
DEFAULT_QPS = 5000.0
DEFAULT_BURST = 5000


class TokenBucket:
    """client-go flowcontrol-style token bucket: ``burst`` capacity
    refilled at ``qps`` tokens/sec; ``acquire`` blocks until a token is
    available."""

    def __init__(self, qps: float, burst: int):
        if qps <= 0:
            raise ValueError(f"qps must be positive, got {qps}")
        self._qps = float(qps)
        # a bucket that can never hold one whole token would block every
        # acquire forever — clamp like client-go's flowcontrol does
        self._burst = float(max(burst, 1))
        self._tokens = self._burst
        self._last = time.monotonic()
        self._lock = threading.Lock()

    def acquire(self) -> None:
        while True:
            with self._lock:
                now = time.monotonic()
                self._tokens = min(
                    self._burst, self._tokens + (now - self._last) * self._qps
                )
                self._last = now
                if self._tokens >= 1.0:
                    self._tokens -= 1.0
                    return
                wait = (1.0 - self._tokens) / self._qps
            time.sleep(wait)


class _ThrottledStore:
    """Store proxy acquiring one rate-limit token per API operation (the
    client-go rate limiter gates every request; watch STREAMS pay one
    token at subscription, not per event — matching client-go, where the
    limiter covers requests, not watch deliveries)."""

    _THROTTLED = frozenset(
        # mutate_many / create_many / delete_many are ONE API request
        # each (batch bind / batch create / batch delete), so one token
        ("create", "create_many", "get", "list", "list_with_rv", "update",
         "delete", "delete_many", "mutate", "mutate_many", "watch")
    )

    def __init__(self, store: ObjectStore, limiter: TokenBucket):
        object.__setattr__(self, "_store", store)
        object.__setattr__(self, "_limiter", limiter)

    def __getattr__(self, name: str) -> Any:
        attr = getattr(self._store, name)
        if name in self._THROTTLED:
            limiter = self._limiter

            def gated(*args: Any, **kwargs: Any) -> Any:
                limiter.acquire()
                return attr(*args, **kwargs)

            return gated
        return attr

    def __setattr__(self, name: str, value: Any) -> None:
        setattr(self._store, name, value)

KIND_POD = "Pod"
KIND_NODE = "Node"
KIND_EVENT = "Event"
KIND_PV = "PersistentVolume"
KIND_PVC = "PersistentVolumeClaim"


class AlreadyBound(Exception):
    pass


class OutOfCapacity(Exception):
    """Commit-time node-capacity rejection on the bind subresource.

    With ONE engine the scheduler's assume cache makes over-commit
    impossible; with N active-active engines (the HA plane) each engine
    evaluates against its own informer snapshot, and two engines can pick
    the same node for different pods before either bind's event
    propagates — the pod-level ``expected_rv``/unset-node_name guards
    arbitrate the POD, but nothing arbitrated the NODE.  Kubernetes
    leaves that to kubelet admission; this control plane has no kubelet,
    so the bind TRANSACTION is the backstop (Omega-style commit-time
    validation): a bind that would push the node past its allocatable
    CPU / memory / pod count is rejected per-item, and the losing engine
    requeues the pod against refreshed state."""


def _raise_first_error(results: List[Any]) -> List[Any]:
    """The shared batch-create contract of BOTH facades: each item is
    independent — the store creates every non-conflicting item and
    returns per-item results; the facade re-raises the FIRST error
    (conflicts come back as KeyError), with failed slots left as None.
    A non-KeyError (injected fault, closed store) raises immediately —
    the single-create path would have surfaced it too."""
    out: List[Any] = []
    first_err: Optional[KeyError] = None
    for res in results:
        if isinstance(res, KeyError):
            out.append(None)
            if first_err is None:
                first_err = res
        elif isinstance(res, BaseException):
            raise res
        else:
            out.append(res)
    if first_err is not None:
        raise first_err
    return out


class _NodeAPI:
    def __init__(self, store: ObjectStore):
        self._store = store

    def create(self, node: Node) -> Node:
        # nodes are cluster-scoped: normalize away ObjectMeta's "default"
        # namespace so get/delete (which use "") always find them
        node.metadata.namespace = ""
        return self._store.create(KIND_NODE, node)

    def create_many(
        self, nodes: List[Node], return_objects: bool = True
    ) -> List[Node]:
        """Batch create, aligned with ``nodes`` — ONE store transaction
        (one lock hold, one fanout; the remote facade's analog is one
        collection POST).  Partial-failure semantics MATCH the remote
        facade: every non-conflicting item is created, then the first
        per-item KeyError raises.  ``return_objects=False`` skips the
        per-item clone (seed paths that drop the results)."""
        for n in nodes:
            n.metadata.namespace = ""
        return _raise_first_error(
            self._store.create_many(KIND_NODE, nodes, return_objects)
        )

    def get(self, name: str) -> Node:
        return self._store.get(KIND_NODE, "", name)

    def list(self) -> List[Node]:
        return self._store.list(KIND_NODE)

    def update(self, node: Node) -> Node:
        return self._store.update(KIND_NODE, node)

    def delete(self, name: str) -> None:
        self._store.delete(KIND_NODE, "", name)


class _PodAPI:
    def __init__(self, store: ObjectStore, namespace: str = "default"):
        self._store = store
        self._ns = namespace

    def create(self, pod: Pod) -> Pod:
        if not pod.metadata.namespace:
            pod.metadata.namespace = self._ns
        return self._store.create(KIND_POD, pod)

    def create_many(
        self, pods: List[Pod], return_objects: bool = True
    ) -> List[Pod]:
        """Batch create, aligned with ``pods`` — see _NodeAPI.create_many
        (all independent items, first KeyError raised at the end)."""
        for p in pods:
            if not p.metadata.namespace:
                p.metadata.namespace = self._ns
        return _raise_first_error(
            self._store.create_many(KIND_POD, pods, return_objects)
        )

    def get(self, name: str, namespace: Optional[str] = None) -> Pod:
        return self._store.get(KIND_POD, namespace or self._ns, name)

    def list(self) -> List[Pod]:
        return self._store.list(KIND_POD)

    def update(self, pod: Pod) -> Pod:
        return self._store.update(KIND_POD, pod)

    def delete(self, name: str, namespace: Optional[str] = None) -> None:
        self._store.delete(KIND_POD, namespace or self._ns, name)

    def mutate(self, name: str, fn, namespace: Optional[str] = None) -> Pod:
        """Atomic read-modify-write under the store lock — the safe form of
        get→clone→update for concurrent writers (e.g. the resultstore's
        annotation flush racing the binding goroutine)."""
        return self._store.mutate(KIND_POD, namespace or self._ns, name, fn)

    def bind(self, binding: Binding) -> Pod:
        """The binding subresource: sets spec.nodeName exactly once.

        The real apiserver rejects a second bind; preserving that guard is
        what makes wave-scheduling conflict detection observable.
        """
        [res] = self.bind_many([binding])
        if isinstance(res, BaseException):
            raise res
        return res

    @staticmethod
    def _node_budgets(store: ObjectStore, targets: set) -> Dict[str, list]:
        """Remaining [milli_cpu, memory, pods] per TARGET node, computed
        from the store's live state — the caller holds the store lock,
        so the view is the exact state the transaction commits against.
        Nodes absent from the store get no budget (and no check): unit
        scenarios bind to names that were never created, matching the
        reference apiserver, which validates neither.

        Reads the store's INCREMENTAL per-node aggregates
        (``_pod_node_agg``, maintained on every Pod commit) — O(target
        nodes) per batch; the full pod-population scan this replaces was
        the last O(all pods) term in the bind path (ROADMAP crumb).  A
        store without the index (foreign test double) falls back to the
        scan.

        Sharded stores (DESIGN.md §31) carry a ``_shard_budget_view``:
        a NON-home group — whose store holds no Node objects at all —
        answers from the rv-stamped budget MIRROR (home allocatable
        minus every OTHER vantage's usage; this group's own share is
        the live local agg, subtracted below under this very lock
        hold), and those entries keep the mirror rv as a 4th element so
        the refusal can carry its staleness watermark.  The HOME group
        additionally debits the board's reported non-home usage from
        its locally-present Nodes."""
        budgets: Dict[str, list] = {}
        view = getattr(store, "_shard_budget_view", None)
        mirrored: set = set()
        for name in targets:
            node = store._objects.get(KIND_NODE, {}).get(f"/{name}")
            if node is None:
                if view is None:
                    continue
                from minisched_tpu.observability import counters

                counters.inc("shard.budget.mirror_checks")
                ent = view.budget(name)
                if ent is None:
                    counters.inc("shard.budget.unknown_node")
                    continue
                alloc, elsewhere, rv = ent
                budgets[name] = [
                    alloc[0] - elsewhere[0],
                    alloc[1] - elsewhere[1],
                    alloc[2] - elsewhere[2],
                    rv,
                ]
                mirrored.add(name)
                continue
            alloc = node.status.allocatable
            budgets[name] = [alloc.milli_cpu, alloc.memory, alloc.pods]
            if view is not None:
                extra = view.extra_used(name)
                if extra is not None:
                    b = budgets[name]
                    b[0] -= extra[0]
                    b[1] -= extra[1]
                    b[2] -= extra[2]
        if not budgets:
            return budgets
        agg = getattr(store, "_pod_node_agg", None)
        if agg is None:
            for pod in store._objects.get(KIND_POD, {}).values():
                b = budgets.get(pod.spec.node_name)
                if b is not None:
                    req = pod.resource_requests()
                    b[0] -= req.milli_cpu
                    b[1] -= req.memory
                    b[2] -= req.pods
            return budgets
        for name, b in budgets.items():
            a = agg.get(name)
            if a is not None:
                b[0] -= a[0]
                b[1] -= a[1]
                b[2] -= a[2]
        return budgets

    def bind_many(
        self, bindings: List[Binding], return_objects: bool = True
    ) -> List[Any]:
        """Batch form of the binding subresource: a wave's placements in
        one store transaction (the reference binds one pod per cycle,
        minisched.go:267-273 — a TPU wave commits thousands).  Returns a
        list aligned with ``bindings``: the bound Pod (None with
        ``return_objects=False`` — skips a clone per bind), or the
        exception (AlreadyBound, missing-pod KeyError, stale-rv Conflict,
        OutOfCapacity) for that entry.

        The budgets and the commits share ONE lock hold: the per-node
        capacity budgets are computed from exactly the state the commits
        apply against (mutate_many's ``prepare`` hook runs under the
        store lock, immediately before the item loop), and each
        successful bind debits them — so concurrent binders (N HA
        engines racing the same node) serialize through the lock and the
        LATER transaction sees the earlier one's placements (see
        OutOfCapacity).  The hook — not an outer ``locked()`` wrap — is
        load-bearing: the group-commit durable store parks the caller on
        a commit barrier AFTER releasing the lock, and a binder that
        still held it would deadlock the group leader (and every other
        mutator) behind its own wait."""

        def apply_for(binding: Binding, budgets: Dict[str, list]):
            def apply(pod: Pod) -> Pod:
                # clone_for_write=False contract: ``pod`` is the STORED
                # object — build a new one, never mutate it.  A bind only
                # changes spec.node_name/status, so everything else
                # (containers, volumes, affinity, labels...) is shared
                # structurally; deep-cloning 16k pod specs per wave was
                # ~0.5s of the bind wall, and copy.copy's __reduce_ex__
                # protocol costs nearly as much — raw __dict__ copies are
                # ~10× cheaper.  Fresh metadata: the store restamps
                # resource_version on it.
                spec = pod.spec
                if spec.node_name:
                    # checked BEFORE the rv precondition: a retried bind
                    # whose first attempt landed must surface as
                    # AlreadyBound-to-our-node (the idempotency signal the
                    # remote dedup converts to success), not as a Conflict
                    # from the rv bump our own commit caused
                    raise AlreadyBound(
                        f"pod {pod.metadata.key} already bound to "
                        f"{spec.node_name}"
                    )
                if (
                    binding.expected_rv is not None
                    and pod.metadata.resource_version != binding.expected_rv
                ):
                    raise Conflict(
                        f"stale resource_version for Pod {pod.metadata.key}: "
                        f"expected {binding.expected_rv}, have "
                        f"{pod.metadata.resource_version}"
                    )
                budget = budgets.get(binding.node_name)
                if budget is not None:
                    req = pod.resource_requests()
                    if (
                        req.milli_cpu > budget[0]
                        or req.memory > budget[1]
                        or req.pods > budget[2]
                    ):
                        # length-4 budgets came from the cross-shard
                        # mirror (see _node_budgets): the refusal
                        # carries the mirror rv so a consumer can judge
                        # how stale the verdict was
                        mirror = ""
                        if len(budget) > 3:
                            mirror = f", budget-mirror rv={budget[3]}"
                            from minisched_tpu.observability import (
                                counters,
                            )

                            counters.inc("shard.budget.refused")
                        raise OutOfCapacity(
                            f"node {binding.node_name} out of capacity for "
                            f"pod {pod.metadata.key} (remaining "
                            f"cpu={budget[0]}m mem={budget[1]} "
                            f"pods={budget[2]}{mirror})"
                        )
                    budget[0] -= req.milli_cpu
                    budget[1] -= req.memory
                    budget[2] -= req.pods
                new_spec = object.__new__(type(spec))
                new_spec.__dict__.update(spec.__dict__)
                new_spec.node_name = binding.node_name
                new = object.__new__(type(pod))
                new.metadata = pod.metadata.clone()
                new.spec = new_spec
                new.status = PodStatus(phase="Running")
                return new

            return apply

        # The rate-limit token (one per batch, matching _ThrottledStore)
        # is taken BEFORE the transaction — TokenBucket.acquire can
        # sleep, and sleeping while holding the store lock would stall
        # every other client, informer fanout, and lease heartbeat
        # behind this binder's throttle.  Everything runs against the
        # RAW store.  Stores without a lock surface (no in-process
        # transaction view — never the case for the facades this client
        # fronts) skip the capacity gate rather than fake it.
        limiter = getattr(self._store, "_limiter", None)
        if limiter is not None:
            limiter.acquire()
        raw = getattr(self._store, "_store", self._store)
        locked = getattr(raw, "locked", None)
        # budgets fill in under the lock (prepare), and the apply
        # closures — which also run under that same hold — read them
        budgets: Dict[str, list] = {}
        items = [
            (b.pod_namespace, b.pod_name, apply_for(b, budgets))
            for b in bindings
        ]
        if not callable(locked):
            return raw.mutate_many(
                KIND_POD,
                items,
                return_objects=return_objects,
                clone_for_write=False,
            )

        def prepare(store) -> None:
            budgets.update(
                self._node_budgets(store, {b.node_name for b in bindings})
            )

        return raw.mutate_many(
            KIND_POD,
            items,
            return_objects=return_objects,
            clone_for_write=False,
            prepare=prepare,
        )


class Client:
    """clientset.Interface equivalent.

    ``qps``/``burst`` enable the client-side rate limiter (the reference
    sets QPS/Burst 5000, k8sapiserver.go:57-62 — use DEFAULT_QPS /
    DEFAULT_BURST for that); None (default) = unlimited.
    """

    def __init__(
        self,
        store: Optional[ObjectStore] = None,
        qps: Optional[float] = None,
        burst: Optional[int] = None,
    ):
        raw = store or ObjectStore()
        if qps:
            self.rate_limiter: Optional[TokenBucket] = TokenBucket(
                qps, burst if burst is not None else int(qps)
            )
            self.store = _ThrottledStore(raw, self.rate_limiter)
        else:
            self.rate_limiter = None
            self.store = raw

    def nodes(self) -> _NodeAPI:
        return _NodeAPI(self.store)

    def pods(self, namespace: str = "default") -> _PodAPI:
        return _PodAPI(self.store, namespace)


class EventRecorder:
    """Events broadcaster (scheduler/scheduler.go:55-59): records scheduler
    lifecycle + per-decision events.

    With a ``store``, each event is written as a real ``Event`` API object
    (the reference's ``events.NewBroadcaster(&events.EventSinkImpl{...})``
    records ``eventsv1`` objects a client can list) — list/watch-able over
    the store and the REST façade; the kind is volatile (no WAL).  Writes
    happen on a dedicated writer thread, like upstream's broadcaster
    goroutines: ``eventf`` on the scheduling hot path only enqueues the
    decision's plain fields (a device wave emits thousands of decisions —
    synchronous store writes there would eat the batched-bind win).

    The writer lands what has piled up as ONE store transaction: it
    blocks for the first decision, takes everything else that is queued
    with it (no size knob, no timer: 1 in a scenario, a score while it
    keeps pace with a wave's commit loop, hundreds to thousands once
    it falls behind), builds the ``Event`` objects and writes them
    with one ``create_many`` — one lock hold, one fanout, one swap of
    the store's read plane a batch, where a ``create`` and a ``delete``
    an event each copied the kind's whole map.  ``flush()`` waits until
    everything enqueued before the call is in the store (call before
    asserting/reading in tests or shutdown).

    ``max_events`` bounds growth on BOTH sides (kube events expire by
    TTL; a 100k-pod run would otherwise accrete 100k objects): the
    in-process ``events`` deque drops its oldest dicts, and the oldest
    Event objects are deleted from the store, one ``delete_many`` BEFORE
    a batch's create, so the store never shows more than the cap (a
    batch longer than the cap is written in pieces of at most the cap).
    """

    def __init__(self, store: Any = None, max_events: int = 2048) -> None:
        from collections import deque

        self._events: Any = deque(maxlen=max_events)
        self._store = store
        self._max_events = max(int(max_events), 1)
        self._mu = threading.Lock()  # the record, the numbering, the enqueue
        self._seq = 0  # decisions enqueued for the store so far
        self._closing = store is None  # eventf enqueues nothing from now on
        self._writer = None
        if store is not None:
            for name in _EVENT_COUNTERS:
                counters.inc(name, 0)  # on /metrics from now on
            self._live: Any = deque()  # (namespace, name) in emit order
            # a C queue: put takes no lock the writer ever holds, so no
            # emitter waits for the writer
            self._q: Any = queue.SimpleQueue()
            self._landed = 0  # the last decision the writer is done with
            self._progress = threading.Condition()  # flush() waits on it
            self._writer = threading.Thread(
                target=self._drain, name="event-writer", daemon=True
            )
            self._writer.start()

    @property
    def events(self) -> list:
        """Snapshot of the in-process event dicts.  A list COPY under the
        lock: the engine thread appends while observers iterate, and at
        maxlen every deque append also pops the left end — iterating the
        live deque raises 'deque mutated during iteration'."""
        with self._mu:
            return list(self._events)

    def eventf(self, obj: Any, event_type: str, reason: str, message: str) -> None:
        meta = getattr(obj, "metadata", None)
        if meta is not None:
            regarding = getattr(meta, "key", "")
            subject = getattr(meta, "name", "")
            namespace = getattr(meta, "namespace", "") or "default"
        else:
            regarding, subject, namespace = "", "", "default"
        record = {
            "object": regarding or str(obj),
            "type": event_type,
            "reason": reason,
            "message": message,
        }
        # ONE lock hold a decision, and plain fields: the Event and its
        # ObjectMeta are the writer's to build, a batch at a time
        with self._mu:
            self._events.append(record)
            if self._closing:  # no store, or closed: the dict alone
                return
            self._seq += 1
            self._q.put(
                (self._seq, subject, namespace, event_type, reason,
                 message, regarding)
            )

    def _drain(self) -> None:
        q, cap = self._q, self._max_events
        while True:
            # block for the first decision, then take whatever else has
            # queued without waiting: the batch is what piled up
            batch = [q.get()]
            try:
                while True:
                    batch.append(q.get_nowait())
            except queue.Empty:
                pass
            closing = batch[-1] is None  # close()'s sentinel: always last
            if closing:
                batch.pop()
            for i in range(0, len(batch), cap):
                piece = batch[i:i + cap]
                try:
                    self._write(piece)
                except Exception as err:  # noqa: BLE001 — the writer never dies
                    print(
                        f"[events] a batch of {len(piece)} was lost: {err!r}",
                        file=sys.stderr, flush=True,
                    )
                with self._progress:
                    self._landed = piece[-1][0]
                    self._progress.notify_all()
            if closing:
                return

    def _write(self, batch: List[tuple]) -> None:
        """One batch (at most ``max_events`` decisions) as one store
        transaction: trim, then create.  An item the store refuses is
        that item's loss alone."""
        with profiling.span("events.write", n=len(batch)):
            events = [
                Event(
                    metadata=ObjectMeta(
                        name=f"{subject or 'scheduler'}.{seq:x}",
                        namespace=namespace,
                    ),
                    type=event_type,
                    reason=reason,
                    message=message,
                    regarding=regarding,
                )
                for seq, subject, namespace, event_type, reason, message,
                regarding in batch
            ]
            live = self._live
            over = len(live) + len(events) - self._max_events  # <= len(live)
            if over > 0:
                counters.inc("events.trimmed", self._trim(over))
            try:
                results = self._store.create_many(
                    KIND_EVENT, events, return_objects=False
                )
            except Exception as err:  # noqa: BLE001 — full/closed store
                results = [err] * len(events)
            written = 0
            degraded = 0
            for evt, res in zip(events, results):
                if not isinstance(res, BaseException):
                    written += 1
                    live.append((evt.metadata.namespace, evt.metadata.name))
                elif isinstance(res, StorageDegraded):
                    degraded += 1
            counters.inc("events.batches")
            counters.inc("events.written", written)
            if degraded:
                # an event shed to a degraded DISK is counted so an ENOSPC
                # episode shows up in the recovery ledger, not as silence
                counters.inc("storage.event_dropped_degraded", degraded)

    def _trim(self, n: int) -> int:
        """Delete the ``n`` oldest Event objects with one ``delete_many``;
        returns how many went.  One already gone (store swapped/cleared)
        is forgotten; one the store would not delete now stays first in
        line for the next batch's trim."""
        live = self._live
        drop = [live.popleft() for _ in range(n)]
        try:
            results = self._store.delete_many(KIND_EVENT, drop)
        except Exception as err:  # noqa: BLE001 — full/closed store
            results = [err] * len(drop)
        kept = [
            key for key, res in zip(drop, results)
            if isinstance(res, BaseException) and not isinstance(res, KeyError)
        ]
        live.extendleft(reversed(kept))
        return results.count(None)

    def flush(self, timeout: float = 5.0) -> None:
        """Block until every event enqueued before the call has been
        written (bounded)."""
        if self._store is None:
            return
        deadline = time.monotonic() + timeout
        target = self._seq
        with self._progress:
            while self._landed < target:
                left = deadline - time.monotonic()
                if left <= 0:
                    return
                self._progress.wait(left)

    def close(self, timeout: float = 5.0) -> None:
        """Drain and terminate the writer thread.  Idempotent; eventf
        after close still records the in-process dict but its store write
        is silently dropped (the writer is gone) — callers close only on
        service teardown."""
        writer = self._writer
        if writer is None:
            return
        with self._mu:
            self._closing = True
            self._q.put(None)  # behind everything enqueued: the writer ends there
        writer.join(timeout=timeout)
        self._writer = None
