"""In-memory, versioned object store with watch semantics.

This is the fast-path replacement for the reference's L1/L0 stack — the
in-process kube-apiserver backed by etcd (k8sapiserver/k8sapiserver.go:43-71,
storage wiring :93-105) — per SURVEY.md §7 stage 2.  The public surface is
deliberately shaped like a storage backend boundary so an etcd/gRPC-backed
implementation can drop in behind the same interface later.

Semantics preserved from the reference stack:

* every mutation bumps a global, monotonically-increasing resource version
  (etcd revision analog);
* watchers receive ADDED / MODIFIED / DELETED events in mutation order
  (the apiserver→informer watch stream, SURVEY.md §3.3);
* reads return deep copies — mutating a returned object never changes the
  store (client-go returns decoded copies off the wire).

Thread-safety: one RLock guards the maps, and events are *enqueued* to
watchers while that lock is held so the per-watch queue order always equals
mutation order; delivery to consumers is decoupled through those unbounded
per-watcher queues, so a slow consumer still cannot stall a mutator
(client-go's watch buffering).
"""

from __future__ import annotations

import enum
import os
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple


class EventType(enum.Enum):
    ADDED = "ADDED"
    MODIFIED = "MODIFIED"
    DELETED = "DELETED"


class Conflict(Exception):
    """Optimistic-concurrency failure: the caller's ``expected_rv``
    precondition did not match the stored object's resource_version (the
    apiserver's 409 on a stale PUT).  Never retried blindly — the right
    recovery is get→re-apply→retry (see RemoteStore.mutate)."""


class HistoryCompacted(Exception):
    """A watch resume asked for history older than the store retains
    (ring overflow, or a restart whose checkpoint compacted it away) —
    the apiserver's 410 Gone.  The consumer must relist."""


class NotLeader(Exception):
    """A mutation reached a FENCED replica: this store consumes the
    leader's replicated WAL stream (controlplane/repl.py) and must not
    accept writes of its own — a demoted ex-leader or a follower taking
    client traffic would fork the history quorum durability promised.
    Reads keep serving (stale-bounded by replication lag).  On the wire
    it is 503 with a ``not leader`` marker; leader-aware clients
    re-discover the plane's current leader and retry there."""


class StorageDegraded(Exception):
    """The durable layer cannot persist mutations (ENOSPC/EIO on the WAL
    append, or the degraded latch a prior failure set) — etcd's NOSPACE
    alarm in miniature.  The store stays READABLE; every mutation is
    refused with this error BEFORE touching in-memory state, so nothing
    is ever acknowledged that a restart would lose.  On the wire it is
    HTTP 507 (Insufficient Storage), which the remote client treats as
    transient: retried with backoff, because the store re-arms itself
    via a recovery probe the moment appends succeed again (disk space
    freed, IO error cleared)."""


class NotYetObserved(Exception):
    """An rv-bounded read (``min_rv=N`` on get/list, or a watch resume
    at rv N) reached a FOLLOWER whose applied rv is still below the
    bound: the replica is healthy but lagging the leader's commit
    stream, and serving the request now would be a silently stale read.
    On the wire it is HTTP 504 with a ``not yet observed`` marker —
    RETRYABLE, unlike HistoryCompacted's 410: the client waits out the
    replication lag or fails over to a fresher replica; a relist would
    be wasted work.  Only ever raised by a fenced replica — the same
    condition on a leader means the client observed versions a crash
    rolled back, which stays a 410 (DESIGN.md §29)."""


class WrongShard(Exception):
    """A write reached a leader group that does not OWN the object's
    namespace: the sharded write plane (controlplane/shards.py,
    DESIGN.md §30) partitions the keyspace by namespace across K
    independent leader groups, and a façade whose topology says another
    group owns the namespace refuses the mutation BEFORE executing it —
    accepting it would fork the namespace's history across two WALs.
    On the wire it is HTTP 421 (Misdirected Request) with a ``wrong
    shard`` marker.  SEMANTIC, never blindly retried: the shard router
    (shards.ShardedStore) chases it by refreshing ``/shards/status``
    topology and re-routing to the owning group — the same chase
    discipline NotLeader gets from leader discovery, one level up."""


class ShardFrozen(Exception):
    """A write hit a namespace inside a shard split's bounded
    write-freeze window (DESIGN.md §30): the namespace is mid-handoff
    between leader groups and neither side may accept mutations until
    the checkpoint seed lands on the target and the topology epoch
    advances.  On the wire it is HTTP 503 with a ``shard frozen``
    marker — TRANSIENT: the remote client's normal 5xx backoff outlasts
    the freeze (the window is bounded by one namespace-filtered
    checkpoint ship, not by the size of the whole shard).  Reads are
    never frozen."""


class ShardFrozenTimeout(ShardFrozen):
    """A frozen-namespace retry loop exhausted its DEADLINE
    (``RemoteStore(frozen_deadline_s=)``) while the namespace stayed
    frozen: either the split is pathologically slow or its coordinator
    died and the freeze lease has not expired yet.  Subclasses
    ShardFrozen on purpose — handlers that treat "frozen" as transient
    keep working — but it is TERMINAL for this call: the client has
    already waited longer than any healthy split's freeze window plus
    the lease TTL bound, so surfacing beats hammering."""


@dataclass
class WatchEvent:
    type: EventType
    obj: Any
    old_obj: Any = None
    #: the global resource_version of the mutation that produced this
    #: event (0 = unknown/legacy producer).  Watch resume is keyed on it:
    #: a consumer that saw rv N resumes with ``resume_rv=N`` and receives
    #: exactly the events with rv > N.
    rv: int = 0
    #: memoized WIRE encoding (the HTTP watch verb's framed JSON-line
    #: chunk), filled by the first stream that serializes this event and
    #: shared by every other watcher's stream — the store fans the SAME
    #: event object into every watcher queue, so under load the encode
    #: cost is O(1) in watcher count instead of O(watchers)
    #: (httpserver.event_wire_chunk; ISSUE 8).  Never part of
    #: equality/repr; the wire line does not depend on the watcher.
    wire: Any = field(default=None, repr=False, compare=False)
    #: monotonic birth stamp (fanout time at the store), consumed by the
    #: delivery paths to observe ``watch.delivery_lag_s`` — the
    #: store-mutation→socket-write lag per watcher (ISSUE 11).  Stamped
    #: in __post_init__ so every producer site gets it for free; never
    #: part of equality/repr (tests compare reconstructed events).
    born: float = field(default=0.0, repr=False, compare=False)

    def __post_init__(self) -> None:
        if not self.born:
            self.born = time.monotonic()


#: per-watcher queue bound, in EVENTS.  The per-watch queues decouple
#: delivery from consumption so a slow consumer can never stall a mutator
#: — but UNBOUNDED they let one wedged stream pin every event object (and
#: its pods) for the life of the process.  A watcher that falls this far
#: behind is EVICTED instead: its watch dies exactly like a dropped
#: stream (``watch.fanout.evicted_slow`` counts it), and the consumer
#: recovers through the existing resume-or-410→relist reconnect path —
#: degrade-the-laggard, never block-the-store-lock (ISSUE 8).  Sized well
#: above a full wave's bind fanout (~16k events) so healthy informers
#: draining in batches never come near it.
DEFAULT_WATCH_QUEUE_EVENTS = 65536


class Watch:
    """A subscription to one kind's event stream."""

    def __init__(
        self,
        store: "ObjectStore",
        kind: str,
        max_queued: int = DEFAULT_WATCH_QUEUE_EVENTS,
    ):
        self._store = store
        self._kind = kind
        self._cond = threading.Condition()
        self._events: List[WatchEvent] = []
        self._stopped = False
        self._max_queued = max(int(max_queued), 1)
        #: set by the store once the watch is REGISTERED: the initial
        #: snapshot / resume-history replay (delivered pre-registration,
        #: possibly far larger than the live bound) is exempt from
        #: slow-watcher eviction — only live fanout lag evicts
        self._live = False
        #: how many of the QUEUED events are still the pre-registration
        #: replay (consumed FIFO, so the head of the queue drains it
        #: first).  The eviction bound applies to len(queue) MINUS this:
        #: a healthy watcher mid-way through a 100k-object snapshot must
        #: not be evicted by its first live event (the replay is exempt
        #: as a BACKLOG, not just at delivery time).
        self._replay_pending = 0
        #: the store's resource_version at registration (for a full
        #: snapshot open: the version the snapshot reflects — the exact
        #: resume cursor once that snapshot is consumed; every queued
        #: event has a higher rv).  A resumed watch carries its resume_rv.
        self.start_rv = 0
        #: edge-trigger hook for consumers that can't block in next():
        #: fired (under this watch's condition — it must only do O(1)
        #: lock-free work, e.g. write a wakeup byte) whenever events are
        #: queued OR the watch stops/evicts.  The selector stream loop
        #: (controlplane/streamloop) registers here; condvar consumers
        #: never need it.
        self._notify_cb: Optional[Callable[[], None]] = None

    def _evict_locked(self) -> None:
        """Slow-watcher eviction (caller holds self._cond): die exactly
        like a dropped stream — stop, free the queue, wake the consumer
        with end-of-stream.  The consumer's reconnect resumes from its
        last-seen rv (or relists on 410); the store's fanout prunes the
        dead registration lazily, same as ``kill``."""
        from minisched_tpu.observability import counters

        self._stopped = True
        self._events.clear()
        self._replay_pending = 0
        counters.inc("watch.fanout.evicted_slow")
        self._cond.notify_all()
        if self._notify_cb is not None:
            self._notify_cb()

    def _live_queued_locked(self) -> int:
        """Queued LIVE events (caller holds self._cond): total queue
        minus the not-yet-consumed replay backlog — the only population
        the eviction bound measures."""
        return len(self._events) - self._replay_pending

    # called by the store while it holds its lock; only touches this
    # watch's own condition/queue, so it cannot block on user code
    def _deliver(self, event: WatchEvent) -> None:
        with self._cond:
            if self._stopped:
                return
            if self._live and self._live_queued_locked() >= self._max_queued:
                self._evict_locked()
                return
            self._events.append(event)
            self._cond.notify_all()
            if self._notify_cb is not None:
                self._notify_cb()

    def _deliver_many(self, events: List[WatchEvent]) -> None:
        """Batch delivery: ONE condvar hold + notify for the whole list.
        A wave's batch bind fans out thousands of events; per-event lock/
        notify round-trips were a measurable slice of the bind wall."""
        if not events:
            return
        with self._cond:
            if self._stopped:
                return
            # gate on EXISTING lag, not batch size: one oversized fanout
            # batch (a >bound create_many) must not evict every
            # caught-up watcher of the kind at once — only a consumer
            # already at the bound is a laggard.  The bound is soft by
            # one batch as a result; the next delivery evicts if the
            # consumer still hasn't drained.
            if self._live and self._live_queued_locked() >= self._max_queued:
                self._evict_locked()
                return
            self._events.extend(events)
            self._cond.notify_all()
            if self._notify_cb is not None:
                self._notify_cb()

    def next(self, timeout: Optional[float] = None) -> Optional[WatchEvent]:
        import time as _time

        deadline = None if timeout is None else _time.monotonic() + timeout
        with self._cond:
            # predicate loop: spurious condvar wakeups must not surface as
            # end-of-stream on a live watch
            while not self._events and not self._stopped:
                if deadline is None:
                    self._cond.wait()
                else:
                    remaining = deadline - _time.monotonic()
                    if remaining <= 0 or not self._cond.wait(remaining):
                        break
            if self._events:
                if self._replay_pending:
                    self._replay_pending -= 1  # FIFO: replay drains first
                return self._events.pop(0)
            return None

    def next_batch(self, timeout: Optional[float] = None) -> List[WatchEvent]:
        """Drain EVERYTHING queued in one condvar hold (empty list on
        timeout/stop).  The informer dispatch thread consumes batches so a
        wave's thousands of bind events cost one lock round-trip, not one
        each — the per-event form starved the GIL-free device window."""
        import time as _time

        deadline = None if timeout is None else _time.monotonic() + timeout
        with self._cond:
            while not self._events and not self._stopped:
                if deadline is None:
                    self._cond.wait()
                else:
                    remaining = deadline - _time.monotonic()
                    if remaining <= 0 or not self._cond.wait(remaining):
                        break
            out, self._events = self._events, []
            self._replay_pending = 0  # FIFO: a full drain consumed it all
            return out

    def kill(self) -> None:
        """Die as a dropped stream would: stop delivering, wake consumers
        with end-of-stream, but WITHOUT deregistering (the store's fanout
        prunes dead watches lazily).  Only the fault fabric calls this —
        the consumer sees exactly what a lost network stream looks like
        and must reconnect."""
        with self._cond:
            self._stopped = True
            self._cond.notify_all()
            if self._notify_cb is not None:
                self._notify_cb()

    def stop(self) -> None:
        self.kill()
        self._store._remove_watch(self._kind, self)

    def set_notify(self, cb: Optional[Callable[[], None]]) -> None:
        """Install the edge-trigger hook (see ``_notify_cb``).  Fires
        once immediately when events are already queued or the watch is
        already stopped, so a registration can never miss the edge that
        happened just before it."""
        with self._cond:
            self._notify_cb = cb
            pending = bool(self._events) or self._stopped
            if pending and cb is not None:
                cb()

    @property
    def stopped(self) -> bool:
        return self._stopped


#: events retained for watch resume, PER KIND.  Sized so a short
#: reconnect (the informer's 0.5–10s backoff) replays from history
#: instead of relisting even at wave scale; overflow advances that
#: kind's floor and a too-old resume gets HistoryCompacted (410) —
#: correct, just costlier for the consumer.  Per-kind isolation is the
#: point: the EventRecorder's volatile Event churn (one create+expiry
#: per scheduling decision) must not evict the Pod/Node tail a resuming
#: informer actually needs.
DEFAULT_HISTORY_EVENTS = 65536

#: BYTE budget for the same ring, PER KIND — the count cap alone let
#: 65536 headline-sized pods (multi-KB of containers/affinity each) pin
#: hundreds of MB of history.  Whichever cap trips first evicts; both
#: advance the floor, so 410-Gone + relist behavior is unchanged — a
#: fat-pod churn burst just compacts sooner.
DEFAULT_HISTORY_BYTES = 64 * 1024 * 1024


def _walk_bytes(x: Any) -> int:
    """Generic footprint estimate (NOT exact — the ring budget needs
    proportionality, not accounting): strings/containers by length,
    dataclass-ish objects via __dict__, private/memo fields skipped."""
    if x is None:
        return 8
    if isinstance(x, str):
        return 56 + len(x)
    if isinstance(x, (int, float, bool)):
        return 32
    if isinstance(x, dict):
        return 64 + sum(_walk_bytes(k) + _walk_bytes(v) for k, v in x.items())
    if isinstance(x, (list, tuple, set, frozenset)):
        return 56 + sum(_walk_bytes(v) for v in x)
    d = getattr(x, "__dict__", None)
    if d is not None:
        return 64 + sum(
            _walk_bytes(v) for k, v in d.items() if not k.startswith("_")
        )
    return 64


def approx_obj_bytes(obj: Any) -> int:
    """Cheap per-object size estimate for the history ring's byte budget.

    The spec walk is memoized ON the spec (kube semantics: specs are
    immutable once created, and the bind path shares them structurally
    between the pending and bound object — exactly like
    ``Pod.resource_requests``), so a wave's thousands of bind events cost
    one dict lookup each, not a recursive walk."""
    total = 256
    meta = getattr(obj, "metadata", None)
    if meta is not None:
        total += 128 + _walk_bytes(meta.labels) + _walk_bytes(meta.annotations)
    spec = getattr(obj, "spec", None)
    if spec is not None:
        d = getattr(spec, "__dict__", None)
        if d is None:
            total += _walk_bytes(spec)
        else:
            memo = d.get("_approx_bytes_memo")
            if memo is None:
                memo = _walk_bytes(spec)
                d["_approx_bytes_memo"] = memo
            total += memo
    return total


def compute_node_agg(pods) -> Dict[str, List[int]]:
    """Per-node ``[milli_cpu, memory, pods]`` summed over BOUND pods —
    the independent recompute of ``ObjectStore._pod_node_agg`` that the
    live scrub and offline fsck check the incremental index against.
    One definition on purpose: two hand-rolled copies of the aggregation
    would let the invariant check drift from the index it polices."""
    agg: Dict[str, List[int]] = {}
    for pod in pods:
        node = pod.spec.node_name
        if not node:
            continue
        req = pod.resource_requests()
        a = agg.get(node)
        if a is None:
            a = agg[node] = [0, 0, 0]
        a[0] += req.milli_cpu
        a[1] += req.memory
        a[2] += req.pods
    return agg


class _ReadSnapshot:
    """One immutable copy-on-write view of the PUBLISHED store state:
    ``maps`` (kind → {key → stored object}) plus the ``_visible_rv``
    those maps reflect, swapped in as ONE reference assignment at every
    publish point — the mutation tail in the base store, the group's
    publish loop in the durable store (ISSUE 14).  Lock-free readers
    grab ``store._snap`` once and hold a frozen epoch: get/list/
    list_with_rv and full-snapshot watch registration never touch the
    store lock.  Sharing the stored objects is safe for the same reason
    fanout shares them (see _fanout): the store never mutates an object
    in place — updates replace dict entries wholesale.

    Two memo fields ride the snapshot and die with it at the next swap,
    both filled lazily OFF the store lock.  Misses serialize on the
    snapshot-private ``_mu`` — NOT the event_wire_chunk benign-race
    idiom: a relist storm means hundreds of threads missing the same
    (kind, ns) at once, and letting them all encode a multi-hundred-KB
    body redundantly is exactly the stampede this cache exists to kill.
    Hits stay lock-free dict reads; ``_mu`` never contends with writers.

    ``list_bodies``: (kind, namespace) → the encoded HTTP list body.
    One snapshot is one rv, so the effective cache key is (kind,
    namespace, rv) and the swap itself is the invalidation — a relist
    storm of N informers costs ONE encode (``store.list_cache.*``).

    ``replay_events``: kind → the shared ADDED-event list a full-
    snapshot watch registration replays.  Every registering watcher
    queues the SAME WatchEvent objects, so the wire memo
    (event_wire_chunk) makes a storm of watch opens encode each object
    once instead of once per stream.  ``born`` is zeroed: a replay is
    not live fanout, so the delivery-lag observers skip it.
    """

    __slots__ = ("maps", "rv", "list_bodies", "replay_events", "_mu")

    def __init__(self, maps: Dict[str, Dict[str, Any]], rv: int) -> None:
        self.maps = maps
        self.rv = rv
        self.list_bodies: Dict[Tuple[str, str], bytes] = {}
        self.replay_events: Dict[str, List[WatchEvent]] = {}
        self._mu = threading.Lock()

    def list_body(
        self, kind: str, ns: str, build: Callable[[], bytes]
    ) -> bytes:
        """Memoized encoded list payload for (kind, namespace):
        ``store.list_cache.encodes`` counts first builds,
        ``store.list_cache.hits`` the shared reuses the façade streams
        from the same bytes."""
        from minisched_tpu.observability import counters

        body = self.list_bodies.get((kind, ns))
        if body is None:
            with self._mu:
                body = self.list_bodies.get((kind, ns))
                if body is None:
                    body = build()
                    self.list_bodies[(kind, ns)] = body
                    counters.inc("store.list_cache.encodes")
                    return body
        counters.inc("store.list_cache.hits")
        return body

    def replay_events_for(self, kind: str) -> List[WatchEvent]:
        evs = self.replay_events.get(kind)
        if evs is None:
            with self._mu:
                evs = self.replay_events.get(kind)
                if evs is None:
                    evs = []
                    for obj in self.maps.get(kind, {}).values():
                        ev = WatchEvent(
                            EventType.ADDED, obj,
                            rv=obj.metadata.resource_version,
                        )
                        # replay, not fanout: lag observers skip born=0
                        ev.born = 0.0
                        evs.append(ev)
                    self.replay_events[kind] = evs
        return evs


class ObjectStore:
    """Versioned multi-kind object store + watch hub."""

    def __init__(
        self,
        history_events: int = DEFAULT_HISTORY_EVENTS,
        history_bytes: int = DEFAULT_HISTORY_BYTES,
        watch_queue_events: int = DEFAULT_WATCH_QUEUE_EVENTS,
    ) -> None:
        self._lock = threading.RLock()
        #: per-watcher queue bound; see DEFAULT_WATCH_QUEUE_EVENTS
        self._watch_queue_events = max(int(watch_queue_events), 1)
        self._objects: Dict[str, Dict[str, Any]] = {}  # kind -> key -> obj
        self._watches: Dict[str, List[Watch]] = {}
        self._rv = 0
        # watch-resume history: per-kind rings of (event, approx bytes) in
        # mutation order, bounded by COUNT and by BYTES (whichever trips
        # first evicts — see DEFAULT_HISTORY_BYTES).  A kind's floor is
        # the highest rv NO LONGER retained for it — resume_rv below the
        # floor means the gap cannot be replayed (HistoryCompacted).
        # ``_history_floor_min`` is the baseline for every kind regardless
        # of ring state (a durable reopen sets it to the checkpoint rv:
        # nothing before the snapshot is reconstructable for ANY kind).
        self._history: Dict[str, deque] = {}
        # per-node Pod request aggregates, maintained INCREMENTALLY on
        # every Pod mutation (node name → [milli_cpu, memory bytes, pod
        # count] summed over pods bound there).  The capacity-validated
        # bind transaction (client._node_budgets) used to scan the whole
        # pod population once per batch — O(all pods) per bind batch at
        # 100k-pod scale; this index makes it O(target nodes).  Kept
        # exact under the store lock: every commit path (create/update/
        # delete/mutate_many/restore) routes through _node_agg_track.
        self._pod_node_agg: Dict[str, List[int]] = {}
        self._history_cap = max(int(history_events), 0)
        self._history_byte_cap = max(int(history_bytes), 0)
        self._history_bytes_used: Dict[str, int] = {}
        self._history_floors: Dict[str, int] = {}
        self._history_floor_min = 0
        #: fault-injection hook (SURVEY.md §5.3 — the reference has none):
        #: called as (op, kind, key) before every mutation AND read;
        #: raising makes the call fail exactly as a flaky apiserver/etcd
        #: would.  Wire a fabric with
        #: ``store.fault_injector = fabric.as_store_injector()``.
        self.fault_injector: Optional[Callable[[str, str, str], None]] = None
        #: optional faults.FaultFabric for non-raising failure modes —
        #: today only ``watch.drop``: at fanout time a scheduled drop
        #: KILLS the watch (stream death) instead of delivering, and the
        #: triggering event is lost with it — the informer's reconnect +
        #: snapshot-replay diff is what recovers the gap.
        self.faults: Any = None
        #: copy-on-write read plane (ISSUE 14): the immutable published
        #: view lock-free readers serve from, swapped (never mutated) by
        #: _cow_publish at every publish point.  MINISCHED_COW_READS=0
        #: is the kill-switch restoring the exact locked read paths
        #: (None = disabled; byte parity pinned in tests/test_cow_reads).
        self._snap: Optional[_ReadSnapshot] = (
            _ReadSnapshot({}, 0)
            if os.environ.get("MINISCHED_COW_READS", "1") != "0"
            else None
        )

    # -- helpers -----------------------------------------------------------
    def _maybe_fault(self, op: str, kind: str, key: str) -> None:
        fi = self.fault_injector  # one read: the hook may be cleared mid-call
        if fi is not None:
            fi(op, kind, key)

    @staticmethod
    def _key(obj: Any) -> str:
        return obj.metadata.key

    def _bump(self) -> int:
        self._rv += 1
        return self._rv

    # -- copy-on-write read plane ------------------------------------------
    def _cow_publish(self, kinds) -> None:
        """Swap the read-plane snapshot (caller holds the lock, AFTER
        the in-memory apply + fanout): rebuild the per-kind maps named
        in ``kinds`` as fresh dict copies of the live maps, reuse every
        other kind's frozen map, stamp the published rv, and install
        the new view as ONE reference assignment.  Readers holding the
        old snapshot keep a consistent pre-mutation epoch; new readers
        see this one.  Runs at exactly the seams that already order
        apply/fanout by rv, so read-your-writes holds: a publisher's
        own mutation is in the snapshot before its call returns (base
        store) or acks (group commit).  An empty ``kinds`` refreshes
        the rv only (checkpoint fast-forward), reusing every map."""
        snap = self._snap
        if snap is None:
            return  # kill-switch: locked reads serve the live maps
        if kinds:
            maps = dict(snap.maps)
            for kind in kinds:
                maps[kind] = dict(self._objects.get(kind, ()))
        else:
            maps = snap.maps
        self._snap = _ReadSnapshot(maps, self._visible_rv())

    def read_plane(self) -> Optional[_ReadSnapshot]:
        """The current immutable read snapshot (None when the COW plane
        is kill-switched off) — the HTTP façade serves list payloads
        straight from it (see _ReadSnapshot.list_body)."""
        return self._snap

    # -- per-node aggregate index ------------------------------------------
    def _node_agg_track(self, kind: str, old: Any, new: Any) -> None:
        """Fold one Pod mutation into the per-node request aggregates
        (caller holds the lock).  ``old``/``new`` are the stored objects
        before/after (None for create/delete).  Requests are spec-memoized
        (Pod.resource_requests), so this is a few dict ops per commit."""
        if kind != "Pod":
            return
        agg = self._pod_node_agg
        for obj, sign in ((old, -1), (new, 1)):
            if obj is None:
                continue
            node = obj.spec.node_name
            if not node:
                continue
            req = obj.resource_requests()
            a = agg.get(node)
            if a is None:
                a = agg[node] = [0, 0, 0]
            a[0] += sign * req.milli_cpu
            a[1] += sign * req.memory
            a[2] += sign * req.pods
            if sign < 0 and not (a[0] or a[1] or a[2]):
                del agg[node]  # bound pods all gone: don't accrete names

    def _rebuild_node_agg(self) -> None:
        """Recompute the index from the live objects — recovery paths
        (WAL replay, checkpoint restore) that write ``_objects`` directly
        call this once at the end instead of tracking per record."""
        with self._lock:
            self._pod_node_agg = {}
            for pod in self._objects.get("Pod", {}).values():
                self._node_agg_track("Pod", None, pod)

    def _record_history(self, kind: str, event: WatchEvent) -> None:
        """Append one event to the kind's resume ring (caller holds the
        lock).  Overflow — by event COUNT or by the kind's BYTE budget —
        advances that kind's floor to the dropped event's rv: resumes
        below the floor must relist (HistoryCompacted)."""
        if self._history_cap <= 0:
            return
        ring = self._history.get(kind)
        if ring is None:
            ring = self._history[kind] = deque()
        # retain a ring-private copy: WITHOUT old_obj (the replaced
        # version is garbage the moment a newer event lands, and pinning
        # it doubles the ring's footprint at wave scale — resume
        # consumers re-derive 'old' from their own caches, and the wire
        # encoding never carried it), and DISTINCT from the fanned-out
        # object so a live HTTP stream's memoized wire bytes
        # (event_wire_chunk) never pin into the ring past its byte
        # budget.  Resume replays deliver their own per-resumer copies
        # (see watch()), so nothing ever memoizes onto ring-resident
        # events at all.
        event = WatchEvent(event.type, event.obj, rv=event.rv)
        cost = approx_obj_bytes(event.obj) + 96  # + ring/event overhead
        used = self._history_bytes_used.get(kind, 0) + cost
        floors = self._history_floors
        while ring and (
            len(ring) >= self._history_cap
            or (self._history_byte_cap > 0 and used > self._history_byte_cap)
        ):
            dropped, dropped_cost = ring.popleft()
            used -= dropped_cost
            if dropped.rv > floors.get(kind, 0):
                floors[kind] = dropped.rv
        ring.append((event, cost))
        self._history_bytes_used[kind] = used

    def history_stats(self, kind: str) -> Dict[str, int]:
        """(events retained, approx bytes retained) for one kind — the
        byte-budget tests and dashboards read this."""
        with self._lock:
            return {
                "events": len(self._history.get(kind, ())),
                "bytes": self._history_bytes_used.get(kind, 0),
            }

    def _floor_for(self, kind: str) -> int:
        return max(self._history_floor_min, self._history_floors.get(kind, 0))

    def set_history_floor(self, rv: int) -> None:
        """Raise the resume floor for EVERY kind (never lowers).  The
        durable store calls this at replay: events at or before the
        checkpoint's rv are not reconstructable, so resumes from them
        must get 410."""
        with self._lock:
            self._history_floor_min = max(self._history_floor_min, rv)

    @property
    def history_floor(self) -> int:
        """The all-kinds baseline floor (per-kind ring overflow can sit
        higher — ``watch`` checks both)."""
        with self._lock:
            return self._history_floor_min

    def _fanout(self, kind: str, event: WatchEvent) -> None:
        # events carry the STORED objects directly — no defensive clones.
        # Safe because the store never mutates an object after it lands in
        # _objects: every update/mutate builds a fresh clone and replaces
        # the dict entry wholesale, so a fanned-out reference can never
        # change underneath its observers.  (Consumers treat API objects
        # as immutable; only clones returned from get()/list()/update()
        # are theirs to mutate.)  At wave scale the per-event clones were
        # a third of the batch-bind cost.
        self._record_history(kind, event)
        faults = self.faults
        for w in list(self._watches.get(kind, ())):
            if w.stopped:
                # killed by a prior drop (kill() leaves registration to
                # the fanout): prune here so dropped streams don't accrete
                self._remove_watch(kind, w)
                continue
            if faults is not None and faults.should_fire("watch.drop", kind):
                w.kill()
                continue
            w._deliver(event)

    def _fanout_many(self, kind: str, events: List[WatchEvent]) -> None:
        """Batched fanout (caller holds the lock): history append per
        event, then ONE _deliver_many per watcher — the shared tail of
        create_many/mutate_many and the group-commit publish path."""
        for ev in events:
            self._record_history(kind, ev)
        faults = self.faults
        for w in list(self._watches.get(kind, ())):
            if w.stopped:
                self._remove_watch(kind, w)  # see _fanout
                continue
            if faults is not None and faults.should_fire("watch.drop", kind):
                w.kill()  # the whole batch is lost to this stream
                continue
            w._deliver_many(events)

    # -- CRUD --------------------------------------------------------------
    def create(self, kind: str, obj: Any) -> Any:
        with self._lock:
            objs = self._objects.setdefault(kind, {})
            key = self._key(obj)
            self._maybe_fault("create", kind, key)
            if key in objs:
                raise KeyError(f"{kind} {key!r} already exists")
            stored = obj.clone()
            if not stored.metadata.uid:
                from minisched_tpu.api.objects import new_uid

                stored.metadata.uid = new_uid(kind.lower())
            stored.metadata.resource_version = self._bump()
            if not stored.metadata.creation_timestamp:
                stored.metadata.creation_timestamp = time.time()
            # durability BEFORE commit: the WAL record lands (and
            # flushes) before the object enters the maps or any watcher
            # can observe the event — a failed append (disk full, fault
            # injection) then means the mutation simply never happened:
            # no phantom in-memory object a restart would lose, no
            # resource_version a remote informer holds that the
            # recovered server rolls back.  The rv bump above may leave
            # a gap on failure; gaps are legal (volatile kinds make them
            # routinely).  Base store: no-op.
            self._commit_record(
                kind, "put", stored, stored.metadata.resource_version
            )
            objs[key] = stored
            self._node_agg_track(kind, None, stored)
            out = stored.clone()
            self._fanout(
                kind,
                WatchEvent(
                    EventType.ADDED, stored,
                    rv=stored.metadata.resource_version,
                ),
            )
            self._cow_publish((kind,))
        return out

    def create_many(
        self, kind: str, objs: List[Any], return_objects: bool = True
    ) -> List[Any]:
        """Batch create under ONE lock hold — the seed path of every
        bench/scenario (a 10k-object cluster through create() paid a lock
        round-trip, a history append, and a per-watcher fanout each).
        Returns a list aligned with ``objs``: the stored clone (None with
        ``return_objects=False`` — skips a clone per item), or the
        exception for that entry (KeyError on conflict) — one failed item
        never aborts the rest, matching mutate_many.  Durability before
        visibility holds batch-wide: every WAL record lands (one flush)
        before the single batched fanout."""
        from minisched_tpu.api.objects import new_uid

        out: List[Any] = []
        events: List[WatchEvent] = []
        with self._lock:
            objs_map = self._objects.setdefault(kind, {})
            for obj in objs:
                key = self._key(obj)
                try:
                    self._maybe_fault("create", kind, key)
                    if key in objs_map:
                        raise KeyError(f"{kind} {key!r} already exists")
                    stored = obj.clone()
                    if not stored.metadata.uid:
                        stored.metadata.uid = new_uid(kind.lower())
                    stored.metadata.resource_version = self._bump()
                    if not stored.metadata.creation_timestamp:
                        stored.metadata.creation_timestamp = time.time()
                    # durability before commit (see create): a refused
                    # append fails THIS item only, leaving memory clean
                    self._on_batch_commit(kind, stored)
                    objs_map[key] = stored
                    self._node_agg_track(kind, None, stored)
                    out.append(stored.clone() if return_objects else None)
                    events.append(
                        WatchEvent(
                            EventType.ADDED, stored,
                            rv=stored.metadata.resource_version,
                        )
                    )
                except Exception as err:  # noqa: BLE001 — returned, not lost
                    out.append(err)
            self._flush_log()
            self._fanout_many(kind, events)
            self._cow_publish((kind,))
        return out

    def get(self, kind: str, namespace: str, name: str) -> Any:
        snap = self._snap
        if snap is not None:
            # lock-free: one reference grab is the whole read (the fault
            # hook is internally locked, safe to consult off-lock)
            self._maybe_fault("get", kind, f"{namespace}/{name}")
            obj = snap.maps.get(kind, {}).get(f"{namespace}/{name}")
            if obj is None:
                raise KeyError(f"{kind} {namespace}/{name} not found")
            return obj.clone()
        with self._lock:
            self._maybe_fault("get", kind, f"{namespace}/{name}")
            obj = self._objects.get(kind, {}).get(f"{namespace}/{name}")
            if obj is None:
                raise KeyError(f"{kind} {namespace}/{name} not found")
            return obj.clone()

    def list(self, kind: str) -> List[Any]:
        snap = self._snap
        if snap is not None:
            self._maybe_fault("list", kind, "")
            return [o.clone() for o in snap.maps.get(kind, {}).values()]
        with self._lock:
            self._maybe_fault("list", kind, "")
            return [o.clone() for o in self._objects.get(kind, {}).values()]

    def list_with_rv(self, kind: str) -> Tuple[List[Any], int]:
        """Epoch-consistent list: (snapshot, the store resource_version it
        reflects).  COW mode serves it lock-free — the snapshot's maps
        and rv were published together, so the pair is atomic by
        construction; the kill-switch path takes the items and the rv
        under ONE lock hold.  A consumer deriving versioned state from a
        listing (the HA membership layer's shard map) needs the rv
        ATOMIC with the items — list() then resource_version can
        interleave a mutation and stamp the snapshot with a version it
        does not reflect."""
        snap = self._snap
        if snap is not None:
            self._maybe_fault("list", kind, "")
            return (
                [o.clone() for o in snap.maps.get(kind, {}).values()],
                snap.rv,
            )
        with self._lock:
            self._maybe_fault("list", kind, "")
            return (
                [o.clone() for o in self._objects.get(kind, {}).values()],
                self._visible_rv(),
            )

    def update(
        self, kind: str, obj: Any, expected_rv: Optional[int] = None
    ) -> Any:
        """``expected_rv`` is the optimistic-concurrency precondition (the
        apiserver's resourceVersion check on PUT): when set, the write
        commits only if the STORED object still carries that version —
        otherwise Conflict, and the caller must re-read and re-apply."""
        with self._lock:
            objs = self._objects.setdefault(kind, {})
            key = self._key(obj)
            self._maybe_fault("update", kind, key)
            old = objs.get(key)
            if old is None:
                raise KeyError(f"{kind} {key!r} not found")
            if (
                expected_rv is not None
                and old.metadata.resource_version != expected_rv
            ):
                raise Conflict(
                    f"stale resource_version for {kind} {key}: expected "
                    f"{expected_rv}, have {old.metadata.resource_version}"
                )
            stored = obj.clone()
            stored.metadata.uid = old.metadata.uid
            stored.metadata.creation_timestamp = old.metadata.creation_timestamp
            stored.metadata.resource_version = self._bump()
            # durability before commit (see create)
            self._commit_record(
                kind, "put", stored, stored.metadata.resource_version
            )
            objs[key] = stored
            self._node_agg_track(kind, old, stored)
            out = stored.clone()
            self._fanout(
                kind,
                WatchEvent(
                    EventType.MODIFIED, stored, old,
                    rv=stored.metadata.resource_version,
                ),
            )
            self._cow_publish((kind,))
        return out

    def delete(self, kind: str, namespace: str, name: str) -> None:
        with self._lock:
            objs = self._objects.get(kind, {})
            key = f"{namespace}/{name}"
            self._maybe_fault("delete", kind, key)
            old = objs.get(key)
            if old is None:
                raise KeyError(f"{kind} {key!r} not found")
            rv = self._bump()
            # durability before commit (see create)
            self._commit_record(kind, "del", old, rv)
            objs.pop(key, None)
            self._node_agg_track(kind, old, None)
            self._fanout(kind, WatchEvent(EventType.DELETED, old, rv=rv))
            self._cow_publish((kind,))

    def delete_many(
        self, kind: str, keys: List[Tuple[str, str]]
    ) -> List[Any]:
        """Batch delete under ONE lock hold — ``delete``'s twin as
        ``create_many`` is ``create``'s.  ``keys``: (namespace, name)
        pairs.  Returns a list aligned with ``keys``: None, or the
        exception that item raised (KeyError for a missing key — a key
        named twice is missing the second time) — one failed item never
        aborts the rest.  The DELETED events carry rising rvs in the
        order given; durability before visibility holds batch-wide
        (every record lands, one flush, then ONE batched fanout and ONE
        swap of the read plane — a ``delete`` a key copied the kind's
        whole map a key)."""
        out: List[Any] = []
        events: List[WatchEvent] = []
        with self._lock:
            objs = self._objects.get(kind, {})
            for namespace, name in keys:
                key = f"{namespace}/{name}"
                try:
                    self._maybe_fault("delete", kind, key)
                    old = objs.get(key)
                    if old is None:
                        raise KeyError(f"{kind} {key!r} not found")
                    rv = self._bump()
                    # durability before commit (see create): a refused
                    # append fails THIS item only, leaving memory clean
                    self._commit_record(kind, "del", old, rv)
                    del objs[key]
                    self._node_agg_track(kind, old, None)
                    out.append(None)
                    events.append(WatchEvent(EventType.DELETED, old, rv=rv))
                except Exception as err:  # noqa: BLE001 — returned, not lost
                    out.append(err)
            self._flush_log()
            self._fanout_many(kind, events)
            self._cow_publish((kind,))
        return out

    def mutate(
        self, kind: str, namespace: str, name: str, fn: Callable[[Any], Any]
    ) -> Any:
        """Read-modify-write under the store lock (optimistic-concurrency-free
        convenience for in-process callers; the binding subresource uses it)."""
        with self._lock:
            obj = self.get(kind, namespace, name)
            updated = fn(obj) or obj
            return self.update(kind, updated)

    def mutate_many(
        self,
        kind: str,
        items: List[Tuple[str, str, Callable[[Any], Any]]],
        return_objects: bool = True,
        clone_for_write: bool = True,
        prepare: Optional[Callable[["ObjectStore"], None]] = None,
    ) -> List[Any]:
        """Apply many read-modify-writes under ONE lock hold — the wave
        engine's batch bind (a wave commits thousands of placements; a
        lock round-trip per bind dominated the e2e profile).

        ``items``: (namespace, name, fn) triples.  Returns a list aligned
        with ``items`` holding the updated object — or the exception that
        item raised: one failed bind (AlreadyBound, deleted pod) must not
        abort the rest of the wave's commits.

        Inlined read-modify-write (vs mutate→get/update): an object clone
        is ~20µs of hand-rolled copying, and the nested path pays five per
        item (get, stored, returned, event-new, event-old).  Here: ONE
        clone mutated and stored, one for the event's new object, and the
        REPLACED object rides the event un-cloned — it just left the store
        dict, so nothing aliases it.  An 8k-pod wave's bind drops from
        ~950ms to ~³⁄₅ of that; the returned list still carries the stored
        object's clone only because callers expect the update() contract.

        ``clone_for_write=False`` skips even that one deep clone: ``fn``
        receives the STORED object and must return a NEW object without
        mutating it — structural sharing of the untouched sub-objects is
        the point (a bind changes one spec field; deep-copying containers/
        affinity/volumes for 16k pods was ~0.5s per wave).  The returned
        object must carry its OWN metadata instance (the store restamps
        resource_version on it).

        ``prepare`` runs under the store lock BEFORE the item loop,
        receiving this store: a caller that must derive shared state
        atomically with the batch (the capacity-validated bind path
        computes per-node budgets) hooks it here instead of wrapping
        the whole call in ``locked()`` — the group-commit durable store
        must NOT be entered with the lock already held (the caller
        would then sleep on the commit barrier still owning the lock
        every other mutator and the group leader need).
        """
        out: List[Any] = []
        events: List[WatchEvent] = []
        with self._lock:
            if prepare is not None:
                prepare(self)
            objs = self._objects.setdefault(kind, {})
            for namespace, name, fn in items:
                key = f"{namespace}/{name}"
                try:
                    self._maybe_fault("update", kind, key)
                    old = objs.get(key)
                    if old is None:
                        raise KeyError(f"{kind} {key!r} not found")
                    if clone_for_write:
                        work = old.clone()
                        work = fn(work) or work
                    else:
                        work = fn(old)
                    work.metadata.uid = old.metadata.uid
                    work.metadata.creation_timestamp = (
                        old.metadata.creation_timestamp
                    )
                    work.metadata.resource_version = self._bump()
                    # durability before commit (see create): a refused
                    # append fails this item, memory stays clean
                    self._on_batch_commit(kind, work)
                    objs[key] = work
                    self._node_agg_track(kind, old, work)
                    out.append(work.clone() if return_objects else None)
                    events.append(
                        WatchEvent(
                            EventType.MODIFIED, work, old,
                            rv=work.metadata.resource_version,
                        )
                    )
                except Exception as err:  # noqa: BLE001 — returned, not lost
                    out.append(err)
            # durability before visibility for the batch too: every item's
            # record was appended by _on_batch_commit; force it to disk
            # BEFORE the events fan out (base store: no-op).  ONE batched
            # fanout per watcher, still under the store lock so queue
            # order equals mutation order across concurrent mutators.
            self._flush_log()
            self._fanout_many(kind, events)
            self._cow_publish((kind,))
        return out

    def _on_batch_commit(self, kind: str, obj: Any) -> None:
        """Per-item durability hook for the inlined mutate_many path (which
        bypasses update()); DurableObjectStore overrides this to append the
        WAL record."""

    def _commit_record(self, kind: str, op: str, obj: Any, rv: int) -> None:
        """Single-op durability hook, called with the store lock held,
        AFTER the in-memory commit and BEFORE the watch fanout — the
        DurableObjectStore appends (and flushes) the WAL record here so
        no observer ever sees a resource_version that a crash could roll
        back.  ``op`` is "put" or "del"; ``obj`` is the stored object
        (put) or the removed one (del)."""

    def _flush_log(self) -> None:
        """Batch-path durability barrier (see mutate_many): force pending
        WAL records to disk before their events become visible."""

    def _visible_rv(self) -> int:
        """The resource_version the PUBLISHED state reflects (caller holds
        the lock).  In the base store that is simply ``_rv``; the
        group-commit durable store reserves rvs under a short lock hold
        and publishes them only after the durability barrier, so its
        visible rv lags the reserved counter while mutations are staged.
        Snapshot stamps (``watch`` start_rv, ``list_with_rv``) must use
        THIS — stamping a reserved-but-unpublished rv would promise
        watchers that events at or below it were already delivered."""
        return self._rv

    @property
    def resource_version(self) -> int:
        with self._lock:
            return self._rv

    def is_fenced(self) -> bool:
        """True when this store refuses writes because it follows a
        leader's replicated stream (DurableObjectStore.fence overrides).
        The base in-memory store always leads itself."""
        return False

    def applied_rv(self) -> int:
        """The rv watermark of the state this store would SERVE right
        now — the read plane's freshness stamp (`X-Minisched-RV`).  COW
        mode reads it lock-free off the published snapshot (maps and rv
        are atomic by construction); the kill-switch path falls back to
        the visible rv under the lock."""
        snap = self._snap
        if snap is not None:
            return snap.rv
        with self._lock:
            return self._visible_rv()

    def locked(self):
        """The store's RLock as a context manager — for multi-call
        operations that need one consistent view (checkpoint snapshots)."""
        return self._lock

    def restore_object(self, kind: str, obj: Any) -> None:
        """Checkpoint-restore insert: preserves the object's uid and
        resource_version (create() would re-stamp both).  Fans out ADDED so
        watchers attached afterwards replay a consistent cache."""
        with self._lock:
            objs = self._objects.setdefault(kind, {})
            key = self._key(obj)
            if key in objs:
                raise KeyError(f"{kind} {key!r} already exists")
            stored = obj.clone()
            # durability before commit (see create)
            self._commit_record(
                kind, "put", stored, stored.metadata.resource_version
            )
            objs[key] = stored
            self._node_agg_track(kind, None, stored)
            self._rv = max(self._rv, stored.metadata.resource_version)
            self._fanout(
                kind,
                WatchEvent(
                    EventType.ADDED, stored,
                    rv=stored.metadata.resource_version,
                ),
            )
            self._cow_publish((kind,))

    def set_resource_version(self, rv: int) -> None:
        """Fast-forward the version counter (checkpoint restore) — never
        backwards, so bookmarks taken before a resume stay monotonic."""
        with self._lock:
            self._rv = max(self._rv, rv)
            self._cow_publish(())

    # -- watch -------------------------------------------------------------
    def watch(
        self,
        kind: str,
        send_initial: bool = True,
        resume_rv: Optional[int] = None,
        clone_snapshot: bool = True,
    ) -> Tuple[Watch, List[Any]]:
        """Open a watch; returns (watch, current snapshot).

        ``send_initial`` replays the snapshot as ADDED events into the watch
        (list+watch, what client-go's reflector does on start).

        ``resume_rv`` resumes instead: the consumer saw everything through
        that resource_version, so the watch pre-delivers ONLY the retained
        history events with rv > resume_rv (no snapshot), then goes live —
        atomically with registration, so nothing falls in a gap.  Raises
        HistoryCompacted when the tail from resume_rv is no longer
        retained (ring overflow / checkpoint compaction): the consumer
        must fall back to a full list+watch.

        ``clone_snapshot=False`` returns the stored objects themselves in
        the snapshot instead of per-caller clones — for consumers that
        only INSPECT it (the HTTP façade counts namespaces for its SYNC
        line); the immutability contract (see _fanout) makes the shared
        references safe, and a watch-open storm skips O(objects) clones
        per stream.
        """
        snap = self._snap
        if snap is not None and resume_rv is None:
            return self._watch_cow(kind, snap, send_initial, clone_snapshot)
        with self._lock:
            if resume_rv is not None:
                floor = self._floor_for(kind)
                if resume_rv < floor:
                    raise HistoryCompacted(
                        f"resource_version {resume_rv} compacted away "
                        f"for {kind} (floor {floor})"
                    )
                if resume_rv > self._rv:
                    if self.is_fenced():
                        # a FOLLOWER that has not yet applied the group
                        # carrying resume_rv: the consumer is not wrong,
                        # this replica is just behind the commit stream.
                        # Retryable — the client waits out the lag or
                        # resumes on a fresher replica (DESIGN.md §29).
                        raise NotYetObserved(
                            f"resource_version {resume_rv} not yet "
                            f"observed by this replica (applied "
                            f"{self._rv})"
                        )
                    # the consumer is AHEAD of this server: it observed
                    # versions a crash rolled back (fanout raced the WAL
                    # flush, or fsync=False lost the tail).  Honoring the
                    # resume would silently skip every re-issued version —
                    # force the relist instead.
                    raise HistoryCompacted(
                        f"resource_version {resume_rv} is ahead of this "
                        f"server (at {self._rv}): recovered from older "
                        f"state; relist required"
                    )
                w = Watch(self, kind, self._watch_queue_events)
                w.start_rv = resume_rv
                # COPIES, not the ring's own events: a resumed HTTP
                # stream memoizes wire bytes onto whatever it serializes
                # (event_wire_chunk), and memos on ring-resident events
                # would pin past the ring's byte budget invisibly.  The
                # copy costs one dataclass per replayed event per
                # resumer — resumes are rare by design.
                w._deliver_many(
                    [
                        WatchEvent(ev.type, ev.obj, rv=ev.rv)
                        for ev, _cost in self._history.get(kind, ())
                        if ev.rv > resume_rv
                    ]
                )
                self._watches.setdefault(kind, []).append(w)
                with w._cond:
                    # the queued history replay stays exempt from the
                    # live bound until the consumer drains it (FIFO)
                    w._replay_pending = len(w._events)
                    w._live = True
                return w, []
            w = Watch(self, kind, self._watch_queue_events)
            w.start_rv = self._visible_rv()
            objs = list(self._objects.get(kind, {}).values())
            snapshot = [o.clone() for o in objs] if clone_snapshot else objs
            if send_initial:
                w._deliver_many(
                    [
                        WatchEvent(
                            EventType.ADDED, obj.clone(),
                            rv=obj.metadata.resource_version,
                        )
                        for obj in objs
                    ]
                )
            self._watches.setdefault(kind, []).append(w)
            with w._cond:
                # the queued snapshot replay stays exempt from the live
                # bound until the consumer drains it (FIFO)
                w._replay_pending = len(w._events)
                w._live = True
        return w, snapshot

    def _watch_cow(
        self,
        kind: str,
        snap: _ReadSnapshot,
        send_initial: bool,
        clone_snapshot: bool,
    ) -> Tuple[Watch, List[Any]]:
        """Full-snapshot watch registration off the read plane (ISSUE
        14): the replay events (shared per snapshot, wire-memoizable —
        a relist storm's N registrations encode each object once) and
        the returned snapshot are built from the immutable COW view
        OFF the lock; only the registration itself takes it, re-checking
        that no publish swapped the snapshot underneath (a swap means
        events fanned out that this replay does not contain — rebuild
        from the fresh view; each retry races exactly one publish, so
        the loop converges under any finite write rate)."""
        w = Watch(self, kind, self._watch_queue_events)
        while True:
            events = snap.replay_events_for(kind) if send_initial else None
            with self._lock:
                if self._snap is not snap:
                    snap = self._snap
                    continue  # lost the race with a publish; rebuild
                w.start_rv = snap.rv
                if events:
                    w._deliver_many(events)
                self._watches.setdefault(kind, []).append(w)
                with w._cond:
                    # the queued snapshot replay stays exempt from the
                    # live bound until the consumer drains it (FIFO)
                    w._replay_pending = len(w._events)
                    w._live = True
            objs = snap.maps.get(kind, {}).values()
            if clone_snapshot:
                return w, [o.clone() for o in objs]
            return w, list(objs)

    def _remove_watch(self, kind: str, w: Watch) -> None:
        with self._lock:
            lst = self._watches.get(kind, [])
            if w in lst:
                lst.remove(w)
