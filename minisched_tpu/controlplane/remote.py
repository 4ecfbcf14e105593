"""Scheduler-over-the-wire: a store/client facade backed by the REST API.

In the reference, the scheduler's informers list/watch THROUGH the HTTP
boundary of the in-process apiserver — client-go against the httptest
server (/root/reference/k8sapiserver/k8sapiserver.go:45-48,57-62;
/root/reference/scheduler/scheduler.go:54,72-73) — so every event the
engine consumes crosses a serialization + stream boundary.  This module
gives the TPU engine the same mode: ``RemoteStore`` speaks the
httpserver's REST + chunked-watch protocol and exposes the subset of the
ObjectStore surface the informer machinery and the engine consume
(watch/list/create/get/update/delete), and ``RemoteClient`` is the Client
facade over it, so ``SchedulerService(RemoteClient(base_url))`` runs the
WHOLE scheduling path — informers, queue, waves, binds — over the wire.

Batch binds ride one ``POST /api/v1/bindings`` request (the wave engine
commits thousands of placements per cycle; one HTTP round-trip per bind
would serialize the wave).  The per-item semantics equal the in-process
``bind_many``: AlreadyBound / missing-pod errors are returned per entry,
never aborting the rest.

Transport (ISSUE 9): every request rides a small keep-alive connection
pool (``controlplane/httppool.HTTPConnectionPool``) instead of a
per-call ``urlopen`` — request latency decouples from TCP connection
setup, and a stale pooled socket (server closed it while idle) is
reopened retry-safely without burning the caller's backoff budget.
Watch streams share the pool's socket setup on dedicated connections;
their read timeout is ``RemoteStore(watch_read_timeout_s=)``.
"""

from __future__ import annotations

import http.client
import json
import random
import threading
import time
import urllib.error
from typing import Any, List, Optional, Tuple

from minisched_tpu.api.objects import Binding
from minisched_tpu.controlplane.checkpoint import _decode, _encode
from minisched_tpu.controlplane.httppool import (
    DEFAULT_MAX_IDLE,
    HTTPConnectionPool,
    bind_already_ours,
    shared_pool,
)
from minisched_tpu.controlplane.client import (
    AlreadyBound,
    OutOfCapacity,
    _NodeAPI,
    _PodAPI,
)
from minisched_tpu.controlplane.store import (
    Conflict,
    EventType,
    HistoryCompacted,
    NotLeader,
    NotYetObserved,
    ShardFrozen,
    ShardFrozenTimeout,
    StorageDegraded,
    WatchEvent,
    WrongShard,
)
from minisched_tpu.faults import InjectedFault
from minisched_tpu.observability import counters
from minisched_tpu.utils.retry import backoff_delays

_COLLECTIONS = {
    "Node": "nodes",
    "Pod": "pods",
    "PersistentVolume": "persistentvolumes",
    "PersistentVolumeClaim": "persistentvolumeclaims",
    "Lease": "leases",
    "Event": "events",
}
_CLUSTER_SCOPED = {"Node", "PersistentVolume"}


def _kind_types():
    from minisched_tpu.controlplane.httpserver import REST_KINDS

    return REST_KINDS


class RemoteWatch:
    """A store.Watch-shaped consumer of one chunked watch stream: a
    daemon reader thread decodes JSON lines into WatchEvents; ``next`` /
    ``next_batch`` / ``stop`` match the in-process Watch surface the
    informer dispatch thread drives."""

    def __init__(
        self,
        pool: HTTPConnectionPool,
        path: str,
        kind: str,
        read_timeout_s: float = 3600.0,
    ):
        self._cond = threading.Condition()
        self._events: List[WatchEvent] = []
        self._stopped = False
        self._explicit_stop = False
        self._typ = _kind_types()[kind]
        #: snapshot-replay count from the server's SYNC first line, set by
        #: the reader thread; ``initial_count()`` blocks on it — this is
        #: what makes the informer's sync barrier exact (a LIST taken
        #: before/after opening the stream can't be atomic with it)
        self._sync_count: Optional[int] = None
        #: the store rv this stream's snapshot reflects (SYNC line) —
        #: same role as the in-process Watch.start_rv
        self.start_rv = 0
        # the pool builds the connection (same host/port/timeout
        # plumbing as request traffic) but the stream OWNS it: a watch
        # monopolizes its socket until death, never the idle stack.
        # ``read_timeout_s`` bounds each blocking read (the old
        # hard-coded 3600.0 — RemoteStore(watch_read_timeout_s=)).
        self._conn, self._resp = pool.open_stream(path, read_timeout_s)
        if self._resp.status != 200:
            body = self._resp.read().decode(errors="replace")
            self._conn.close()
            if self._resp.status == 410:
                # resume asked for compacted history: the caller must
                # relist (HistoryCompacted == the in-process store's)
                raise HistoryCompacted(body)
            if self._resp.status == 504 and "not yet observed" in body:
                # a lagging FOLLOWER has not applied the resume cursor
                # yet: retryable — the caller re-opens here later or on
                # a fresher replica; relisting would be wasted work
                raise NotYetObserved(body)
            raise RuntimeError(f"HTTP {self._resp.status}: {body}")
        self._thread = threading.Thread(
            target=self._read, name=f"remote-watch-{kind}", daemon=True
        )
        self._thread.start()

    def _read(self) -> None:
        try:
            # http.client de-chunks HTTP/1.1 transfer-encoding; readline
            # gives one JSON event (or a bare keepalive newline) per line
            for raw in self._resp:
                line = raw.strip()
                if not line:
                    continue
                msg = json.loads(line)
                if msg["type"] == "SYNC":
                    with self._cond:
                        self.start_rv = int(msg.get("rv", 0))
                        self._sync_count = int(msg["count"])
                        self._cond.notify_all()
                    continue
                ev = WatchEvent(
                    EventType(msg["type"]),
                    _decode(self._typ, msg["object"]),
                    rv=int(msg.get("rv", 0)),
                )
                with self._cond:
                    if self._stopped:
                        return
                    self._events.append(ev)
                    self._cond.notify_all()
        except Exception:
            if self._explicit_stop:
                pass  # shutdown teardown: expected
            else:
                import traceback

                traceback.print_exc()  # network failure: the informer's
                # reconnect path re-lists; the trace says why it had to
        finally:
            with self._cond:
                self._stopped = True
                self._cond.notify_all()

    def initial_count(self, timeout: float = 30.0) -> int:
        """Block until the server's SYNC line arrives (how many snapshot
        events this stream replays before live events)."""
        import time as _time

        deadline = _time.monotonic() + timeout
        with self._cond:
            while self._sync_count is None and not self._stopped:
                remaining = deadline - _time.monotonic()
                if remaining <= 0 or not self._cond.wait(remaining):
                    break
            if self._sync_count is None:
                raise RuntimeError("watch stream sent no SYNC line")
            return self._sync_count

    def next(self, timeout: Optional[float] = None) -> Optional[WatchEvent]:
        batch = self._wait(timeout, take_all=False)
        return batch[0] if batch else None

    def next_batch(self, timeout: Optional[float] = None) -> List[WatchEvent]:
        return self._wait(timeout, take_all=True)

    def _wait(self, timeout: Optional[float], take_all: bool) -> List[WatchEvent]:
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
            if not self._events:
                return []
            if take_all:
                out, self._events = self._events, []
                return out
            return [self._events.pop(0)]

    def stop(self) -> None:
        with self._cond:
            self._explicit_stop = True
            self._stopped = True
            self._cond.notify_all()
        try:
            self._resp.close()  # unblocks the reader thread
        except Exception:
            pass
        try:
            self._conn.close()
        except Exception:
            pass

    @property
    def stopped(self) -> bool:
        return self._stopped


#: transport-level failures worth a retry: the request may never have
#: reached the server (connection refused/reset, DNS) or the response was
#: lost (timeout, dropped stream).  HTTPError is NOT here — it means the
#: server answered; only its 5xx family is retried, inside _req_ex.
_TRANSIENT_ERRORS = (
    urllib.error.URLError,
    ConnectionError,
    TimeoutError,
    http.client.HTTPException,
    InjectedFault,
    OSError,
)


def delete_each(store: Any, kind: str, keys: List[Any]) -> List[Any]:
    """``delete_many`` for a store that deletes a key a request: a loop
    over ``store.delete``, results aligned with ``keys`` as the
    in-process stores answer — None, or that item's exception (404 is
    KeyError) — so a caller has one call whatever store it was handed."""
    out: List[Any] = []
    for namespace, name in keys:
        try:
            out.append(store.delete(kind, namespace, name))
        except Exception as err:  # noqa: BLE001 — returned, not lost
            out.append(err)
    return out


class RemoteStore:
    """The ObjectStore surface the informers + engine consume, over REST.

    Every call carries a per-call timeout and retries transient failures
    (connection resets, timeouts, HTTP 5xx) with jittered exponential
    backoff — a scheduler facing a lossy control plane must degrade into
    waiting, not crash or silently drop state.  Semantic errors (404/409:
    AlreadyBound, missing object, conflict) never retry.

    Retry safety: GET/PUT/DELETE are idempotent and replay blindly.  The
    batch-bind POST is made idempotent by the bind subresource's own
    precondition (spec.node_name must be unset — the store-side analog of
    a resource_version precondition): a retried bind whose first attempt
    actually landed comes back AlreadyBound *to the node we asked for*,
    which bind_many_remote converts to success.  Create POSTs are replayed
    too; a retry whose first attempt landed surfaces as a per-item
    conflict, which callers already handle per entry.
    """

    def __init__(
        self,
        base_url: str,
        timeout_s: float = 30.0,
        retries: int = 4,
        backoff_initial_s: float = 0.05,
        backoff_factor: float = 2.0,
        backoff_jitter: float = 0.2,
        retry_seed: Optional[int] = None,
        faults: Any = None,
        watch_read_timeout_s: float = 3600.0,
        pool_max_idle: int = DEFAULT_MAX_IDLE,
        endpoints: Optional[List[str]] = None,
        frozen_deadline_s: float = 10.0,
    ):
        self._base = base_url.rstrip("/")
        self._timeout_s = timeout_s
        self._retries = max(int(retries), 0)
        #: how long one call may wait out a frozen namespace (shard
        #: split window, DESIGN.md §31) before surfacing the typed
        #: ShardFrozenTimeout.  Its OWN budget, jitter-backed, separate
        #: from ``retries``: a healthy freeze is milliseconds, a dead
        #: coordinator's freeze thaws at the lease TTL — so this bounds
        #: the hammering without burning the transient-failure budget
        self._frozen_deadline_s = max(float(frozen_deadline_s), 0.0)
        self._backoff_initial_s = backoff_initial_s
        self._backoff_factor = backoff_factor
        self._backoff_jitter = backoff_jitter
        self._rng = random.Random(retry_seed)
        #: faults.FaultFabric consulted at ``remote.request`` before each
        #: attempt leaves the process (client-side connection reset)
        self._faults = faults
        #: per-read timeout on watch STREAMS (was hard-coded 3600.0): an
        #: informer behind a proxy that kills idle flows sooner can now
        #: match it and ride the reconnect/resume path instead of
        #: stalling a full hour
        self._watch_read_timeout_s = watch_read_timeout_s
        #: keep-alive transport: every request checks a connection out of
        #: this pool; watch streams use its socket setup on dedicated
        #: connections (see RemoteWatch).  The pool is SHARED per
        #: (host, port, timeout) across every RemoteStore/HTTPClient in
        #: the process — close() drops only our reference.
        self._pool = shared_pool(
            self._base, max_idle=pool_max_idle, timeout_s=timeout_s
        )
        # -- multi-endpoint read policy (DESIGN.md §29) -------------------
        # ``endpoints`` lists every replica façade of one replicated
        # plane.  With two or more, this store becomes endpoint-aware:
        # reads round-robin-failover across replicas carrying a
        # ``min_rv`` bound at the session rv (monotonic reads + read-
        # your-writes across endpoint switches), writes are routed to
        # the leader discovered via ``/repl/status``, and a dead or
        # fenced or lagging endpoint rotates instead of erroring.  With
        # one endpoint every path below is byte-identical to before.
        bases = [self._base]
        for e in endpoints or []:
            e = e.rstrip("/")
            if e not in bases:
                bases.append(e)
        self._endpoints = bases
        self._multi = len(bases) > 1
        self._pools = {self._base: self._pool}
        for b in bases[1:]:
            self._pools[b] = shared_pool(
                b, max_idle=pool_max_idle, timeout_s=timeout_s
            )
        self._ep_mu = threading.Lock()
        #: highest rv this SESSION has observed (response bodies: list
        #: rvs, object rvs on writes) — the monotonic floor every
        #: endpoint-routed read is bounded by
        self._session_rv = 0
        self._read_base = self._base
        self._leader_base: Optional[str] = None if self._multi else self._base

    # -- endpoint routing ---------------------------------------------------
    @property
    def session_rv(self) -> int:
        with self._ep_mu:
            return self._session_rv

    def observe_rv(self, rv: int) -> None:
        """Advance the session rv floor (never backwards).  Called from
        response decoding and by consumers that learn an rv out-of-band
        (an informer's delivered watch events)."""
        if rv <= 0:
            return
        with self._ep_mu:
            if rv > self._session_rv:
                self._session_rv = rv

    def _advance_from(self, out: Any) -> None:
        """Harvest rv watermarks from a decoded response body: list
        envelopes carry ``resource_version``, single objects carry
        ``metadata.resource_version``, batch responses carry them per
        item — an acked write advances the floor so the next read
        (wherever routed) must observe it (read-your-writes)."""
        if not isinstance(out, dict):
            return
        rv = out.get("resource_version")
        if rv is None:
            md = out.get("metadata")
            if isinstance(md, dict):
                rv = md.get("resource_version")
        best = int(rv or 0)
        items = out.get("items")
        if isinstance(items, list):
            for item in items:
                if not isinstance(item, dict):
                    continue
                obj = item if "metadata" in item else item.get("object")
                if isinstance(obj, dict):
                    md = obj.get("metadata")
                    if isinstance(md, dict):
                        best = max(best, int(md.get("resource_version") or 0))
        self.observe_rv(best)

    def _rotate_read(self, failed: str) -> None:
        """Move the read cursor off a failed/lagging endpoint (no-op if
        another thread already rotated past it)."""
        with self._ep_mu:
            if self._read_base == failed and self._multi:
                i = self._endpoints.index(failed)
                self._read_base = self._endpoints[
                    (i + 1) % len(self._endpoints)
                ]
                counters.inc("remote.read_failover")

    def _invalidate_leader(self, failed: str) -> None:
        with self._ep_mu:
            if self._leader_base == failed and self._multi:
                self._leader_base = None

    def _discover_leader(self) -> Optional[str]:
        """Probe every endpoint's ``/repl/status`` and return the base
        URL of the replica that currently leads.  A 404 means the plane
        is not replicated — that sole server IS the leader.  When no
        replica claims the role (mid-election), the fenced replicas'
        ``leader_hint`` is followed if it names a probed peer; else
        None, and the caller's backoff loop re-discovers."""
        statuses: dict = {}
        for base in self._endpoints:
            try:
                st, raw, _ = self._pools[base].request(
                    "GET", "/repl/status"
                )
            except _TRANSIENT_ERRORS:
                continue
            if st == 404:
                counters.inc("remote.leader_discoveries")
                return base
            if st != 200:
                continue
            try:
                doc = json.loads(raw)
            except ValueError:
                continue
            statuses[base] = doc
            if doc.get("role") == "leader" and not doc.get("fenced"):
                counters.inc("remote.leader_discoveries")
                return base
        by_id = {d.get("replica"): b for b, d in statuses.items()}
        for doc in statuses.values():
            hint = doc.get("leader_hint") or doc.get("leader") or ""
            if hint in by_id:
                counters.inc("remote.leader_discoveries")
                return by_id[hint]
        return None

    def _route(
        self, is_read: bool, path: str
    ) -> Tuple[HTTPConnectionPool, str, str]:
        """(pool, base, wire path) for one attempt.  Reads ride the
        current read endpoint with the session-rv ``min_rv`` bound
        appended; writes ride the discovered leader.  Raises OSError
        (transient — the retry loop backs off) when no leader is
        discoverable mid-election."""
        if not self._multi:
            return self._pool, self._base, path
        if path.startswith("/repl/") or path.startswith("/net/"):
            return self._pool, self._base, path
        if is_read:
            with self._ep_mu:
                base = self._read_base
                rv = self._session_rv
            wire = path
            if rv > 0:
                wire += ("&" if "?" in wire else "?") + f"min_rv={rv}"
            return self._pools[base], base, wire
        with self._ep_mu:
            base = self._leader_base
        if base is None:
            base = self._discover_leader()
            if base is None:
                raise OSError(
                    "no leader discoverable among "
                    f"{len(self._endpoints)} endpoints"
                )
            with self._ep_mu:
                self._leader_base = base
        return self._pools[base], base, path

    # -- plumbing -----------------------------------------------------------
    def _path(self, kind: str, namespace: str = "", name: str = "") -> str:
        coll = _COLLECTIONS[kind]
        if kind in _CLUSTER_SCOPED or not namespace:
            p = f"/api/v1/{coll}"
        else:
            p = f"/api/v1/namespaces/{namespace}/{coll}"
        return f"{p}/{name}" if name else p

    def _req(self, method: str, path: str, payload: Any = None) -> Any:
        return self._req_ex(method, path, payload)[0]

    def _req_ex(
        self, method: str, path: str, payload: Any = None
    ) -> Tuple[Any, int]:
        """(decoded response, attempts used beyond the first) — callers
        that must reason about idempotency (bind_many_remote) need to know
        whether a retry happened."""
        data = json.dumps(payload).encode() if payload is not None else None
        delays = backoff_delays(
            self._backoff_initial_s,
            self._backoff_factor,
            self._retries + 1,
            self._backoff_jitter,
            self._rng,
        )
        last_err: Optional[BaseException] = None
        is_read = method == "GET"
        # a frozen namespace (shard split window) gets its OWN
        # jitter-backed deadline loop below instead of consuming the
        # transient-failure attempt budget — hence the manual counter
        attempt = 0
        frozen_deadline: Optional[float] = None
        frozen_delays: Any = None
        while attempt < self._retries + 1:
            frozen = False
            status = None
            base: Optional[str] = None
            try:
                # endpoint routing happens PER ATTEMPT: a rotation or a
                # leader re-discovery between attempts re-routes the
                # retry instead of hammering the same dead replica
                pool, base, wire_path = self._route(is_read, path)
                if self._faults is not None:
                    self._faults.check("remote.request", path)
                # pooled keep-alive transport: reuses an idle socket when
                # one exists; a stale reuse is reopened inside the pool
                # without consuming one of OUR backoff attempts — but it
                # IS a retransmission, so it must count toward the
                # attempts bind_many_remote's idempotency dedup reasons
                # about (the first wire attempt may have committed
                # before the socket died)
                status, raw, replayed = pool.request(
                    method, wire_path, body=data
                )
            except _TRANSIENT_ERRORS as e:
                last_err = e
                if self._multi and base is not None:
                    # a dead endpoint fails over instead of burning the
                    # whole backoff budget against one corpse
                    if is_read:
                        self._rotate_read(base)
                    else:
                        self._invalidate_leader(base)
            if status is not None:
                if status < 400:
                    out = json.loads(raw)
                    self._advance_from(out)
                    return out, attempt + (1 if replayed else 0)
                body = raw.decode(errors="replace")
                if status == 409 and "already bound" in body:
                    raise AlreadyBound(body)
                if status == 409 and "stale resource_version" in body:
                    # semantic, never blindly retried: the caller must
                    # re-read before re-applying (see mutate)
                    raise Conflict(body)
                if status == 409 and "out of capacity" in body:
                    raise OutOfCapacity(body)
                if status in (404, 409):
                    raise KeyError(body)
                if status == 421:
                    # misdirected write: this plane is SHARDED and the
                    # namespace belongs to another leader group
                    # (DESIGN.md §30).  Semantic, never blindly retried —
                    # retrying the same group can never succeed.  The
                    # shard router (shards.ShardedStore) catches this,
                    # refreshes /shards/status topology and re-routes.
                    raise WrongShard(body)
                if status == 503 and "shard frozen" in body:
                    # bounded write-freeze window of a shard split:
                    # transient by contract (a healthy freeze is one
                    # namespace-filtered checkpoint ship long), but
                    # waited out under the frozen DEADLINE below — a
                    # dead coordinator's freeze only thaws at its lease
                    # TTL, and hammering it must end in a typed timeout
                    counters.inc("remote.shard_frozen_retry")
                    last_err = ShardFrozen(body)
                    frozen = True
                elif status == 503 and "not leader" in body:
                    # fenced replica (DESIGN.md §27): retrying HERE can
                    # never succeed.  Single-endpoint callers get the
                    # typed error immediately and re-discover themselves;
                    # an endpoint-aware store drops its cached leader and
                    # lets the next attempt re-route via /repl/status
                    counters.inc("storage.repl.not_leader_errors")
                    if not self._multi:
                        raise NotLeader(body)
                    self._invalidate_leader(base or "")
                    last_err = NotLeader(body)
                elif status == 504 and "not yet observed" in body:
                    # rv-bounded read ahead of this replica's applied rv
                    # (DESIGN.md §29): retryable by contract — rotate to
                    # a (hopefully fresher) replica and back off; the
                    # write we are bound by IS acked and will arrive
                    counters.inc("remote.not_yet_observed")
                    if self._multi and base is not None:
                        self._rotate_read(base)
                    last_err = NotYetObserved(body)
                elif status == 507:
                    # Insufficient Storage: the server's WAL is degraded
                    # (ENOSPC/EIO latch).  In the backoff set on purpose —
                    # the store probes its own recovery, so a later retry
                    # can succeed; when they all fail, the TYPED error
                    # surfaces so the engine parks waves instead of
                    # treating it as an unknown 5xx
                    counters.inc("storage.remote_degraded_retry")
                    last_err = StorageDegraded(body)
                elif status < 500:
                    raise RuntimeError(f"HTTP {status}: {body}")
                else:
                    last_err = RuntimeError(f"HTTP {status}: {body}")
            if frozen:
                # frozen-shard wait: its own deadline + jittered
                # backoff, NOT the generic attempt budget — the freeze
                # can outlast every transient-retry backoff combined
                # (lease TTL bound) without being a dead server
                now = time.monotonic()
                if frozen_deadline is None:
                    frozen_deadline = now + self._frozen_deadline_s
                    frozen_delays = backoff_delays(
                        self._backoff_initial_s,
                        self._backoff_factor,
                        1 << 20,
                        self._backoff_jitter,
                        self._rng,
                    )
                if now >= frozen_deadline:
                    counters.inc("remote.shard_frozen_timeout")
                    raise ShardFrozenTimeout(
                        f"remote {method} {path} namespace still frozen "
                        f"after its {self._frozen_deadline_s:.1f}s "
                        f"deadline: {last_err}"
                    )
                time.sleep(
                    min(
                        next(frozen_delays),
                        max(frozen_deadline - now, 0.0),
                    )
                )
                continue
            attempt += 1
            if attempt <= self._retries:
                counters.inc("remote.retry")
                time.sleep(next(delays))
        if isinstance(last_err, StorageDegraded):
            raise StorageDegraded(
                f"remote {method} {path} still degraded after "
                f"{self._retries + 1} attempts: {last_err}"
            )
        if isinstance(last_err, NotYetObserved):
            raise NotYetObserved(
                f"remote {method} {path} still unobserved after "
                f"{self._retries + 1} attempts: {last_err}"
            )
        if isinstance(last_err, ShardFrozen):
            raise ShardFrozen(
                f"remote {method} {path} still frozen after "
                f"{self._retries + 1} attempts: {last_err}"
            )
        if isinstance(last_err, NotLeader):
            raise NotLeader(
                f"remote {method} {path} found no writable leader after "
                f"{self._retries + 1} attempts: {last_err}"
            )
        raise RuntimeError(
            f"remote {method} {path} failed after {self._retries + 1} "
            f"attempts: {last_err}"
        )

    # -- store surface ------------------------------------------------------
    def watch(
        self,
        kind: str,
        send_initial: bool = True,
        resume_rv: Optional[int] = None,
    ) -> Tuple[RemoteWatch, List[Any]]:
        """(watch, snapshot placeholder): the stream replays the
        server-side snapshot as ADDED events and announces its exact
        count in a SYNC first line (atomic with the watch registration —
        a LIST taken separately can miscount across a delete in the gap
        and strand the informer's sync barrier).  The returned snapshot
        list is sized to that count; its entries are None — the informer
        only measures ``len``, and the objects themselves arrive through
        the stream.

        ``resume_rv``: resume from that resource_version instead of a
        full snapshot replay (``?resource_version=N`` on the wire) —
        SYNC count 0, history events stream in as live events.  Raises
        HistoryCompacted (the server's 410) when the tail is gone.

        Endpoint-aware stores open the stream on the current READ
        endpoint and fail over across replicas on connect failure or a
        lagging follower's NotYetObserved — combined with the server's
        exact rv>resume_rv replay, a consumer that resumes at its last
        delivered rv gets every event exactly once no matter which
        replica ends up serving the stream (DESIGN.md §29)."""
        path = f"{self._path(kind)}?watch=true"
        if resume_rv is not None:
            path += f"&resource_version={int(resume_rv)}"
        if not self._multi:
            w = RemoteWatch(
                self._pool, path, kind,
                read_timeout_s=self._watch_read_timeout_s,
            )
            return w, [None] * w.initial_count()
        last: Optional[BaseException] = None
        for _ in range(len(self._endpoints)):
            with self._ep_mu:
                base = self._read_base
            try:
                w = RemoteWatch(
                    self._pools[base], path, kind,
                    read_timeout_s=self._watch_read_timeout_s,
                )
                return w, [None] * w.initial_count()
            except (NotYetObserved,) + _TRANSIENT_ERRORS as e:
                last = e
                counters.inc("remote.watch_failover")
                self._rotate_read(base)
        raise last if last is not None else RuntimeError(
            f"watch {kind} open failed on every endpoint"
        )

    def list(self, kind: str) -> List[Any]:
        typ = _kind_types()[kind]
        out = self._req("GET", self._path(kind))
        return [_decode(typ, o) for o in out["items"]]

    def list_with_rv(self, kind: str) -> Tuple[List[Any], int]:
        """(items, store resource_version) — the rv is exactly the
        version the snapshot reflects (== ObjectStore.list_with_rv over
        the wire: epoch-consistent off the COW read plane, one lock hold
        in kill-switch mode).  The server may stream the body chunked
        from its shared list-payload cache (a relist storm costs it one
        encode); ``http.client`` dechunks transparently, so the decoded
        payload is byte-identical either way."""
        typ = _kind_types()[kind]
        out = self._req("GET", self._path(kind))
        return (
            [_decode(typ, o) for o in out["items"]],
            int(out.get("resource_version", 0)),
        )

    def get(self, kind: str, namespace: str, name: str) -> Any:
        typ = _kind_types()[kind]
        return _decode(typ, self._req("GET", self._path(kind, namespace, name)))

    def create(self, kind: str, obj: Any) -> Any:
        typ = _kind_types()[kind]
        return _decode(
            typ,
            self._req(
                "POST",
                self._path(kind, obj.metadata.namespace),
                _encode(obj),
            ),
        )

    def create_many(
        self, kind: str, objs: List[Any], return_objects: bool = True
    ) -> List[Any]:
        """Batch create: one collection POST per distinct namespace
        (cluster setup at one request per object ran ~380 obj/s — 29s of
        wall around a 1.7s measurement).  Per-namespace batching matters:
        the server rewrites every item's namespace to the URL's, so a
        mixed batch on one URL would silently move objects across
        namespaces.  Returns objects aligned with ``objs``; a per-item
        failure comes back as the exception.  ``return_objects=False``
        skips the response bodies entirely (the server answers ``{}`` per
        success) — seed paths that drop the created objects otherwise pay
        a full encode+transfer+decode per object for nothing."""
        if not objs:
            return []
        typ = _kind_types()[kind]
        by_ns: dict = {}
        for i, o in enumerate(objs):
            by_ns.setdefault(o.metadata.namespace, []).append(i)
        results: List[Any] = [None] * len(objs)
        for ns, idxs in by_ns.items():
            payload: dict = {"items": [_encode(objs[i]) for i in idxs]}
            if not return_objects:
                payload["return_objects"] = False
            out = self._req("POST", self._path(kind, ns), payload)
            for i, item in zip(idxs, out["items"]):
                err = item.get("error")
                if err is not None:
                    results[i] = (
                        StorageDegraded(err)
                        if item.get("type") == "StorageDegraded"
                        else KeyError(err)
                    )
                elif item.get("object") is not None:
                    results[i] = _decode(typ, item["object"])
                else:
                    results[i] = None
        return results

    def update(
        self, kind: str, obj: Any, expected_rv: Optional[int] = None
    ) -> Any:
        typ = _kind_types()[kind]
        path = self._path(kind, obj.metadata.namespace, obj.metadata.name)
        if expected_rv is not None:
            path += f"?expected_rv={int(expected_rv)}"
        return _decode(typ, self._req("PUT", path, _encode(obj)))

    def mutate(
        self,
        kind: str,
        namespace: str,
        name: str,
        fn: Any,
        max_conflict_retries: int = 16,
    ) -> Any:
        """Read-modify-write over the wire: GET, apply ``fn``, PUT with
        the read's resource_version as the ``expected_rv`` precondition —
        and on 409 Conflict, RE-READ and re-apply (get–mutate–retry).
        This is the store.mutate surface the in-process client gets from
        the lock-holding store, rebuilt on optimistic concurrency: two
        remote writers can no longer silently last-write-wins each other,
        and a bind/annotation racing this path surfaces as a retried
        merge instead of a lost update."""
        last: Optional[BaseException] = None
        for _ in range(max_conflict_retries + 1):
            obj = self.get(kind, namespace, name)
            rv = obj.metadata.resource_version
            updated = fn(obj) or obj
            try:
                return self.update(kind, updated, expected_rv=rv)
            except Conflict as err:
                counters.inc("remote.conflict_retry")
                last = err
        raise RuntimeError(
            f"remote mutate {kind} {namespace}/{name} still conflicting "
            f"after {max_conflict_retries + 1} attempts: {last}"
        )

    def delete(self, kind: str, namespace: str, name: str) -> None:
        self._req("DELETE", self._path(kind, namespace, name))

    def delete_many(self, kind: str, keys: List[Any]) -> List[Any]:
        """The store's batch delete over the wire: a ``DELETE`` a key
        (the façade has no batch DELETE); see ``delete_each``."""
        return delete_each(self, kind, keys)

    def close(self) -> None:
        """Drop the pools' idle keep-alive sockets (open watch streams
        own their connections and are unaffected)."""
        for pool in self._pools.values():
            pool.close()

    def bind_many_remote(
        self,
        bindings: List[Binding],
        return_objects: bool = True,
        batch_id: Optional[str] = None,
        ack_ids: Optional[List[str]] = None,
        assume_retry: bool = False,
    ) -> List[Any]:
        import uuid

        # one ack identity per LOGICAL batch: _req_ex serializes the
        # payload once before its retry loop, so every transport retry
        # carries the same batch_id and the server answers already-acked
        # entries from its registry instead of re-running them.
        # ``batch_id``/``ack_ids`` let a caller that SPLITS one logical
        # batch across servers (shards.ShardedStore's two-shard commit)
        # pin the identity itself: the per-item ack id stays stable even
        # when a topology change re-partitions the sub-batches, so a
        # chased retry still dedups against the registry entry the first
        # dispatch recorded.  ``assume_retry`` widens the AlreadyBound→
        # success conversion to attempt 0 — only safe when the CALLER
        # knows this call is a re-dispatch of an already-attempted batch.
        items = []
        for i, b in enumerate(bindings):
            it: dict = {
                "namespace": b.pod_namespace,
                "name": b.pod_name,
                "node_name": b.node_name,
            }
            if b.expected_rv is not None:
                it["expected_rv"] = b.expected_rv
            if ack_ids is not None:
                it["ack"] = str(ack_ids[i])
            items.append(it)
        out, attempts = self._req_ex(
            "POST",
            "/api/v1/bindings",
            {
                "items": items,
                "return_objects": return_objects,
                "batch_id": batch_id or uuid.uuid4().hex,
            },
        )
        if assume_retry:
            attempts = max(attempts, 1)
        from minisched_tpu.api.objects import Pod

        results: List[Any] = []
        for b, item in zip(bindings, out["items"]):
            if item.get("acked"):
                # answered from the server's ack registry: the FIRST
                # attempt's recorded outcome, not a re-execution
                counters.inc("remote.bind_ack_replayed")
            err = item.get("error")
            if err is not None:
                if item.get("type") == "Conflict":
                    results.append(Conflict(err))
                    continue
                if item.get("type") == "OutOfCapacity":
                    # the node lost a capacity race to a peer engine's
                    # bind: per-item, retriable — the engine requeues the
                    # pod against refreshed state
                    results.append(OutOfCapacity(err))
                    continue
                if item.get("type") == "StorageDegraded":
                    # the server's disk gave out mid-batch: this bind
                    # never committed — typed and retriable, the engine
                    # parks the pod and retries once the store re-arms
                    results.append(StorageDegraded(err))
                    continue
                if item.get("type") == "AlreadyBound":
                    # idempotent-retry guard: a retried request whose FIRST
                    # attempt committed before its response was lost comes
                    # back AlreadyBound to the node we asked for — that is
                    # OUR bind landing, not a conflict.  The bind
                    # subresource's unset-node_name precondition is what
                    # makes this conversion safe (a genuine conflict names
                    # a different node, or fires on the un-retried first
                    # attempt and stays an error).  One shared rule with
                    # HTTPClient.bind: httppool.bind_already_ours.
                    ours = bind_already_ours(
                        item.get("node") or "", err, b.node_name
                    )
                    if attempts > 0 and ours:
                        counters.inc("remote.bind_retry_dedup")
                        results.append(None)
                        continue
                    results.append(AlreadyBound(err))
                else:
                    results.append(KeyError(err))
            elif item.get("object") is not None:
                results.append(_decode(Pod, item["object"]))
            else:
                results.append(None)
        return results


class _RemotePodAPI(_PodAPI):
    """The Pod facade over the wire: everything rides the RemoteStore's
    REST calls; binds take the batch endpoint (one request per wave),
    batch creates one collection POST."""

    def bind_many(
        self, bindings: List[Binding], return_objects: bool = True
    ) -> List[Any]:
        return self._store.bind_many_remote(
            bindings, return_objects=return_objects
        )

    def create_many(
        self, pods: List[Any], return_objects: bool = True
    ) -> List[Any]:
        for p in pods:
            if not p.metadata.namespace:
                p.metadata.namespace = self._ns
        out = []
        for res in self._store.create_many("Pod", pods, return_objects):
            if isinstance(res, BaseException):
                raise res
            out.append(res)
        return out


class _RemoteNodeAPI(_NodeAPI):
    """Node facade over the wire with the batch-create collection POST."""

    def create_many(
        self, nodes: List[Any], return_objects: bool = True
    ) -> List[Any]:
        for n in nodes:
            n.metadata.namespace = ""
        out = []
        for res in self._store.create_many("Node", nodes, return_objects):
            if isinstance(res, BaseException):
                raise res
            out.append(res)
        return out


class RemoteClient:
    """Client facade whose every operation crosses the HTTP boundary —
    hand it to SchedulerService to run the whole scheduling path
    over the wire (scheduler.go:54,72-73 against k8sapiserver.go:45-48).
    Keyword arguments (timeouts, retry policy, fault fabric) pass through
    to RemoteStore."""

    def __init__(self, base_url: str, **kwargs: Any):
        self.store = RemoteStore(base_url, **kwargs)

    def nodes(self) -> _RemoteNodeAPI:
        return _RemoteNodeAPI(self.store)

    def pods(self, namespace: str = "default") -> _RemotePodAPI:
        return _RemotePodAPI(self.store, namespace)
