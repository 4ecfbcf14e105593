"""HTTP API façade: the control plane served over REST.

Re-creates the reference's L1 boundary — a real kube-apiserver served
through an ``httptest.Server`` with health polling
(k8sapiserver/k8sapiserver.go:43-71, :231-249) — as a stdlib
ThreadingHTTPServer over the in-memory ObjectStore.  Kubernetes-shaped
routes:

    GET    /healthz                                   → 200 "ok"
    GET    /api/v1/nodes                              → list
    GET    /api/v1/nodes/{name}                       → get
    POST   /api/v1/nodes                              → create
    PUT    /api/v1/nodes/{name}                       → update
    DELETE /api/v1/nodes/{name}                       → delete
    (same under /api/v1/namespaces/{ns}/pods)
    POST   /api/v1/namespaces/{ns}/pods/{name}/binding → bind subresource
    GET    /api/v1/...?watch=true                     → JSON-lines stream

Objects serialize with the checkpoint codec (language-neutral JSON).
``start_api_server`` mirrors ``StartAPIServer(etcdURL) → (config,
shutdownFn)``: returns (server, base_url, shutdown_fn) after polling
/healthz until it answers, exactly like the reference does
(k8sapiserver.go:232-244).  ``HTTPClient`` gives scenarios the same
facade as the in-process Client, over the wire.
"""

from __future__ import annotations

import json
import os
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Dict, List, Optional, Tuple
from urllib.parse import parse_qs

from minisched_tpu.api.objects import Binding, Node, Pod
from minisched_tpu.controlplane.checkpoint import (
    KIND_TYPES,
    _decode,
    _encode,
    _plan_for,
)
from minisched_tpu.controlplane.client import (
    AlreadyBound,
    Client,
    OutOfCapacity,
)
from minisched_tpu.controlplane.store import (
    Conflict,
    HistoryCompacted,
    NotLeader,
    NotYetObserved,
    ObjectStore,
    ShardFrozen,
    StorageDegraded,
    WrongShard,
)
from minisched_tpu.observability import profiling

profiling.register_spans(
    "http.create", "http.create_read", "http.create_decode",
    "http.create_store", "http.create_respond",
    "http.delete", "http.delete_store", "http.delete_respond",
)


def _kind_for(collection: str) -> str:
    return {"nodes": "Node", "pods": "Pod",
            "persistentvolumes": "PersistentVolume",
            "persistentvolumeclaims": "PersistentVolumeClaim",
            "leases": "Lease",
            "events": "Event"}[collection]


#: kinds the REST façade serves: the durable roster plus the volatile
#: Event kind (the reference's broadcaster records eventsv1 objects a
#: client can list — scheduler/scheduler.go:55-59; Events stay out of the
#: WAL codec's KIND_TYPES on purpose)
from minisched_tpu.api import objects as _objects  # noqa: E402

REST_KINDS = {**KIND_TYPES, "Event": _objects.Event}

# KIND_TYPES were planned as checkpoint was imported: with this one, every
# kind a request can name has its decode plan before the first request
_plan_for(_objects.Event)


#: kinds stored under namespace "" regardless of URL/body (kube semantics)
_CLUSTER_SCOPED = {"Node", "PersistentVolume"}


def _fixup_namespace(kind: str, ns: str, obj: Any) -> None:
    """The one namespace rule for creates (single and batch): cluster-
    scoped kinds normalize to ""; otherwise the URL namespace wins (kube
    semantics), else the body's, else "default"."""
    if kind in _CLUSTER_SCOPED:
        obj.metadata.namespace = ""
    elif ns:
        obj.metadata.namespace = ns
    elif not obj.metadata.namespace:
        obj.metadata.namespace = "default"


def _route_label(path: str) -> str:
    """Low-cardinality route label for the ``http.request_s`` histogram:
    the SHAPE of the path (collection + name/subresource markers), never
    raw object names — a million pods must not mint a million label
    children."""
    if not path.startswith("/api/"):
        return path if path in (
            "/healthz", "/metrics", "/debug/trace"
        ) else "other"
    try:
        kind, _ns, name, sub = _route(path)
    except (KeyError, ValueError):
        return "unroutable"
    label = kind.lower()
    if name:
        label += "/{name}"
    if sub:
        label += "/" + sub
    return label


def _route(path: str):
    """→ (kind, namespace, name, subresource) — name/sub may be ''."""
    parts = [p for p in path.split("/") if p]
    # api/v1/nodes[/name]  |  api/v1/namespaces/ns/pods[/name[/binding]]
    if parts[:2] != ["api", "v1"] or len(parts) < 3:
        raise KeyError(path)
    rest = parts[2:]
    try:
        if rest[0] == "namespaces":
            ns, collection, *tail = rest[1:]
        else:
            ns, (collection, *tail) = "", rest
    except (IndexError, ValueError):
        raise KeyError(path)
    name = tail[0] if tail else ""
    sub = tail[1] if len(tail) > 1 else ""
    return _kind_for(collection), ns, name, sub


#: bound on the per-server binding ack registry (entries, FIFO): big
#: enough that every in-flight wave's retries land inside it, small
#: enough that a soak never grows without bound
_ACK_REGISTRY_CAP = 65536

#: chunk size for list bodies streamed from the shared COW cache — big
#: enough that the framing overhead is noise, small enough that a slice
#: of a multi-MB payload never parks one writev for seconds
_LIST_CHUNK_BYTES = 256 * 1024


def _chunk_frame(data: bytes) -> bytes:
    """One chunked-transfer frame — the ONE definition of the watch
    stream's wire framing (event chunks, keepalives, SYNC all use it;
    the terminal ``0\\r\\n\\r\\n`` is the standard end marker)."""
    return f"{len(data):X}\r\n".encode() + data + b"\r\n"


def event_wire_chunk(ev: Any) -> bytes:
    """The watch verb's framed wire bytes for one event — JSON line plus
    the chunked-transfer framing — encoded ONCE and memoized on the event
    object itself (store fanout hands every watcher the SAME WatchEvent
    instance, so N streams serializing one mutation cost one encode, not
    N; ISSUE 8).  The line carries no watcher-specific state by
    construction: namespace filtering happens BEFORE this call, and the
    payload is (type, object, rv) only.  ``watch.fanout.encoded`` counts
    first encodes, ``watch.fanout.shared`` the reuses — the fanout
    microbench gates on encoded staying O(events), not O(events ×
    watchers).  (Two streams racing the first encode may both pay it —
    benign: last write wins on identical bytes.)"""
    from minisched_tpu.observability import counters

    wire = ev.wire
    if wire is None:
        wire = _chunk_frame(
            json.dumps(
                {"type": ev.type.value, "object": _encode(ev.obj), "rv": ev.rv}
            ).encode()
            + b"\n"
        )
        ev.wire = wire
        counters.inc("watch.fanout.encoded")
    else:
        counters.inc("watch.fanout.shared")
    return wire


class _WatchHTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer that can DETACH a request socket: a watch
    handler hands its connection to the selector stream loop (ISSUE 9)
    and returns, so ``shutdown_request`` must skip sockets the loop now
    owns — the default would send FIN and close the stream under it."""

    #: socketserver's default listen backlog is 5: a 1k-watcher connect
    #: burst overflows it, the kernel drops SYNs, and every affected
    #: client pays a ≥1s retransmission before the accept loop (which
    #: drains fine) ever sees it — measured 150ms MEAN establishment at
    #: 120 serial connects.  A plane built for thousands of watchers
    #: queues the burst instead.
    request_queue_size = 1024

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._detach_lock = threading.Lock()
        self._detached: set = set()

    def detach_socket(self, sock) -> None:
        with self._detach_lock:
            self._detached.add(sock)

    def undetach_socket(self, sock) -> None:
        """Give a socket back to normal teardown (adopt raced a loop
        shutdown)."""
        with self._detach_lock:
            self._detached.discard(sock)

    def shutdown_request(self, request) -> None:
        with self._detach_lock:
            if request in self._detached:
                self._detached.discard(request)
                return  # the stream loop owns this socket now
        super().shutdown_request(request)


class _PendingDelete:
    """One ``DELETE`` in the combiner's queue."""

    __slots__ = ("kind", "key", "outcome", "done", "leads")

    def __init__(self, kind: str, key: Tuple[str, str]) -> None:
        self.kind, self.key = kind, key
        self.outcome: Optional[BaseException] = None
        #: set when a leader has landed it (None: it led from the start)
        self.done: Optional[threading.Event] = None
        self.leads = False


class _DeleteCombiner:
    """Single-object ``DELETE``s that arrive together land as ONE
    ``store.delete_many`` a kind.

    The API deletes one object a request, and every handler thread used
    to take the store's lock for its own: beside a scheduler that shares
    the interpreter, each hand-over of that lock waits for the
    interpreter too, so deletes went through one at a time however many
    connections sent them (27 ms each in ``store.delete`` for 1.2 ms of
    CPU, PERF.md section 6, PR 35).  Here the first request to arrive
    leads: it lands whatever has queued behind it (its own first) in one
    transaction, wakes the requests it served, and hands the lead to the
    next one waiting.  A request is answered only after its delete is
    applied, with the outcome ``store.delete`` would have given it."""

    def __init__(self, store: ObjectStore) -> None:
        self._store = store
        self._mu = threading.Lock()
        self._queued: List[_PendingDelete] = []
        self._led = False  # some request is leading

    def delete(self, kind: str, namespace: str, name: str) -> None:
        mine = _PendingDelete(kind, (namespace, name))
        with self._mu:
            self._queued.append(mine)
            if self._led:
                mine.done = threading.Event()
            else:
                self._led = mine.leads = True
        if mine.done is not None:
            mine.done.wait()
        if mine.leads:
            self._land()
        if mine.outcome is not None:
            raise mine.outcome

    def _land(self) -> None:
        """The leader's turn: one transaction a kind over everything
        queued (the leader's own request is among it), then the lead
        goes to whoever queued meanwhile, or to nobody."""
        with self._mu:
            batch, self._queued = self._queued, []
        by_kind: Dict[str, List[_PendingDelete]] = {}
        for item in batch:
            by_kind.setdefault(item.kind, []).append(item)
        for kind, items in by_kind.items():
            try:
                outcomes = self._store.delete_many(
                    kind, [item.key for item in items]
                )
            except Exception as err:  # noqa: BLE001 — every request of
                # the transaction answers it (NotLeader, StorageDegraded)
                outcomes = [err] * len(items)
            for item, outcome in zip(items, outcomes):
                item.outcome = outcome
        with self._mu:
            heir = self._queued[0] if self._queued else None
            if heir is None:
                self._led = False
            else:
                heir.leads = True
        for item in batch:
            if item.done is not None:
                item.done.set()
        if heir is not None:
            heir.done.set()


class _Handler(BaseHTTPRequestHandler):
    store: ObjectStore = None  # set by start_api_server
    deletes: _DeleteCombiner = None  # set by start_api_server
    active_watches = None  # set by start_api_server (set + lock)
    watch_lock = None
    faults = None  # optional faults.FaultFabric, set by start_api_server
    ack_registry = None  # set by start_api_server: ack id → response entry
    ack_order = None  # FIFO of ack ids for eviction
    ack_lock = None
    #: streamloop.StreamLoop when the selector fanout path is on (set by
    #: start_api_server; None = thread-per-watcher, the exact old path)
    stream_loop = None
    #: repl.ReplRuntime when this server fronts a replicated store
    #: (DESIGN.md §27); None = the /repl/* routes answer 404
    repl = None
    #: shards.ShardInfo when this server fronts ONE leader group of a
    #: sharded write plane (DESIGN.md §30); None = unsharded — the
    #: /shards/* routes answer 404 and no write is ever shard-refused,
    #: which is exactly the MINISCHED_SHARDS=1 parity invariant
    shard = None
    protocol_version = "HTTP/1.1"

    def log_message(self, *args) -> None:  # quiet
        pass

    def _inject_fault(self) -> bool:
        """Consult the fabric before routing: ``http.reset`` closes the
        connection without a single response byte (the client sees a
        transport error — retries must assume the request MAY have been
        processed, which is why only pre-commit injection and idempotent
        verbs are safe to replay blindly; see remote.py); ``http.500``
        answers 503.  Both fire BEFORE the store is touched, so a retried
        request never finds half-applied state.  /healthz is exempt —
        readiness polling is the one probe chaos must not lie to."""
        f = self.faults
        if f is None:
            return False
        path = self.path.partition("?")[0]
        if path == "/healthz":
            return False
        if f.should_fire("http.reset", path):
            try:
                self.connection.close()
            except OSError:
                pass
            self.close_connection = True
            return True
        if f.should_fire("http.500", path):
            # the body may be unread; keep-alive reuse would misparse it
            # as the next request's start line
            self.close_connection = True
            self._error(503, "injected: control plane unavailable")
            return True
        return False

    def _send(
        self, code: int, payload: Any, rv: Optional[int] = None
    ) -> None:
        body = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        if rv is not None:
            # the rv watermark this response's state reflects — the
            # read plane's freshness stamp (DESIGN.md §29): a client
            # reading across replicas advances its session rv from it
            # and bounds later reads with ?min_rv= so reads never go
            # backwards across an endpoint switch
            self.send_header("X-Minisched-RV", str(rv))
        # headers and body in ONE write: end_headers() puts the headers on
        # the wire by themselves, and with Nagle on the body then waits
        # for the client's delayed ACK — 40 ms a small answer on a
        # keep-alive connection, which is every DELETE and every create
        self._headers_buffer.append(b"\r\n")
        self._headers_buffer.append(body)
        self.flush_headers()

    def _body(self) -> Any:
        n = int(self.headers.get("Content-Length", 0))
        return json.loads(self.rfile.read(n)) if n else {}

    def _error(self, code: int, msg: str) -> None:
        self._send(code, {"error": msg})

    def _int_param(self, query: str, name: str) -> Optional[int]:
        """One integer query parameter (None when absent).  A non-integer
        value answers the 400 itself and re-raises ValueError so the verb
        handler just returns — the parse/error behavior cannot drift
        between GET's resource_version and PUT's expected_rv."""
        if not query:
            return None
        params = parse_qs(query)
        if name not in params:
            return None
        try:
            return int(params[name][0])
        except ValueError:
            self._error(400, f"{name} must be an integer")
            raise

    def _shard_guard(self, kind: str, *namespaces: str) -> bool:
        """Refuse a write whose namespace this leader group does not own
        (421 ``wrong shard``) or that sits inside a split's freeze
        window (503 ``shard frozen``) — BEFORE the store executes
        anything, so a refused request is always safe to re-route or
        retry whole.  True = proceed.  Unsharded servers (shard None)
        never refuse: the kill-switch parity path."""
        sh = self.shard
        if sh is None:
            return True
        from minisched_tpu.observability import counters

        eff = [
            "" if kind in _CLUSTER_SCOPED else (ns or "default")
            for ns in namespaces
        ]
        try:
            for ns in dict.fromkeys(eff):
                sh.check_write(ns)
        except WrongShard as e:
            counters.inc("storage.shard.wrong_shard_refused")
            self._error(421, str(e))
            return False
        except ShardFrozen as e:
            counters.inc("storage.shard.frozen_refused")
            self._error(503, str(e))
            return False
        # accepted: feed the autosplit watcher's hottest-namespace tally
        sh.note_writes(dict.fromkeys(eff))
        return True

    def _observe_request(self, verb: str, path: str, t0: float) -> None:
        from minisched_tpu.observability import hist

        hist.observe(
            "http.request_s", time.monotonic() - t0,
            verb=verb, route=_route_label(path),
        )

    def do_GET(self) -> None:
        t0 = time.monotonic()
        path, _, query = self.path.partition("?")
        try:
            self._handle_get(path, query)
        finally:
            # long-lived watch streams are not requests; their latency
            # story is watch.delivery_lag_s, not http.request_s — and the
            # replication tail is the same shape (storage.repl_ship_s)
            if "watch=true" not in query and path != "/repl/stream":
                self._observe_request("GET", path, t0)

    def _handle_get(self, path: str, query: str) -> None:
        if self._inject_fault():
            return
        if path == "/healthz":
            self._send(200, "ok")
            return
        if path == "/metrics":
            # Prometheus text exposition of the process-global registries
            # (counters + gauges + histograms; observability/hist)
            from minisched_tpu.observability import hist

            body = hist.render_prometheus().encode()
            self.send_response(200)
            self.send_header("Content-Type", "text/plain; version=0.0.4")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return
        if path == "/debug/trace":
            # flight-recorder dump: the bounded span ring as JSONL
            from minisched_tpu.observability import trace

            body = trace.dump_jsonl().encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/x-ndjson")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return
        if path == "/net/partition":
            # the partition nemesis's control surface (faults/net.py):
            # the chaos harness inspects a replica child's link table
            from minisched_tpu.faults.net import GLOBAL_NET

            self._send(200, GLOBAL_NET.describe())
            return
        if path.startswith("/repl/"):
            repl = self.repl
            if repl is None:
                self._error(404, "replication not enabled on this server")
            else:
                repl.handle_get(self, path, query)
            return
        if path.startswith("/shards/"):
            # the sharded write plane's discovery + split surface
            # (DESIGN.md §30), mirroring /repl/*'s 404-when-absent so a
            # router can probe any façade and learn whether it is sharded
            sh = self.shard
            if sh is None:
                self._error(404, "sharding not enabled on this server")
            elif path == "/shards/status":
                self._send(200, sh.describe(), rv=self.store.applied_rv())
            elif path == "/shards/handoff":
                ns = (parse_qs(query).get("namespace") or [""])[0]
                if not ns:
                    self._error(400, "handoff requires ?namespace=")
                    return
                from minisched_tpu.controlplane import shards as _shards

                self._send(200, _shards.build_handoff(self.store, ns))
            elif path == "/shards/budget":
                # the HOME group's per-Node budget doc (DESIGN.md §31);
                # any home replica serves it (rv-stamped, follower reads
                # fine) — 404 elsewhere so mirrors can probe blindly
                if sh.topology.owner("") != sh.group_id:
                    self._error(
                        404, "budget doc lives on the home group"
                    )
                    return
                from minisched_tpu.controlplane import shards as _shards

                self._send(200, _shards.build_budget_doc(self.store, sh))
            else:
                self._error(404, f"no route {path}")
            return
        try:
            kind, ns, name, _ = _route(path)
        except (KeyError, ValueError):
            self._error(404, f"no route {path}")
            return
        if "watch=true" in query:
            try:
                resume_rv = self._int_param(query, "resource_version")
            except ValueError:
                return  # 400 already sent
            self._watch(kind, ns, resume_rv)
            return
        try:
            min_rv = self._int_param(query, "min_rv")
        except ValueError:
            return  # 400 already sent
        # the rv watermark of the state this replica serves RIGHT NOW,
        # taken before the read: the stamp promises "at least this
        # fresh", and only-forward rv movement keeps that true even if
        # a publish lands mid-read
        applied = self.store.applied_rv()
        if min_rv is not None:
            from minisched_tpu.observability import counters

            counters.inc("wire.read.bounded_requests")
            if min_rv > applied:
                # rv-bounded read ahead of this replica's applied state:
                # refuse RETRYABLY (504) rather than serve silently
                # stale data — the client waits out the replication lag
                # or fails over to a fresher replica (DESIGN.md §29)
                counters.inc("wire.read.not_yet_observed")
                self._send(
                    504,
                    {
                        "error": (
                            f"resource_version {min_rv} not yet observed "
                            f"by this replica (applied {applied})"
                        )
                    },
                    rv=applied,
                )
                return
        try:
            if name:
                obj = self.store.get(kind, ns, name)
                self._send(200, _encode(obj), rv=applied)
            else:
                self._list(kind, ns)
        except KeyError as e:
            self._error(404, str(e))

    def _list(self, kind: str, ns: str) -> None:
        """Epoch-consistent list: the rv reflects exactly these items.

        COW mode serves the memoized body straight off the read-plane
        snapshot — a relist storm of N informers pays ONE encode per
        (kind, namespace, rv), the rest stream the shared bytes chunked
        (mirroring ``event_wire_chunk``).  Kill-switch mode
        (``MINISCHED_COW_READS=0``) takes the locked ``list_with_rv``
        path and re-encodes per request; the decoded bodies are
        byte-identical (same payload shape, same iteration order)."""
        from minisched_tpu.observability import counters, hist

        t0 = time.monotonic()
        counters.inc("wire.relist_requests")
        try:
            snap = self.store.read_plane()
            if snap is not None:
                # same fault hook the locked list path fires, off-lock
                self.store._maybe_fault("list", kind, "")

                def build() -> bytes:
                    objs = snap.maps.get(kind, {}).values()
                    items = [
                        o for o in objs
                        if not ns or o.metadata.namespace == ns
                    ]
                    return json.dumps(
                        {
                            "items": [_encode(o) for o in items],
                            "resource_version": snap.rv,
                        }
                    ).encode()

                body = snap.list_body(kind, ns, build)
                counters.inc("wire.relist_bytes_shared", len(body))
                self._send_shared_body(200, body, rv=snap.rv)
            else:
                # the rv is taken ATOMICALLY with the snapshot (one
                # store lock hold) so consumers deriving versioned
                # state from a listing (HA membership) can trust it
                items, rv = self.store.list_with_rv(kind)
                if ns:  # namespaced list filters, matching the watch verb
                    items = [o for o in items if o.metadata.namespace == ns]
                self._send(
                    200,
                    {
                        "items": [_encode(o) for o in items],
                        "resource_version": rv,
                    },
                    rv=rv,
                )
        finally:
            hist.observe(
                "http.list_s", time.monotonic() - t0, kind=kind.lower()
            )

    def _send_shared_body(
        self, code: int, body: bytes, rv: Optional[int] = None
    ) -> None:
        """Stream shared cached bytes chunked WITHOUT copying the whole
        payload per response — memoryview slices of the one cached body
        go straight to the socket.  ``http.client`` dechunks
        transparently, so clients see the exact bytes ``_send`` would
        have produced for the same payload."""
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Transfer-Encoding", "chunked")
        if rv is not None:
            self.send_header("X-Minisched-RV", str(rv))  # see _send
        self.end_headers()
        mv = memoryview(body)
        for off in range(0, len(mv), _LIST_CHUNK_BYTES):
            piece = mv[off : off + _LIST_CHUNK_BYTES]
            self.wfile.write(f"{len(piece):X}\r\n".encode())
            self.wfile.write(piece)
            self.wfile.write(b"\r\n")
        self.wfile.write(b"0\r\n\r\n")

    def _watch(self, kind: str, ns: str, resume_rv: Optional[int] = None) -> None:
        """JSON-lines event stream (chunked) until the client hangs up or
        the server shuts down — the apiserver watch verb the informer
        machinery rides.  A namespaced path filters to that namespace.

        ``resume_rv`` (the ``?resource_version=N`` query) resumes instead
        of relisting: the stream replays retained history with rv > N and
        goes live, SYNC count 0 (the consumer's cache is already current
        through N).  History compacted past N → 410 Gone, and the
        consumer must relist."""
        try:
            # clone_snapshot=False: the snapshot is only counted for the
            # SYNC line, never mutated or re-serialized here — skipping
            # the per-watcher deep copy is what makes storm registration
            # O(1) off the COW read plane
            watch, snapshot = self.store.watch(
                kind,
                send_initial=resume_rv is None,
                resume_rv=resume_rv,
                clone_snapshot=False,
            )
        except NotYetObserved as e:
            # follower lagging behind the resume cursor: retryable 504,
            # NOT the relist-forcing 410 (the client's cache is fine —
            # this replica just hasn't applied that far yet)
            from minisched_tpu.observability import counters

            counters.inc("wire.read.not_yet_observed")
            self._error(504, str(e))
            return
        except HistoryCompacted as e:
            self._error(410, str(e))
            return
        with self.watch_lock:
            self.active_watches.add(watch)
        self.send_response(200)
        self.send_header("Content-Type", "application/jsonlines")
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()

        def chunk(data: bytes) -> None:
            self.wfile.write(_chunk_frame(data))
            self.wfile.flush()

        # first line: how many snapshot events this stream will replay
        # (ns-filtered), taken ATOMICALLY with the watch registration —
        # a client-side LIST-then-watch can't get this count right (a
        # delete in the gap strands its sync barrier forever).  A
        # resumed stream replays history, not the snapshot: count 0.
        n_initial = sum(
            1
            for o in snapshot
            if not ns or o.metadata.namespace == ns
        )
        sync_line = (
            json.dumps(
                {
                    "type": "SYNC",
                    "count": n_initial,
                    # the rv this stream's snapshot reflects, taken
                    # atomically with the watch registration — the
                    # consumer's resume cursor once it has consumed
                    # the snapshot (a max over object rvs under-counts
                    # deletes and replays already-folded history)
                    "rv": watch.start_rv,
                }
            ).encode()
            + b"\n"
        )
        loop = self.stream_loop
        if loop is not None:
            # selector fanout path (ISSUE 9): handshake + snapshot/resume
            # replay on THIS thread (blocking writes are right for a
            # possibly-huge backlog), then DETACH the socket into the
            # one-thread stream loop and return this thread to the pool.
            # Wire bytes are identical to the thread path below.
            handed_off = False
            try:
                chunk(sync_line)
                for ev in watch.next_batch(timeout=0):
                    if ns and ev.obj.metadata.namespace != ns:
                        continue
                    self.wfile.write(event_wire_chunk(ev))
                self.wfile.flush()
                handed_off = True
            except OSError:
                from minisched_tpu.observability import counters

                counters.inc("watch.disconnects")
            finally:
                # like the thread path's finally: ANY failure before the
                # handoff (client hangup is the common OSError; anything
                # else propagates to the handler's logging) must not
                # leave a consumer-less registration for fanout to feed
                if not handed_off:
                    self.close_connection = True
                    watch.stop()
                    with self.watch_lock:
                        self.active_watches.discard(watch)
            if not handed_off:
                return
            self.close_connection = True
            with self.watch_lock:
                # the loop owns the lifecycle now; shutdown reaches this
                # watch through StreamLoop.stop, not active_watches
                self.active_watches.discard(watch)
            sock = self.connection
            self.server.detach_socket(sock)
            try:
                loop.adopt(sock, watch, ns)
            except RuntimeError:
                # adopt raced a loop shutdown: give the socket back to
                # the server's normal teardown
                self.server.undetach_socket(sock)
                watch.stop()
            return
        try:
            chunk(sync_line)
            while True:
                ev = watch.next(timeout=0.5)
                if ev is None:
                    if watch.stopped:
                        break
                    chunk(b"\n")  # keepalive
                    continue
                if ns and ev.obj.metadata.namespace != ns:
                    continue
                # shared-payload fanout: the framed bytes are encoded once
                # per EVENT (memoized on it) and shared by every stream
                self.wfile.write(event_wire_chunk(ev))
                self.wfile.flush()
                if ev.born:
                    from minisched_tpu.observability import hist

                    hist.observe(
                        "watch.delivery_lag_s",
                        max(time.monotonic() - ev.born, 0.0),
                    )
            # orderly end-of-stream: terminal chunk, then drop keep-alive so
            # neither side blocks waiting for the other
            self.wfile.write(b"0\r\n\r\n")
            self.wfile.flush()
        except OSError:
            # client hung up mid-chunk (BrokenPipe/ConnectionReset/aborted
            # socket): count it — these used to vanish silently — and fall
            # through to the finally, which prunes the watcher from the
            # store IMMEDIATELY instead of leaving a dead registration for
            # the next fanout to trip over
            from minisched_tpu.observability import counters

            counters.inc("watch.disconnects")
        finally:
            self.close_connection = True
            watch.stop()
            with self.watch_lock:
                self.active_watches.discard(watch)

    def do_POST(self) -> None:
        t0 = time.monotonic()
        try:
            self._handle_post()
        finally:
            self._observe_request(
                "POST", self.path.partition("?")[0], t0
            )

    def _handle_post(self) -> None:
        if self._inject_fault():
            return
        if self.path.partition("?")[0] == "/api/v1/bindings":
            self._bind_many()
            return
        if self.path.partition("?")[0] == "/net/partition":
            # cut/heal this process's outbound links (faults/net.py) —
            # how the chaos soak partitions replica children it cannot
            # reach into
            from minisched_tpu.faults.net import GLOBAL_NET

            try:
                self._send(200, GLOBAL_NET.control(self._body()))
            except (KeyError, ValueError) as e:
                self._error(400, f"bad partition control: {e}")
            return
        if self.path.partition("?")[0].startswith("/repl/"):
            repl = self.repl
            if repl is None:
                self._error(404, "replication not enabled on this server")
            else:
                repl.handle_post(self, self.path.partition("?")[0])
            return
        if self.path.partition("?")[0].startswith("/shards/"):
            self._shards_post(self.path.partition("?")[0])
            return
        try:
            kind, ns, name, sub = _route(self.path)
        except (KeyError, ValueError):
            self._error(404, f"no route {self.path}")
            return
        if sub == "binding":
            data = self._body()
            node_name = data.get("node_name")
            if not node_name:
                self._error(400, "binding body requires node_name")
                return
            expected_rv = data.get("expected_rv")
            if not self._shard_guard("Pod", ns):
                return
            try:
                pod = Client(self.store).pods(ns or "default").bind(
                    Binding(name, ns or "default", node_name,
                            expected_rv=expected_rv)
                )
                self._send(201, _encode(pod))
            except AlreadyBound as e:
                self._send(
                    409,
                    self._already_bound_entry(e, ns or "default", name),
                )
            except (Conflict, OutOfCapacity) as e:
                self._error(409, str(e))
            except NotLeader as e:
                # 503 with the "not leader" marker: this replica is
                # fenced (DESIGN.md §27) — the client re-discovers the
                # plane's current leader, it does NOT blind-retry here
                self._error(503, str(e))
            except StorageDegraded as e:
                # 507 Insufficient Storage: the WAL cannot append (ENOSPC/
                # EIO latch) — transient by contract (the store probes its
                # own recovery), so the remote client retries with backoff
                self._error(507, str(e))
            except KeyError as e:
                self._error(404, str(e))
            return
        # a pod create is the first layer a pod crosses: one span a call
        # (1 pod or 1,024) with its four stages as children; other kinds
        # (a cluster's set-up) open none
        span = profiling.span if kind == "Pod" else profiling.no_span
        with span("http.create") as whole:
            self._create(kind, ns, span, whole)

    def _create(self, kind: str, ns: str, span: Any, whole: Any) -> None:
        try:
            with span("http.create_read"):
                body = self._body()
        except Exception as e:
            self._error(400, f"malformed body: {e}")
            return
        # collection POST with an "items" list = batch create (one
        # round-trip for a whole cluster's setup; single objects never
        # encode with a top-level "items" key).  Per-item errors are
        # returned per entry, like the batch bindings endpoint.
        if isinstance(body, dict) and isinstance(body.get("items"), list):
            whole.set(n=len(body["items"]))
            self._create_many(
                kind, ns, body["items"], span,
                return_objects=body.get("return_objects", True),
            )
            return
        whole.set(n=1)
        try:
            with span("http.create_decode", n=1):
                obj = _decode(REST_KINDS[kind], body)
        except Exception as e:
            self._error(400, f"malformed body: {e}")
            return
        _fixup_namespace(kind, ns, obj)
        if not self._shard_guard(kind, obj.metadata.namespace):
            return
        try:
            with span("http.create_store", n=1):
                created = self.store.create(kind, obj)
            with span("http.create_respond", n=1):
                self._send(201, _encode(created))
        except NotLeader as e:
            self._error(503, str(e))
        except StorageDegraded as e:
            self._error(507, str(e))
        except KeyError as e:
            self._error(409, str(e))

    def _shards_post(self, path: str) -> None:
        """The split-procedure control surface (DESIGN.md §30):

        ``/shards/control``  topology/freeze/unfreeze on this façade's
                             ShardInfo (every replica of every group gets
                             the same op — the topology is config pushed
                             by the split driver, not consensus state);
        ``/shards/seed``     install a handoff doc's objects into THIS
                             group's store (leader only — the writes ride
                             the normal durable path and replicate);
        ``/shards/purge``    delete a moved namespace's objects from the
                             SOURCE group after the topology flips.

        seed/purge bypass ``_shard_guard`` by construction: they are the
        split's own machinery, moving objects the topology says this
        group does not (yet / any longer) own."""
        sh = self.shard
        if sh is None:
            self._error(404, "sharding not enabled on this server")
            return
        try:
            body = self._body()
        except Exception as e:
            self._error(400, f"malformed body: {e}")
            return
        from minisched_tpu.controlplane import shards as _shards

        try:
            if path == "/shards/control":
                sh.apply_control(body)
                self._send(200, sh.describe())
            elif path == "/shards/seed":
                self._send(200, _shards.apply_seed(self.store, body))
            elif path == "/shards/purge":
                ns = body.get("namespace") or ""
                if not ns:
                    self._error(400, "purge requires namespace")
                    return
                self._send(
                    200,
                    _shards.purge_namespace(
                        self.store, ns, names=body.get("names")
                    ),
                )
            else:
                self._error(404, f"no route {path}")
        except NotLeader as e:
            self._error(503, str(e))
        except StorageDegraded as e:
            self._error(507, str(e))
        except (KeyError, ValueError) as e:
            self._error(400, f"bad shard control: {e}")

    def _already_bound_entry(
        self, err: BaseException, namespace: str, name: str
    ) -> dict:
        """409 AlreadyBound body with the CURRENT bound node as a
        structured field — the ONE builder for the single-bind and
        batch-bind responses: the client's idempotent-retry dedup
        compares ``node`` to the node it asked for, and string-matching
        the prose message would couple the wire contract to an
        f-string."""
        entry = {"error": str(err), "type": "AlreadyBound"}
        try:
            entry["node"] = self.store.get(
                "Pod", namespace, name
            ).spec.node_name
        except Exception:
            pass  # pod vanished between bind and lookup
        return entry

    def _create_many(
        self, kind: str, ns: str, items: list, span: Any,
        return_objects: bool = True,
    ) -> None:
        """Batch create: decode each item (same namespace fixup as the
        single-object POST), then ONE store transaction
        (``store.create_many``: one lock hold, one fanout — per-object
        create() made a 10k-node seed pay a lock round-trip and a
        per-watcher fanout each); one response entry per item ({"object"}
        on success — bare ``{}`` with ``return_objects=False`` — or
        {"error", "type"} on conflict/bad input)."""
        out: list = [None] * len(items)
        decoded = []
        with span("http.create_decode", n=len(items)):
            for i, raw in enumerate(items):
                try:
                    obj = _decode(REST_KINDS[kind], raw)
                except Exception as e:
                    out[i] = {
                        "error": f"malformed item: {e}", "type": "BadRequest"
                    }
                    continue
                _fixup_namespace(kind, ns, obj)
                decoded.append((i, obj))
        if not self._shard_guard(
            kind, *[o.metadata.namespace for _, o in decoded]
        ):
            return
        try:
            with span("http.create_store", n=len(decoded)):
                results = self.store.create_many(
                    kind, [o for _, o in decoded],
                    return_objects=return_objects,
                )
        except NotLeader as e:
            self._error(503, str(e))
            return
        except StorageDegraded as e:
            self._error(507, str(e))
            return
        with span("http.create_respond", n=len(items)):
            self._respond_many(decoded, results, out)

    def _respond_many(self, decoded: list, results: list, out: list) -> None:
        for (i, _), res in zip(decoded, results):
            if isinstance(res, KeyError):
                out[i] = {"error": str(res), "type": "Conflict"}
            elif isinstance(res, StorageDegraded):
                # mid-batch ENOSPC: earlier items landed, this one (and
                # the rest) were refused pre-commit — typed per entry so
                # the remote facade can surface a retriable error
                out[i] = {"error": str(res), "type": "StorageDegraded"}
            elif isinstance(res, BaseException):
                out[i] = {"error": str(res), "type": "Error"}
            elif res is None:
                out[i] = {}
            else:
                out[i] = {"object": _encode(res)}
        self._send(200, {"items": out})

    def _bind_many(self) -> None:
        """Batch binding subresource: a wave's placements in ONE request
        (one HTTP round-trip per bind would serialize the TPU wave; the
        store transaction below is the same bind_many the in-process
        client uses).  Per-item errors are returned per entry —
        AlreadyBound / missing pod / stale-rv Conflict never abort the
        rest of the batch.

        Partial-batch acks: a request carrying ``batch_id`` gets each
        entry recorded under the ack id ``{batch_id}/{index}``.  A RETRIED
        batch (same batch_id — the response to the first attempt was lost)
        answers already-acked entries straight from the registry, marked
        ``"acked": true``, instead of re-running them through the store —
        so a retry after a partially-processed wave re-posts only the
        entries whose outcome is genuinely unknown.  The registry is
        in-memory (bounded FIFO) and does NOT survive a server restart;
        after one, the bind subresource's own preconditions take over
        (AlreadyBound-to-the-requested-node ⇒ the retried entry landed)."""
        try:
            data = self._body()
            items = data.get("items", [])
            return_objects = data.get("return_objects", True)
            batch_id = str(data.get("batch_id") or "")
            bindings = []
            ack_keys = []
            for i, it in enumerate(items):
                if not it.get("name") or not it.get("node_name"):
                    self._error(400, "each binding requires name and node_name")
                    return
                bindings.append(
                    Binding(
                        it["name"], it.get("namespace") or "default",
                        it["node_name"],
                        expected_rv=it.get("expected_rv"),
                    )
                )
                # ack identity suffix: the item's position by default, or
                # a caller-pinned "ack" field — a cross-shard commit
                # (shards.ShardedStore) pins each binding's ordinal in
                # the LOGICAL batch, so the registry key survives a
                # topology change re-partitioning the sub-batches
                ack_keys.append(str(it.get("ack", i)))
        except Exception as e:
            # malformed JSON / non-dict body / non-dict items: a client
            # mistake must get a 400 like every other handler, not a
            # dropped connection
            self._error(400, f"malformed body: {e}")
            return
        replayed: dict = {}
        if batch_id:
            with self.ack_lock:
                for i in range(len(bindings)):
                    entry = self.ack_registry.get(
                        f"{batch_id}/{ack_keys[i]}"
                    )
                    if entry is not None:
                        replayed[i] = entry
        todo = [i for i in range(len(bindings)) if i not in replayed]
        # shard ownership is checked for the TODO entries only, BEFORE
        # any executes: a refused request has run nothing, so the shard
        # router can safely re-split and re-dispatch the whole sub-batch
        # (already-acked entries keep replaying from THIS group's
        # registry wherever the namespace lives now)
        if not self._shard_guard(
            "Pod", *[bindings[i].pod_namespace for i in todo]
        ):
            return
        try:
            results = Client(self.store).pods().bind_many(
                [bindings[i] for i in todo], return_objects=return_objects
            )
        except NotLeader as e:
            self._error(503, str(e))
            return
        except StorageDegraded as e:
            # the WHOLE transaction was refused pre-commit (degraded
            # latch): 507, retryable — nothing to ack, nothing landed
            self._error(507, str(e))
            return
        out: list = [None] * len(bindings)
        fresh: dict = {}
        for i, res in zip(todo, results):
            b = bindings[i]
            if isinstance(res, AlreadyBound):
                entry = self._already_bound_entry(
                    res, b.pod_namespace, b.pod_name
                )
            elif isinstance(res, Conflict):
                entry = {"error": str(res), "type": "Conflict"}
            elif isinstance(res, OutOfCapacity):
                entry = {"error": str(res), "type": "OutOfCapacity"}
            elif isinstance(res, StorageDegraded):
                # ENOSPC hit mid-batch: this bind never committed —
                # typed so the remote client requeues it as retriable
                entry = {"error": str(res), "type": "StorageDegraded"}
            elif isinstance(res, BaseException):
                entry = {"error": str(res), "type": "NotFound"}
            elif res is not None:
                entry = {"object": _encode(res)}
            else:
                entry = {}
            out[i] = entry
            # the registry keeps the OUTCOME, never the encoded pod: a
            # success pins one tiny dict, not a multi-KB document, at
            # 65536 entries (the replay re-reads the live object below).
            # A degraded entry is NOT an outcome — the bind never ran,
            # and acking it would make the retry replay the transient
            # error instead of re-executing the bind.
            if entry.get("type") != "StorageDegraded":
                fresh[i] = entry if "error" in entry else {"committed": True}
        for i, entry in replayed.items():
            if entry.get("committed"):
                ack: dict = {"acked": True}
                if return_objects:
                    b = bindings[i]
                    try:
                        ack["object"] = _encode(
                            self.store.get("Pod", b.pod_namespace, b.pod_name)
                        )
                    except Exception:
                        pass  # pod since deleted: ack alone says it landed
                out[i] = ack
            else:
                out[i] = dict(entry, acked=True)
        if batch_id and fresh:
            with self.ack_lock:
                for i, entry in fresh.items():
                    ack_id = f"{batch_id}/{ack_keys[i]}"
                    if ack_id not in self.ack_registry:
                        self.ack_order.append(ack_id)
                    self.ack_registry[ack_id] = entry
                while len(self.ack_order) > _ACK_REGISTRY_CAP:
                    self.ack_registry.pop(self.ack_order.popleft(), None)
            # WAL-back the acks (ROADMAP crumb): a durable store persists
            # each outcome as a volatile ``ack`` record, so a RETRIED
            # batch stays idempotent across a server restart — not just
            # across a lost response.  Best-effort: the bind subresource's
            # own preconditions remain the backstop when the disk is
            # degraded or the store is in-memory.
            record_acks = getattr(self.store, "record_acks", None)
            if record_acks is not None:
                try:
                    record_acks(
                        {
                            f"{batch_id}/{ack_keys[i]}": e
                            for i, e in fresh.items()
                        }
                    )
                except Exception:
                    pass  # never fail a response whose binds committed
        self._send(200, {"items": out})

    def do_PUT(self) -> None:
        t0 = time.monotonic()
        try:
            self._handle_put()
        finally:
            self._observe_request(
                "PUT", self.path.partition("?")[0], t0
            )

    def _handle_put(self) -> None:
        if self._inject_fault():
            return
        path, _, query = self.path.partition("?")
        try:
            kind, ns, name, _ = _route(path)
        except (KeyError, ValueError):
            self._error(404, f"no route {path}")
            return
        try:
            expected_rv = self._int_param(query, "expected_rv")
        except ValueError:
            return  # 400 already sent
        try:
            obj = _decode(REST_KINDS[kind], self._body())
        except Exception as e:
            self._error(400, f"malformed body: {e}")
            return
        # the URL is authoritative: a body naming a different object is a
        # client error, not a silent update of the other object
        if name and obj.metadata.name != name:
            self._error(400, f"body names {obj.metadata.name!r}, path names {name!r}")
            return
        if ns and obj.metadata.namespace != ns:
            self._error(400, f"body namespace {obj.metadata.namespace!r} != {ns!r}")
            return
        if not self._shard_guard(kind, ns or obj.metadata.namespace):
            return
        try:
            self._send(
                200,
                _encode(self.store.update(kind, obj, expected_rv=expected_rv)),
            )
        except Conflict as e:
            # 409 with the stale-rv marker: the remote client maps it to
            # store.Conflict and retries get→re-apply→PUT, never blindly
            self._error(409, str(e))
        except NotLeader as e:
            self._error(503, str(e))
        except StorageDegraded as e:
            self._error(507, str(e))
        except KeyError as e:
            self._error(404, str(e))

    def do_DELETE(self) -> None:
        t0 = time.monotonic()
        try:
            self._handle_delete()
        finally:
            self._observe_request(
                "DELETE", self.path.partition("?")[0], t0
            )

    def _handle_delete(self) -> None:
        if self._inject_fault():
            return
        try:
            kind, ns, name, _ = _route(self.path)
            if not self._shard_guard(kind, ns):
                return
            # a pod's delete is a span as its create is: the store's
            # share (the lock, the delete, the publish) apart from the
            # answer's
            span = profiling.span if kind == "Pod" else profiling.no_span
            with span("http.delete", n=1):
                with span("http.delete_store", n=1):
                    self.deletes.delete(kind, ns, name)
                with span("http.delete_respond", n=1):
                    self._send(200, {})
        except NotLeader as e:
            self._error(503, str(e))
        except StorageDegraded as e:
            self._error(507, str(e))
        except (KeyError, ValueError) as e:
            self._error(404, str(e))


def start_api_server(
    store: Optional[ObjectStore] = None,
    port: int = 0,
    faults: Any = None,
    stream_buffer_bytes: Optional[int] = None,
    stream_sndbuf_bytes: Optional[int] = None,
    repl: Any = None,
    shard: Any = None,
) -> Tuple[ThreadingHTTPServer, str, Callable[[], None]]:
    """Boot the REST façade on an ephemeral port and poll /healthz until it
    answers (k8sapiserver.go:231-249's readiness loop).  Returns
    (server, base_url, shutdown_fn).  ``faults``: a faults.FaultFabric
    armed with http.500 / http.reset makes this server lossy on purpose
    (see _Handler._inject_fault).

    Watch streams detach into a selector stream loop (ISSUE 9): N
    watchers cost N sockets + ONE thread instead of N handler threads.
    ``MINISCHED_STREAMLOOP=0`` kills the switch and restores the
    thread-per-watcher path exactly.  ``stream_buffer_bytes`` overrides
    the loop's per-stream out-buffer eviction bound (benches shrink it
    to exercise the wire-level laggard path)."""
    store = store or ObjectStore()
    from collections import deque as _deque

    stream_loop = None
    if os.environ.get("MINISCHED_STREAMLOOP", "1") != "0":
        from minisched_tpu.controlplane.streamloop import (
            DEFAULT_MAX_BUFFER_BYTES,
            DEFAULT_STREAM_SNDBUF_BYTES,
            StreamLoop,
        )

        stream_loop = StreamLoop(
            max_buffer_bytes=stream_buffer_bytes or DEFAULT_MAX_BUFFER_BYTES,
            sndbuf_bytes=stream_sndbuf_bytes or DEFAULT_STREAM_SNDBUF_BYTES,
        )
    # seed the binding-ack registry from WAL ``ack`` records (durable
    # stores replay them): a batch retried across a server RESTART then
    # answers from the recovered outcomes instead of re-executing —
    # closing the per-process gap the in-memory registry had
    recovered = getattr(store, "recovered_acks", None)
    acks = dict(recovered()) if recovered is not None else {}
    handler = type(
        "BoundHandler",
        (_Handler,),
        {"store": store, "deletes": _DeleteCombiner(store),
         "active_watches": set(),
         "watch_lock": threading.Lock(), "faults": faults,
         "ack_registry": acks, "ack_order": _deque(acks),
         "ack_lock": threading.Lock(), "stream_loop": stream_loop,
         "repl": repl, "shard": shard},
    )
    # sharded façades grow a runtime besides the request surface
    # (DESIGN.md §31): freeze-lease journal wiring + WAL re-arm, the
    # capacity-mirror sync loop, optional autosplit.  None for shard
    # None — the unsharded plane stays byte-identical.
    shard_runtime = None
    if shard is not None:
        from minisched_tpu.controlplane.shards import attach_shard_runtime

        shard_runtime = attach_shard_runtime(store, shard)
    server = _WatchHTTPServer(("127.0.0.1", port), handler)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{server.server_address[1]}"
    deadline = time.monotonic() + 30.0  # 100ms interval, 30s timeout
    while time.monotonic() < deadline:
        try:
            with urllib.request.urlopen(base + "/healthz", timeout=1.0) as r:
                if r.status == 200:
                    break
        except OSError:
            pass
        time.sleep(0.1)
    else:
        raise RuntimeError("API server failed /healthz within 30s")

    def shutdown() -> None:
        # stop active watch streams first: their handler threads would
        # otherwise loop (and hold store watch registrations) forever.
        # Detached streams are the loop's: StreamLoop.stop ends each with
        # the terminal chunk and closes its socket.
        with handler.watch_lock:
            watches = list(handler.active_watches)
        for w in watches:
            w.stop()
        if stream_loop is not None:
            stream_loop.stop()
        if shard_runtime is not None:
            shard_runtime.stop()
        server.shutdown()
        server.server_close()
        thread.join(timeout=2.0)

    return server, base, shutdown


class HTTPClient:
    """The Client facade over the wire — what the reference's scenario
    does with client-go against the httptest server (sched.go:70-143).
    Requests ride a small keep-alive pool (ISSUE 9): no per-call TCP
    handshake, stale idle sockets reopened retry-safely inside it."""

    def __init__(self, base_url: str):
        self._base = base_url.rstrip("/")
        from minisched_tpu.controlplane.httppool import shared_pool

        # the default timeout matches RemoteStore's so both facades land
        # on the SAME shared per-endpoint pool (timeout is part of the
        # sharing key — it is baked into each socket at connect)
        self._pool = shared_pool(self._base)

    def _req(self, method: str, path: str, payload: Any = None) -> Any:
        data = json.dumps(payload).encode() if payload is not None else None
        status, raw, replayed = self._pool.request(method, path, body=data)
        if status < 400:
            return json.loads(raw)
        body = raw.decode(errors="replace")
        # every wire error carries whether the pool RETRANSMITTED the
        # request (stale keep-alive socket): a 409 answering a replay may
        # be the caller's own first attempt having landed — bind() below
        # needs the flag to tell the two apart
        if status == 409 and "already bound" in body:
            raise self._mark(AlreadyBound(body), replayed)
        if status == 409 and "stale resource_version" in body:
            # == in-process update(expected_rv)
            raise self._mark(Conflict(body), replayed)
        if status == 409 and "out of capacity" in body:
            # == in-process bind semantics
            raise self._mark(OutOfCapacity(body), replayed)
        if status == 409 and "already exists" in body:
            # == in-process store.create semantics
            raise self._mark(KeyError(body), replayed)
        if status == 404:
            raise self._mark(KeyError(body), replayed)
        if status == 421:
            # == in-process shard-ownership refusal (DESIGN.md §30):
            # typed so a shard-aware caller re-routes to the owning
            # group instead of retrying a façade that will keep refusing
            raise self._mark(WrongShard(body), replayed)
        if status == 503 and "shard frozen" in body:
            # == in-process split-freeze refusal: transient by contract
            raise self._mark(ShardFrozen(body), replayed)
        if status == 503 and "not leader" in body:
            # == in-process fence refusal (DESIGN.md §27): typed so a
            # leader-aware caller re-discovers the plane's leader rather
            # than retrying a replica that will keep refusing
            raise self._mark(NotLeader(body), replayed)
        if status == 507:
            # == in-process WAL refusal
            raise self._mark(StorageDegraded(body), replayed)
        if status == 504 and "not yet observed" in body:
            # == in-process rv-bounded read refusal (DESIGN.md §29):
            # typed so the caller retries / fails over instead of
            # treating a lagging follower as a hard error
            raise self._mark(NotYetObserved(body), replayed)
        raise RuntimeError(f"HTTP {status}: {body}")

    @staticmethod
    def _mark(err: BaseException, replayed: bool) -> BaseException:
        err.replayed = replayed
        return err

    def close(self) -> None:
        """Drop the pool's idle keep-alive sockets (RemoteStore.close's
        twin — clients created per bench role/chaos round must not leak
        CLOSE_WAIT fds for their GC lifetime)."""
        self._pool.close()

    class _Nodes:
        def __init__(self, c: "HTTPClient"):
            self._c = c

        def create(self, node: Node) -> Node:
            return _decode(Node, self._c._req("POST", "/api/v1/nodes", _encode(node)))

        def get(self, name: str) -> Node:
            return _decode(Node, self._c._req("GET", f"/api/v1/nodes/{name}"))

        def list(self):
            out = self._c._req("GET", "/api/v1/nodes")
            return [_decode(Node, o) for o in out["items"]]

        def delete(self, name: str) -> None:
            self._c._req("DELETE", f"/api/v1/nodes/{name}")

    class _Pods:
        def __init__(self, c: "HTTPClient", ns: str):
            self._c = c
            self._ns = ns

        def _path(self, name: str = "", namespace: Optional[str] = None) -> str:
            p = f"/api/v1/namespaces/{namespace or self._ns}/pods"
            return f"{p}/{name}" if name else p

        def create(self, pod: Pod) -> Pod:
            return _decode(Pod, self._c._req("POST", self._path(), _encode(pod)))

        def get(self, name: str, namespace: Optional[str] = None) -> Pod:
            return _decode(
                Pod, self._c._req("GET", self._path(name, namespace))
            )

        def list(self):
            out = self._c._req("GET", self._path())
            return [_decode(Pod, o) for o in out["items"]]

        def update(self, pod: Pod) -> Pod:
            return _decode(
                Pod, self._c._req("PUT", self._path(pod.metadata.name), _encode(pod))
            )

        def delete(self, name: str, namespace: Optional[str] = None) -> None:
            self._c._req("DELETE", self._path(name, namespace))

        def bind(self, binding: Binding) -> Pod:
            try:
                return _decode(
                    Pod,
                    self._c._req(
                        "POST",
                        self._path(binding.pod_name) + "/binding",
                        {"node_name": binding.node_name},
                    ),
                )
            except AlreadyBound as e:
                # idempotent-retry dedup: an AlreadyBound answering a
                # pool RETRANSMISSION, naming the node we asked for, is
                # our own first attempt having committed before its
                # socket died — success, not error.  A genuine conflict
                # names a different node, or arrives on a non-replayed
                # response, and stays an error.  ONE rule shared with
                # bind_many_remote: httppool.bind_already_ours.
                if getattr(e, "replayed", False):
                    from minisched_tpu.controlplane.httppool import (
                        bind_already_ours,
                    )

                    try:
                        doc = json.loads(str(e))
                    except Exception:
                        doc = {}
                    if bind_already_ours(
                        doc.get("node") or "",
                        doc.get("error") or str(e),
                        binding.node_name,
                    ):
                        try:
                            return self.get(
                                binding.pod_name, binding.pod_namespace
                            )
                        except KeyError:
                            # pod since deleted: the bind LANDED (the
                            # 409 named our node) — answer like the
                            # server's ack replay does when the object
                            # is gone, with a synthesized bound pod,
                            # never an error for a committed bind
                            from minisched_tpu.api.objects import make_pod

                            p = make_pod(
                                binding.pod_name,
                                namespace=binding.pod_namespace,
                            )
                            p.spec.node_name = binding.node_name
                            return p
                raise

    def nodes(self) -> "_Nodes":
        return HTTPClient._Nodes(self)

    def pods(self, namespace: str = "default") -> "_Pods":
        return HTTPClient._Pods(self, namespace)
