"""Selector-based watch-stream fanout (ISSUE 9, controlplane/streamloop):
N watchers cost N sockets + ONE event-loop thread instead of N pinned
handler threads, encode-once fanout crosses the wire intact, a
socket-level laggard is evicted onto the resume path and observes every
event EXACTLY once after reconnecting, and ``MINISCHED_STREAMLOOP=0``
restores the thread-per-watcher path byte-for-byte."""

from __future__ import annotations

import json
import socket
import threading
import time

from minisched_tpu.api.objects import make_pod
from minisched_tpu.controlplane.httpserver import start_api_server
from minisched_tpu.controlplane.store import ObjectStore
from minisched_tpu.observability import counters


class ChunkLineReader:
    """Minimal incremental reader for the watch verb's wire format:
    chunked-transfer frames each carrying (part of) JSON lines.  Feeds on
    raw socket bytes; yields decoded JSON objects (keepalive blank lines
    skipped).  ``eof`` flips on the terminal chunk or socket EOF."""

    def __init__(self, sock: socket.socket):
        self.sock = sock
        self.buf = bytearray()
        self.payload = bytearray()
        self.eof = False

    def _parse_chunks(self) -> None:
        while True:
            nl = self.buf.find(b"\r\n")
            if nl < 0:
                return
            size = int(bytes(self.buf[:nl]), 16)
            if size == 0:
                self.eof = True
                return
            start, end = nl + 2, nl + 2 + size
            if len(self.buf) < end + 2:
                return  # incomplete frame
            self.payload += self.buf[start:end]
            del self.buf[: end + 2]

    def next_json(self, timeout: float = 5.0):
        """The next JSON line (None on timeout/EOF)."""
        deadline = time.monotonic() + timeout
        while True:
            nl = self.payload.find(b"\n")
            if nl >= 0:
                line = bytes(self.payload[:nl]).strip()
                del self.payload[: nl + 1]
                if not line:
                    continue  # keepalive
                return json.loads(line)
            if self.eof:
                return None
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return None
            self.sock.settimeout(remaining)
            try:
                data = self.sock.recv(65536)
            except socket.timeout:
                return None
            except OSError:
                self.eof = True
                return None
            if not data:
                self.eof = True
                return None
            self.buf += data
            self._parse_chunks()

    def drain_available(self) -> list:
        """Parse everything already received (non-blocking), then until
        EOF/error — what an evicted client can still salvage."""
        out = []
        self.sock.settimeout(0.2)
        while True:
            try:
                data = self.sock.recv(65536)
            except (socket.timeout, OSError):
                break
            if not data:
                self.eof = True
                break
            self.buf += data
            self._parse_chunks()
        while True:
            nl = self.payload.find(b"\n")
            if nl < 0:
                break
            line = bytes(self.payload[:nl]).strip()
            del self.payload[: nl + 1]
            if line:
                out.append(json.loads(line))
        return out


def open_watch_socket(
    base: str, path: str = "/api/v1/pods?watch=true", rcvbuf: int = 0
):
    """One raw HTTP watch stream: returns (socket, reader) with response
    headers consumed and the stream positioned at the first chunk."""
    host, port = base.split("//")[1].split(":")
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    if rcvbuf:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
    s.connect((host, int(port)))
    s.sendall(
        f"GET {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode()
    )
    # read headers
    hdr = bytearray()
    s.settimeout(5.0)
    while b"\r\n\r\n" not in hdr:
        data = s.recv(4096)
        assert data, "connection closed before headers"
        hdr += data
    head, _, rest = bytes(hdr).partition(b"\r\n\r\n")
    assert b"200" in head.split(b"\r\n", 1)[0], head
    assert b"Transfer-Encoding: chunked" in head, head
    r = ChunkLineReader(s)
    r.buf += rest
    r._parse_chunks()
    return s, r


def test_many_watchers_one_loop_thread():
    """50 concurrent real HTTP watch streams: every handler thread
    returns to the pool after the handshake (thread count stays flat),
    the loop owns all 50 sockets, and one mutation reaches all 50
    streams through the encode-once fanout."""
    store = ObjectStore()
    base_threads = threading.active_count()
    server, base, shutdown = start_api_server(store)
    handler = server.RequestHandlerClass
    try:
        adopted0 = counters.get("wire.streams_adopted")
        streams = [open_watch_socket(base) for _ in range(50)]
        for _s, r in streams:
            sync = r.next_json()
            assert sync["type"] == "SYNC" and sync["count"] == 0
        loop = handler.stream_loop
        assert loop is not None
        # the handler answers SYNC and only then hands the socket over
        # (adopt counts it there), so the client can be a step ahead
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and (
            loop.stream_count() < 50
            or counters.get("wire.streams_adopted") < adopted0 + 50
        ):
            time.sleep(0.02)
        assert loop.stream_count() == 50
        assert counters.get("wire.streams_adopted") == adopted0 + 50
        # handler threads exited after detach: the process grew by the
        # serve_forever thread + the ONE loop thread (plus at most a
        # transiently-dying handler), NOT by 50 pinned watch threads
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if threading.active_count() <= base_threads + 3:
                break
            time.sleep(0.05)
        assert threading.active_count() <= base_threads + 3, (
            threading.enumerate()
        )

        enc0 = counters.get("watch.fanout.encoded")
        shr0 = counters.get("watch.fanout.shared")
        store.create("Pod", make_pod("fan1"))
        for _s, r in streams:
            ev = r.next_json()
            assert ev["type"] == "ADDED"
            assert ev["object"]["metadata"]["name"] == "fan1"
        # one encode, 49 shared reuses — the PR-8 claim over the wire
        assert counters.get("watch.fanout.encoded") == enc0 + 1
        assert counters.get("watch.fanout.shared") == shr0 + 49
    finally:
        for s, _r in streams:
            s.close()
        shutdown()


def test_snapshot_replay_inline_then_live_events_in_order():
    """The handshake + snapshot replay happen BEFORE detach (handler
    thread, blocking writes); live events follow through the loop in
    order with no seam: SYNC(count=N), N ADDED replays, then live."""
    store = ObjectStore()
    for i in range(5):
        store.create("Pod", make_pod(f"seed{i}"))
    server, base, shutdown = start_api_server(store)
    try:
        s, r = open_watch_socket(base)
        sync = r.next_json()
        assert sync == {
            "type": "SYNC", "count": 5, "rv": store.resource_version
        }
        seen = [r.next_json()["object"]["metadata"]["name"] for _ in range(5)]
        assert sorted(seen) == [f"seed{i}" for i in range(5)]
        store.create("Pod", make_pod("live0"))
        ev = r.next_json()
        assert ev["object"]["metadata"]["name"] == "live0"
        s.close()
    finally:
        shutdown()


def test_evicted_watcher_resumes_exactly_once_over_wire():
    """Eviction-resume parity over REAL sockets (extends the queue-level
    coverage in test_churn): a watcher too slow at the socket level is
    evicted (bounded out-buffer, ``wire.evicted_outbuf``), reconnects
    with ``resource_version=<last seen>``, and observes every mutation
    EXACTLY once across the two streams — nothing missed, nothing
    duplicated.  A fast watcher on the same store is untouched."""
    store = ObjectStore()
    # small out-buffer + small client receive window: the laggard's
    # frames pile up server-side fast
    server, base, shutdown = start_api_server(
        store, stream_buffer_bytes=4096
    )
    try:
        slow_s, slow_r = open_watch_socket(base, rcvbuf=4096)
        fast_s, fast_r = open_watch_socket(base)
        assert slow_r.next_json()["type"] == "SYNC"
        assert fast_r.next_json()["type"] == "SYNC"

        # fat pods: each frame ~32KiB, so unread events overflow kernel
        # buffers + the 4KiB out-buffer quickly
        pad = "x" * 32768
        all_rvs = []
        ev0 = counters.get("wire.evicted_outbuf")
        fast_seen = []
        fast_stop = threading.Event()

        def consume_fast():
            while not fast_stop.is_set():
                ev = fast_r.next_json(timeout=1.0)
                if ev is not None:
                    fast_seen.append(ev["rv"])
                elif fast_r.eof:
                    return

        t = threading.Thread(target=consume_fast, daemon=True)
        t.start()
        # slow client reads the first 3 events, then stops consuming.
        # The mutations are PACED (sustained churn, not one burst): the
        # fast consumer must be able to keep up on one core — only the
        # wedged watcher may fall behind.
        slow_seen = []
        for i in range(60):
            p = make_pod(f"fat{i:03d}", labels={"pad": pad})
            all_rvs.append(
                store.create("Pod", p).metadata.resource_version
            )
            if i < 3:
                ev = slow_r.next_json()
                if ev is not None:
                    slow_seen.append(ev["rv"])
            time.sleep(0.01)
        # the laggard must get evicted (socket dies under it); keep
        # mutating until the kernel's autotuned buffers fill
        deadline = time.monotonic() + 20.0
        j = 0
        while (
            counters.get("wire.evicted_outbuf") == ev0
            and time.monotonic() < deadline
        ):
            p = make_pod(f"tick{j:04d}", labels={"pad": pad})
            all_rvs.append(
                store.create("Pod", p).metadata.resource_version
            )
            j += 1
            time.sleep(0.02)
        assert counters.get("wire.evicted_outbuf") > ev0

        # salvage what the kernel already delivered, then resume
        for ev in slow_r.drain_available():
            slow_seen.append(ev["rv"])
        assert slow_r.eof  # the eviction killed the stream abruptly
        slow_s.close()
        assert slow_seen, "slow watcher saw nothing before eviction"
        last = max(slow_seen)
        # FIFO delivery: what the evicted client salvaged is a clean
        # PREFIX of the mutation sequence — the loss starts after `last`
        assert slow_seen == [rv for rv in all_rvs if rv <= last]
        s2, r2 = open_watch_socket(
            base, path=f"/api/v1/pods?watch=true&resource_version={last}"
        )
        sync = r2.next_json()
        assert sync["type"] == "SYNC" and sync["count"] == 0
        expect = [rv for rv in all_rvs if rv > last]
        resumed = []
        while len(resumed) < len(expect):
            ev = r2.next_json(timeout=10.0)
            assert ev is not None, (
                f"resume stalled: {len(resumed)}/{len(expect)}"
            )
            resumed.append(ev["rv"])
        # EXACTLY once: pre-eviction prefix + resumed tail = the full
        # mutation sequence, nothing missed, nothing duplicated
        assert resumed == expect
        assert not (set(slow_seen) & set(resumed))
        assert slow_seen + resumed == all_rvs
        s2.close()
        # the fast watcher rode through the whole episode un-evicted
        fast_stop.set()
        t.join(timeout=20.0)
        assert len(fast_seen) >= 60
        fast_s.close()
    finally:
        shutdown()


def test_streamloop_killswitch_restores_thread_path(monkeypatch):
    """MINISCHED_STREAMLOOP=0: no stream loop exists, no stream is ever
    adopted, and the watch verb serves from its dedicated handler thread
    exactly as before — same SYNC line, same frames, same teardown."""
    monkeypatch.setenv("MINISCHED_STREAMLOOP", "0")
    store = ObjectStore()
    server, base, shutdown = start_api_server(store)
    try:
        assert server.RequestHandlerClass.stream_loop is None
        adopted0 = counters.get("wire.streams_adopted")
        s, r = open_watch_socket(base)
        assert r.next_json()["type"] == "SYNC"
        store.create("Pod", make_pod("threaded"))
        ev = r.next_json()
        assert ev["object"]["metadata"]["name"] == "threaded"
        assert counters.get("wire.streams_adopted") == adopted0
        s.close()
    finally:
        shutdown()


def test_outbuf_eviction_unit():
    """Unit-level: a socket whose kernel never accepts bytes (send
    always blocks) grows its out-buffer to the bound and is evicted —
    abrupt close, watch stopped, registration pruned."""
    from minisched_tpu.controlplane.streamloop import StreamLoop

    class BlockedSocket:
        """Wraps one end of a socketpair; send pretends the kernel
        buffer is permanently full."""

        def __init__(self, sock):
            self._sock = sock
            self.closed = False

        def fileno(self):
            return self._sock.fileno()

        def setblocking(self, flag):
            self._sock.setblocking(flag)

        def send(self, data):
            raise BlockingIOError()

        def recv(self, n):
            raise BlockingIOError()

        def close(self):
            self.closed = True
            self._sock.close()

    store = ObjectStore()
    loop = StreamLoop(max_buffer_bytes=4096)
    a, b = socket.socketpair()
    wrapped = BlockedSocket(a)
    try:
        watch, _ = store.watch("Pod", send_initial=False)
        loop.adopt(wrapped, watch, "")
        ev0 = counters.get("wire.evicted_outbuf")
        pad = "y" * 2048
        deadline = time.monotonic() + 10.0
        i = 0
        while (
            counters.get("wire.evicted_outbuf") == ev0
            and time.monotonic() < deadline
        ):
            store.create("Pod", make_pod(f"blk{i}", labels={"pad": pad}))
            i += 1
            time.sleep(0.02)
        assert counters.get("wire.evicted_outbuf") == ev0 + 1
        deadline = time.monotonic() + 5.0
        while not watch.stopped and time.monotonic() < deadline:
            time.sleep(0.02)
        assert watch.stopped
        assert wrapped.closed
        assert loop.stream_count() == 0
        # the store pruned the dead registration on its next fanout
        store.create("Pod", make_pod("after"))
        with store.locked():
            assert not [
                w for w in store._watches.get("Pod", ()) if not w.stopped
            ]
    finally:
        loop.stop()
        b.close()
