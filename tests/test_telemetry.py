"""Live telemetry plane (ISSUE 11): histograms, /metrics, trace ring.

Covers the tentpole's three layers — the fixed-bucket histogram registry
(observability/hist), the Prometheus scrape surface (REST façade +
metricsd sidecar), and the per-pod scheduling trace recorder
(observability/trace) — plus the documentation lint that keeps every
counter/gauge/histogram name in the tree registered in its module
docstring.
"""

from __future__ import annotations

import json
import math
import os
import re
import threading
import time
import urllib.request

import pytest

from minisched_tpu.observability import counters, hist, trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN = os.path.join(os.path.dirname(__file__), "data", "metrics_golden.txt")


# ---------------------------------------------------------------------------
# bucket ladder
# ---------------------------------------------------------------------------


def test_bucket_index_boundaries_exact():
    """A value EQUAL to a bucket's upper bound lands IN that bucket
    (Prometheus ``le`` semantics), exactly, at every power-of-two
    boundary — frexp, not float log2."""
    assert hist.bucket_index(0.0) == 0
    assert hist.bucket_index(hist.BUCKET_BASE_S) == 0
    for k, bound in enumerate(hist.BUCKET_BOUNDS):
        assert hist.bucket_index(bound) == k, f"bound {bound} (k={k})"
        if k + 1 < hist.NBUCKETS:
            assert hist.bucket_index(bound * 1.0000001) == k + 1
    # beyond the last finite bound → overflow
    assert hist.bucket_index(hist.BUCKET_BOUNDS[-1] * 2) == hist.NBUCKETS
    assert hist.bucket_index(1e12) == hist.NBUCKETS


def test_bucket_bounds_are_stable():
    """The ladder is a fixed contract (cross-process mergeability and the
    bench cross-check both key on it): 100µs · 2^k, 26 finite buckets."""
    assert hist.BUCKET_BOUNDS[0] == 1e-4
    assert len(hist.BUCKET_BOUNDS) == 26
    for a, b in zip(hist.BUCKET_BOUNDS, hist.BUCKET_BOUNDS[1:]):
        assert b == a * 2


def test_histogram_concurrent_observe_loses_no_samples():
    h = hist.Histograms()
    n_threads, per_thread = 8, 5000

    def worker(tid: int) -> None:
        for i in range(per_thread):
            h.observe("t.lat_s", (i % 20 + 1) * 1e-4, shard=str(tid % 2))

    threads = [
        threading.Thread(target=worker, args=(t,)) for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    bucket_counts, overflow, total, count = h.merged("t.lat_s")
    assert count == n_threads * per_thread
    assert sum(bucket_counts) + overflow == count
    expect_sum = n_threads * sum((i % 20 + 1) * 1e-4 for i in range(per_thread))
    assert total == pytest.approx(expect_sum, rel=1e-9)


def test_quantile_bounds_nearest_rank():
    h = hist.Histograms()
    # 99 fast samples in bucket 0, one slow one far up the ladder
    for _ in range(99):
        h.observe("q.lat_s", 5e-5)
    h.observe("q.lat_s", 0.5)
    lo, hi = h.quantile_bounds("q.lat_s", 0.50)
    assert (lo, hi) == (0.0, hist.BUCKET_BOUNDS[0])
    lo, hi = h.quantile_bounds("q.lat_s", 0.99)
    assert (lo, hi) == (0.0, hist.BUCKET_BOUNDS[0])  # rank 99 of 100
    lo, hi = h.quantile_bounds("q.lat_s", 1.0)
    assert lo < 0.5 <= hi
    assert h.quantile_bounds("missing", 0.99) is None


# ---------------------------------------------------------------------------
# Prometheus exposition: golden file + parser round-trip
# ---------------------------------------------------------------------------


def _golden_registries():
    """The deterministic fixture both the golden test and the
    regeneration helper render."""
    c = counters.Counters()
    c.inc("wire.pool_open", 3)
    c.inc("remote.retry", 7)
    c.set_gauge("wire.streams_active", 2)
    h = hist.Histograms()
    h.observe("sched.time_to_bind_s", 1e-4, priority="0")
    # production stamps the pod key as an exemplar (queue.observe_bind):
    # the p99 bucket on a scrape names the slow pod
    h.observe(
        "sched.time_to_bind_s", 0.5, exemplar="default/slow-pod", priority="0"
    )
    h.observe("sched.time_to_bind_s", 1e9, priority='we"ird\\l\nbl')
    h.observe("http.request_s", 0.02, verb="GET", route="pods/{name}")
    h.observe("http.list_s", 0.003, kind="pods")
    return c, h


def test_prometheus_exposition_matches_golden():
    c, h = _golden_registries()
    text = hist.render_prometheus(c, h)
    with open(GOLDEN) as f:
        assert text == f.read()


def test_prometheus_parser_roundtrips_golden():
    """The minimal scrape parser recovers types, escaped labels, and the
    exact bucket/sum/count samples from the golden exposition."""
    with open(GOLDEN) as f:
        text = f.read()
    types, samples = hist.parse_prometheus(text)
    assert types["wire_pool_open"] == "counter"
    assert types["wire_streams_active"] == "gauge"
    assert types["sched_time_to_bind_seconds"] == "histogram"
    by_name = {}
    for name, labels, val in samples:
        by_name.setdefault(name, []).append((labels, val))
    # label escaping round-trips: \" \\ \n come back verbatim
    weird = [
        labels
        for labels, _v in by_name["sched_time_to_bind_seconds_count"]
        if labels.get("priority") != "0"
    ]
    assert weird == [{"priority": 'we"ird\\l\nbl'}]
    # count/sum agree with what was observed
    counts = dict(
        (labels["priority"], v)
        for labels, v in by_name["sched_time_to_bind_seconds_count"]
    )
    assert counts["0"] == 2
    # the overflow observation is only in the +Inf bucket
    inf_rows = [
        (labels, v)
        for labels, v in by_name["sched_time_to_bind_seconds_bucket"]
        if labels["le"] == "+Inf"
    ]
    assert sum(v for _l, v in inf_rows) == 3


def test_parsed_quantile_matches_live_quantile():
    """The scrape-side quantile (parsed _bucket samples) and the live
    registry's quantile_bounds tell the same story — the contract the
    bench cross-check and the metrics CLI both lean on."""
    c, h = _golden_registries()
    text = hist.render_prometheus(c, h)
    _types, samples = hist.parse_prometheus(text)
    live = h.quantile_bounds("sched.time_to_bind_s", 0.50)
    parsed = hist.parsed_histogram_quantile(
        samples, "sched_time_to_bind_seconds", 0.50
    )
    assert live == parsed
    # and for the +Inf-resident p99 the parsed upper bound is inf
    p99 = hist.parsed_histogram_quantile(
        samples, "sched_time_to_bind_seconds", 0.99
    )
    assert p99[1] == math.inf


def test_metric_name_mapping():
    assert hist._metric_name("sched.time_to_bind_s") == (
        "sched_time_to_bind_seconds"
    )
    assert hist._metric_name("wire.pool_open") == "wire_pool_open"
    assert hist._metric_name("9weird-name") == "_9weird_name"


# ---------------------------------------------------------------------------
# documentation lint: every metric literal in the tree is registered
# ---------------------------------------------------------------------------

_COUNTER_CALL = re.compile(
    r"""counters\.(?:inc|set_gauge)\(\s*["']([^"']+)["']"""
)
_HIST_CALL = re.compile(r"""hist\.observe\(\s*\n?\s*["']([^"']+)["']""")
# profiling.span("http.create", ...) and a local alias span("...") — a span
# name has a dot, which the trace ring's trace.span("wave_build") has not
_SPAN_CALL = re.compile(r"""\bspan\(\s*\n?\s*["']([a-z_]+\.[a-z_.]+)["']""")
# metrics.timed("phase", ...) / metrics.observe("phase", dt): the span is
# profiling.span_name(phase)
_PHASE_CALL = re.compile(
    r"""metrics\.(?:timed|observe)\(\s*\n?\s*["']([a-z_]+)["']"""
)


def _span_names(src: str):
    from minisched_tpu.observability import profiling

    return _SPAN_CALL.findall(src) + [
        profiling.span_name(phase) for phase in _PHASE_CALL.findall(src)
    ]


def _py_sources():
    for dirpath, _dirs, files in os.walk(os.path.join(REPO, "minisched_tpu")):
        for fn in files:
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


def test_every_metric_name_is_documented():
    """Registry lint: any ``counters.inc("x")`` / ``set_gauge`` name must
    appear in counters.py's module docstring, any ``hist.observe("x")``
    name in hist.py's, and so must the name of any span opened with
    ``profiling.span("x")`` or ``metrics.timed("phase")`` — the docstrings
    ARE the metric registry, and an undocumented metric is a scrape nobody
    can interpret."""
    counter_doc = counters.__doc__ or ""
    hist_doc = hist.__doc__ or ""
    missing = []
    for path in _py_sources():
        with open(path, encoding="utf-8") as f:
            src = f.read()
        rel = os.path.relpath(path, REPO)
        if rel.endswith("observability/counters.py"):
            continue  # the registry itself (helper defs, not call sites)
        for name in _COUNTER_CALL.findall(src):
            if name not in counter_doc:
                missing.append(f"{rel}: counter {name!r} not in counters.py doc")
        for name in _HIST_CALL.findall(src):
            if name not in hist_doc:
                missing.append(f"{rel}: histogram {name!r} not in hist.py doc")
        for name in _span_names(src):
            if name not in hist_doc:
                missing.append(f"{rel}: span {name!r} not in hist.py doc")
    assert not missing, "\n".join(missing)


def test_an_undocumented_span_name_fails_the_lint():
    """The span half of the lint on a source written here: a dotted span
    literal and an engine phase are both seen, and neither is in the
    registry docstring."""
    src = (
        'with profiling.span("bogus.layer", n=1):\n    pass\n'
        'with self.metrics.timed(\n    "bogus_phase", wave=1\n):\n    pass\n'
        'trace.span("wave_build", wave=1)\n'
    )
    assert _span_names(src) == ["bogus.layer", "sched.bogus_phase"]
    assert not any(name in (hist.__doc__ or "") for name in _span_names(src))


def test_lint_scanner_actually_sees_call_sites():
    """Guard the guard: the regexes must match the tree's real call
    idioms, or the lint above passes vacuously."""
    seen_counters, seen_hists, seen_spans = set(), set(), set()
    for path in _py_sources():
        with open(path, encoding="utf-8") as f:
            src = f.read()
        seen_counters.update(_COUNTER_CALL.findall(src))
        seen_hists.update(_HIST_CALL.findall(src))
        seen_spans.update(_span_names(src))
    assert {
        "http.create", "http.create_decode", "watch.deliver",
        "informer.dispatch", "sched.queue_pop_wait", "sched.wave_build",
        "sched.loop_handoff_wait", "sched.wave_dispatch", "sched.wave_fetch",
        "sched.wave_commit", "sched.wave_stall", "sched.scan_flush",
    } <= seen_spans
    assert "wire.pool_open" in seen_counters
    assert "sched.time_to_bind_s" in seen_hists
    assert "watch.delivery_lag_s" in seen_hists
    assert "storage.wal_append_s" in seen_hists


# ---------------------------------------------------------------------------
# route label
# ---------------------------------------------------------------------------


def test_route_label_low_cardinality():
    from minisched_tpu.controlplane.httpserver import _route_label

    assert _route_label("/healthz") == "/healthz"
    assert _route_label("/metrics") == "/metrics"
    assert _route_label("/debug/trace") == "/debug/trace"
    assert _route_label("/api/v1/pods") == "pod"
    a = _route_label("/api/v1/namespaces/default/pods/my-pod-123")
    b = _route_label("/api/v1/namespaces/default/pods/other-pod-456")
    assert a == b == "pod/{name}"  # names never mint label children
    assert (
        _route_label("/api/v1/namespaces/default/pods/p/binding")
        == "pod/{name}/binding"
    )
    assert _route_label("/api/v1/nonsense") == "unroutable"
    assert _route_label("/favicon.ico") == "other"


# ---------------------------------------------------------------------------
# trace ring
# ---------------------------------------------------------------------------


def test_trace_ring_bounded_and_filterable():
    ring = trace.TraceRing(capacity=8)
    for i in range(20):
        ring.span("enqueue", pod=f"default/p{i % 2}", seq=i)
    assert len(ring) == 8  # flight recorder, not a log
    assert all(s["seq"] >= 12 for s in ring.spans())
    only_p1 = ring.spans(pod="default/p1")
    assert only_p1 and all(s["pod"] == "default/p1" for s in only_p1)
    lines = ring.dump_jsonl().strip().splitlines()
    assert len(lines) == 8
    assert all(json.loads(ln)["stage"] == "enqueue" for ln in lines)


def test_trace_span_drops_none_fields():
    ring = trace.TraceRing(capacity=8)
    ring.span("wave_build", wave=3, mesh=None, skipped=None)
    [s] = ring.spans()
    assert s["wave"] == 3 and "mesh" not in s and "skipped" not in s


def test_flight_dump_env_gated(tmp_path, monkeypatch):
    ring = trace.TraceRing(capacity=8)
    ring.span("wave_park", wave=1, cause="TestError")
    monkeypatch.delenv("MINISCHED_TRACE_DIR", raising=False)
    assert ring.flight_dump("no-dir") is None
    monkeypatch.setenv("MINISCHED_TRACE_DIR", str(tmp_path))
    path = ring.flight_dump("storage degraded/park!")
    assert path is not None and os.path.exists(path)
    assert "storage_degraded_park_" in os.path.basename(path)
    rec = json.loads(open(path).read().strip())
    assert rec["stage"] == "wave_park" and rec["cause"] == "TestError"


# ---------------------------------------------------------------------------
# scrape surfaces: metricsd sidecar + REST façade
# ---------------------------------------------------------------------------


def _get(url: str) -> tuple:
    with urllib.request.urlopen(url, timeout=5.0) as r:
        return r.status, r.headers.get("Content-Type", ""), r.read().decode()


def test_metricsd_serves_metrics_and_trace():
    from minisched_tpu.observability.metricsd import start_metrics_server

    hist.observe("sched.wave_build_s", 0.001)
    trace.span("wave_build", wave=999999, size=1)
    srv, port, shutdown = start_metrics_server(port=0)
    try:
        status, ctype, body = _get(f"http://127.0.0.1:{port}/metrics")
        assert status == 200 and ctype.startswith("text/plain")
        types, samples = hist.parse_prometheus(body)
        assert types.get("sched_wave_build_seconds") == "histogram"
        status, ctype, body = _get(f"http://127.0.0.1:{port}/debug/trace")
        assert status == 200 and "ndjson" in ctype
        assert any(
            json.loads(ln).get("wave") == 999999
            for ln in body.strip().splitlines()
        )
        status, _ct, body = _get(f"http://127.0.0.1:{port}/healthz")
        assert status == 200 and body == "ok"
    finally:
        shutdown()


def test_facade_serves_metrics_and_trace():
    from minisched_tpu.controlplane.httpserver import start_api_server
    from minisched_tpu.controlplane.store import ObjectStore

    server, base, shutdown = start_api_server(ObjectStore(), port=0)
    try:
        status, ctype, body = _get(base + "/metrics")
        assert status == 200 and "version=0.0.4" in ctype
        types, _samples = hist.parse_prometheus(body)
        assert types  # a live process always has SOMETHING registered
        status, _ct, _body = _get(base + "/debug/trace")
        assert status == 200
        # the scrape itself is instrumented (route label, not raw path)
        child = hist.GLOBAL.get("http.request_s", verb="GET", route="/metrics")
        assert child is not None and child.count >= 1
    finally:
        shutdown()


def test_scheduler_feeds_time_to_bind_and_trace():
    """End-to-end tentpole: a live in-process scheduler stamps arrival at
    queue admission, observes time-to-bind at ack, and leaves an
    enqueue→pop→bind span chain in the trace ring."""
    from minisched_tpu.api.objects import make_node, make_pod
    from minisched_tpu.controlplane.client import Client
    from minisched_tpu.service.config import default_scheduler_config
    from minisched_tpu.service.service import SchedulerService

    _counts0 = hist.GLOBAL.merged("sched.time_to_bind_s")[3]
    client = Client()
    svc = SchedulerService(client)
    svc.start_scheduler(default_scheduler_config(time_scale=0.01))
    client.nodes().create(make_node("node1"))
    client.pods().create(make_pod("ttb-pod-1"))
    deadline = time.time() + 10
    while time.time() < deadline:
        if client.pods().get("ttb-pod-1").spec.node_name:
            break
        time.sleep(0.05)
    got = client.pods().get("ttb-pod-1")
    svc.shutdown_scheduler()
    assert got.spec.node_name == "node1"
    assert hist.GLOBAL.merged("sched.time_to_bind_s")[3] > _counts0
    # the priority label is the pod's priority class (0 here)
    assert hist.GLOBAL.get("sched.time_to_bind_s", priority="0") is not None
    stages = [
        s["stage"] for s in trace.spans(pod="default/ttb-pod-1")
    ]
    assert "enqueue" in stages and "pop" in stages
    assert "bind" in stages and "bind_ack" in stages
    assert stages.index("enqueue") < stages.index("pop") < stages.index("bind")
    [ack] = trace.spans(pod="default/ttb-pod-1", stage="bind_ack")
    assert ack["ttb_s"] >= 0.0 and ack["node"] == "node1"


def test_queue_arrival_stamp_survives_requeue_and_purges_on_delete():
    """The arrival stamp is queue-owned and idempotent: requeues (fresh
    QueuedPodInfos) keep the ORIGINAL clock; delete_many purges it so
    pods bound by a peer never leak stamps."""
    from minisched_tpu.api.objects import make_pod
    from minisched_tpu.queue.queue import SchedulingQueue

    now = {"t": 100.0}
    q = SchedulingQueue(clock=lambda: now["t"])
    pod = make_pod("stampy")
    q.add(pod)
    now["t"] = 105.0
    q.pop()
    q.add(pod, requeue=True)  # fresh QPI, same uid
    uid = q._uid(pod)
    assert q._arrival_ts[uid] == 100.0  # NOT re-stamped at 105
    n0 = hist.GLOBAL.merged("sched.time_to_bind_s")[3]
    now["t"] = 108.0
    q.observe_bind(pod, "node-x")
    assert uid not in q._arrival_ts
    assert hist.GLOBAL.merged("sched.time_to_bind_s")[3] == n0 + 1
    # a second ack for the same pod is a no-op (stamp consumed)
    q.observe_bind(pod, "node-x")
    assert hist.GLOBAL.merged("sched.time_to_bind_s")[3] == n0 + 1
    # and delete_many purges an un-bound pod's stamp WITHOUT observing
    p2 = make_pod("stampy2")
    q.add(p2)
    assert q._uid(p2) in q._arrival_ts
    q.delete_many([p2])
    assert q._uid(p2) not in q._arrival_ts
    assert hist.GLOBAL.merged("sched.time_to_bind_s")[3] == n0 + 1
    # but a BOUND pod departing through delete_many is a bind ack via
    # the event path (HA handlers route bind MODIFIEDs here, racing the
    # binding thread's observe_bind): the stamp is consumed INTO the
    # histogram, exactly once
    p3 = make_pod("stampy3")
    q.add(p3)
    now["t"] = 111.0
    p3.spec.node_name = "node-y"
    q.delete_many([p3])
    assert q._uid(p3) not in q._arrival_ts
    assert hist.GLOBAL.merged("sched.time_to_bind_s")[3] == n0 + 2
    q.observe_bind(p3, "node-y")  # binding thread lost the race: no-op
    assert hist.GLOBAL.merged("sched.time_to_bind_s")[3] == n0 + 2


def test_watch_event_birth_stamp():
    from minisched_tpu.api.objects import make_pod
    from minisched_tpu.controlplane.store import EventType, WatchEvent

    before = time.monotonic()
    ev = WatchEvent(EventType.ADDED, make_pod("x"))
    assert before <= ev.born <= time.monotonic()
    # equality semantics unchanged (born is compare=False)
    p = make_pod("y")
    assert WatchEvent(EventType.ADDED, p) == WatchEvent(EventType.ADDED, p)


def test_metrics_cli_pretty_prints(capsys):
    from minisched_tpu.observability.metricsd import (
        scrape_main,
        start_metrics_server,
    )

    hist.observe("sched.wave_commit_s", 0.003)
    srv, port, shutdown = start_metrics_server(port=0)
    try:
        rc = scrape_main([f"http://127.0.0.1:{port}"])
    finally:
        shutdown()
    assert rc == 0
    out = capsys.readouterr().out
    assert "sched_wave_commit_seconds" in out
    assert "p99" in out
    assert scrape_main([]) == 2
