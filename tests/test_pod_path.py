"""ISSUE 38: a pod's path on one clock.

The program stamps each pod at the informer (``informer.pod_add_lag_s``,
span ``informer.gate_wait``), the queue (``sched.queue_wait_s``), the hold
before the evaluate that carries it (``sched.pod_hold_s{lane}``), its bind
(``sched.pod_commit_s{lane}``, ``sched.time_to_bind_s``) and the bind's
watch write (``watch.bind_lag_s``), all on ``time.monotonic()``, and the
trace ring keeps ONE record a bound pod that carries the same stamps and
the id of its evaluate span.
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from minisched_tpu.observability import hist, profiling

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LANES = ("wave", "wide", "narrow", "exact")
STAGES = ("born", "enqueued", "popped", "evaluated", "bound")

#: the metric files PR 38 lays under ``benchmarks/metrics/``, each naming a
#: histogram; no cell lists them until a benchmark PR appends them to the
#: cells' ``per_layer`` (PERF.md §7 row 19)
NEW_METRICS = (
    "queue.informer_lag_ms", "wave_build.pod_hold_ms", "commit.pod_bind_ms",
    "rest.watch_bind_ms", "queue.informer_lag_p99_ms",
    "queue.gate_wait_p99_ms", "wave_build.pod_hold_p99_ms",
    "commit.pod_bind_p99_ms", "rest.watch_bind_p99_ms",
)


@contextlib.contextmanager
def spied():
    """Every histogram observation (name, labels, values) and every span's
    (name, wall seconds) made while open, in order."""
    seen = []
    spans = []
    evaluates = []
    h_observe = hist.Histograms.observe
    h_many = hist.Histograms.observe_many
    span_exit = profiling.Span.__exit__
    phase_init = profiling._PhaseSpan.__init__
    mu = threading.Lock()

    def observe(self, name, v, exemplar=None, **labels):
        with mu:
            seen.append((name, labels, [v]))
        return h_observe(self, name, v, exemplar=exemplar, **labels)

    def observe_many(self, name, values, exemplar=None, **labels):
        with mu:
            seen.append((name, labels, list(values)))
        return h_many(self, name, values, exemplar=exemplar, **labels)

    def exit_(self, *exc):
        out = span_exit(self, *exc)
        with mu:
            spans.append((self.name, self.wall_s))
        return out

    def init_(self, metrics, phase, ids):
        if phase in ("wave_device", "scan_evaluate"):
            with mu:
                evaluates.append((time.monotonic(), phase, dict(ids)))
        phase_init(self, metrics, phase, ids)

    hist.Histograms.observe = observe
    hist.Histograms.observe_many = observe_many
    profiling.Span.__exit__ = exit_
    profiling._PhaseSpan.__init__ = init_
    try:
        yield {"hists": seen, "spans": spans, "evaluates": evaluates}
    finally:
        hist.Histograms.observe = h_observe
        hist.Histograms.observe_many = h_many
        profiling.Span.__exit__ = span_exit
        profiling._PhaseSpan.__init__ = phase_init


def _values(spy, name, **labels):
    return [
        v for n, have, vals in spy["hists"] for v in vals
        if n == name and all(have.get(k) == x for k, x in labels.items())
    ]


# -- the served stack, one burst a lane ---------------------------------------


@pytest.fixture(scope="module")
def pod_path():
    """The served stack (``__main__.start``, device engine) with all four
    lanes driven over HTTP: 24 plain pods (the packed wave), 34 spread
    pods of 17 services (the blocked scan: two full blocks, one wide
    call), 40 spread pods of two services (the blocked scan: few enough
    for one narrow call), 8 spread pods (the exact scan).  Returns the
    spy's record and the ring's bind records of these pods."""
    import chip_smoke
    from minisched_tpu.api.objects import make_node, make_pod
    from minisched_tpu.controlplane.remote import RemoteClient
    from test_spans import _spread_pod, _wait_bound

    with chip_smoke.booted_stack() as (base, service):
        client = RemoteClient(base)
        client.nodes().create_many(
            [
                make_node(
                    f"n{i:02d}", labels={"zone": f"z{i % 3}"},
                    capacity={"cpu": "8", "memory": "16Gi", "pods": 110},
                )
                for i in range(16)
            ],
            return_objects=False,
        )
        lister = service.informer_factory.informer_for("Node").lister
        while len(lister()) < 16:
            time.sleep(0.05)
        with spied() as spy:
            client.pods().create_many(
                [make_pod(f"a{i}", requests={"cpu": "100m"}) for i in range(24)],
                return_objects=False,
            )
            _wait_bound(client, 24)
            client.pods().create_many(
                [_spread_pod(f"w{k}.{i}", f"w{k}") for k in range(17) for i in range(2)],
                return_objects=False,
            )
            _wait_bound(client, 58)
            client.pods().create_many(
                [_spread_pod(f"r{i}", "r") for i in range(2)]
                + [_spread_pod(f"s{i}") for i in range(38)],
                return_objects=False,
            )
            _wait_bound(client, 98)
            client.pods().create_many(
                [_spread_pod(f"t{i}") for i in range(8)], return_objects=False
            )
            _wait_bound(client, 106)
            time.sleep(0.2)  # the last bind's watch writes
        with urllib.request.urlopen(base + "/debug/trace", timeout=30) as r:
            lines = [json.loads(ln) for ln in r.read().decode().splitlines()]
    ours = {f"default/{p}{i}" for p, n in (("a", 24), ("r", 2), ("s", 38), ("t", 8))
            for i in range(n)}
    ours |= {f"default/w{k}.{i}" for k in range(17) for i in range(2)}
    records = [s for s in lines[1:] if s.get("pod") in ours]
    return {"spy": spy, "records": records, "head": lines[0], "ours": ours}


def test_every_pod_was_popped_once_and_every_lane_ran(pod_path):
    """The premise of the identity below: each pod bound at its first
    pop, and the bursts took all four lanes."""
    spy, records = pod_path["spy"], pod_path["records"]
    assert len(_values(spy, "sched.queue_wait_s")) == len(pod_path["ours"])
    assert collections.Counter(r["lane"] for r in records) == {
        "wave": 24, "wide": 34, "narrow": 40, "exact": 8,
    }


@pytest.mark.parametrize("lane", LANES)
def test_the_stages_add_up_to_time_to_bind(pod_path, lane):
    """For every pod of the lane: the queue wait, the hold and the commit
    that the program observed are its record's stamp differences, and
    they sum to the time-to-bind it observed, within 1 ms."""
    spy = pod_path["spy"]
    records = [r for r in pod_path["records"] if r["lane"] == lane]
    assert records
    waits = collections.Counter(_values(spy, "sched.queue_wait_s"))
    holds = collections.Counter(_values(spy, "sched.pod_hold_s", lane=lane))
    commits = collections.Counter(_values(spy, "sched.pod_commit_s", lane=lane))
    ttbs = collections.Counter(_values(spy, "sched.time_to_bind_s"))
    assert sum(holds.values()) == sum(commits.values()) == len(records)
    for r in records:
        wait = max(r["popped"] - r["enqueued"], 0.0)
        hold = r["evaluated"] - r["popped"]
        commit = r["bound"] - r["evaluated"]
        ttb = max(r["bound"] - r["enqueued"], 0.0)
        for seen, v in ((waits, wait), (holds, hold), (commits, commit), (ttbs, ttb)):
            assert seen[v] > 0, (r["pod"], v)
            seen[v] -= 1
        assert abs(wait + hold + commit - ttb) < 1e-3


def test_one_record_a_bound_pod_on_the_monotonic_clock(pod_path):
    """Exactly one ring record a bound pod, written at the bind, its
    stamps in order on the clock the spans read; the dump opens with the
    wall clock's offset."""
    records, head = pod_path["records"], pod_path["head"]
    assert head["stage"] == "clock" and abs(
        (head["wall"] - head["ts"]) - (time.time() - time.monotonic())
    ) < 1.0
    assert collections.Counter(r["pod"] for r in records) == dict.fromkeys(
        pod_path["ours"], 1
    )
    for r in records:
        assert r["stage"] == "bind" and r["ts"] == r["bound"]
        stamps = [r[k] for k in STAGES]
        assert stamps == sorted(stamps) and stamps[-1] <= time.monotonic()


def test_the_record_carries_the_id_of_its_evaluate_span(pod_path):
    """The record names the evaluate that carried the pod by the id its
    span carried: ``wave`` for ``sched.wave_device``, ``call`` for
    ``sched.scan_evaluate`` (opened just after the pod's evaluated stamp)."""
    evaluates = pod_path["spy"]["evaluates"]
    for r in pod_path["records"]:
        t, phase, ids = next(e for e in evaluates if e[0] >= r["evaluated"])
        if r["lane"] == "wave":
            assert phase == "wave_device" and r["wave"] == ids["wave"]
        else:
            assert phase == "scan_evaluate" and r["call"] == ids["call"]


def test_the_exemplar_names_the_slowest_of_the_batch():
    """``observe_many`` stamps the slowest value's bucket with its key."""
    h = hist.Histograms()
    h.observe_many("t.many_s", [0.001, 0.5, 0.002], exemplar="default/slow")
    snap = h.get("t.many_s").snapshot()
    assert snap["count"] == 3 and abs(snap["sum"] - 0.503) < 1e-12
    assert list(snap["exemplars"].values()) == [("default/slow", 0.5)]
    assert snap["exemplars"].keys() == {hist.bucket_index(0.5)}


def test_observe_many_lands_where_observe_would():
    values = [0.0, 1e-4, 3e-3, 0.75, 5000.0]
    one, many = hist.Histograms(), hist.Histograms()
    for v in values:
        one.observe("t_s", v, lane="wave")
    many.observe_many("t_s", values, lane="wave")
    many.observe_many("t_s", [], lane="wave")
    a, b = one.get("t_s", lane="wave").snapshot(), many.get("t_s", lane="wave").snapshot()
    assert (a["counts"], a["overflow"], a["count"]) == (
        b["counts"], b["overflow"], b["count"]
    )
    assert abs(a["sum"] - b["sum"]) < 1e-9


# -- the informer's wait at the dispatch gate ---------------------------------


def test_a_pod_held_at_the_closed_gate_reads_the_hold():
    """Pause dispatch, create a pod, wait, resume: the pod's ADDED event
    reads the wait in ``informer.pod_add_lag_s`` and the batch in the
    span ``informer.gate_wait``."""
    from minisched_tpu.api.objects import make_pod
    from minisched_tpu.controlplane.client import Client
    from minisched_tpu.controlplane.informer import (
        ResourceEventHandlers,
        SharedInformerFactory,
    )

    client = Client()
    factory = SharedInformerFactory(client.store)
    added = threading.Event()
    factory.informer_for("Pod").add_event_handlers(
        ResourceEventHandlers(on_add=lambda pod: added.set())
    )
    factory.start()
    try:
        assert factory.wait_for_cache_sync(5.0)
        with spied() as spy:
            factory.pause_dispatch()
            client.pods().create(make_pod("held"))
            time.sleep(0.3)
            factory.resume_dispatch()
            assert added.wait(5.0)
    finally:
        factory.shutdown()
    lags = _values(spy, "informer.pod_add_lag_s")
    waits = [wall for name, wall in spy["spans"] if name == "informer.gate_wait"]
    assert len(lags) == 1 and lags[0] >= 0.2
    assert waits and max(waits) >= 0.2


# -- the bind's watch write ---------------------------------------------------


@pytest.mark.parametrize("streamloop", ["1", "0"])
def test_the_bind_lag_is_one_a_bind_a_stream(monkeypatch, streamloop):
    """Two watch streams see ADDED, the bind, a relabel and DELETED:
    ``watch.bind_lag_s`` is observed once a stream, for the bind alone;
    a resumed stream that replays the bind from history adds none.  Both
    delivery paths (the stream loop and the thread a watcher)."""
    from minisched_tpu.api.objects import Binding, make_pod
    from minisched_tpu.controlplane.client import Client
    from minisched_tpu.controlplane.httpserver import start_api_server
    from test_wire_fanout import open_watch_socket

    monkeypatch.setenv("MINISCHED_STREAMLOOP", streamloop)
    client = Client()
    store = getattr(client.store, "_store", client.store)
    server, base, shutdown = start_api_server(store)
    try:
        streams = [open_watch_socket(base) for _ in range(2)]
        for _s, r in streams:
            assert r.next_json()["type"] == "SYNC"
        rv0 = store.resource_version
        with spied() as spy:
            client.pods().create(make_pod("w0"))
            client.pods().bind(Binding("w0", "default", "node-a"))
            pod = client.pods().get("w0")
            pod.metadata.labels["seen"] = "yes"
            client.pods().update(pod)
            client.pods().delete("w0")
            for _s, r in streams:
                got = [r.next_json()["type"] for _ in range(4)]
                assert got == ["ADDED", "MODIFIED", "MODIFIED", "DELETED"]
            # the thread path observes just after its write: give it that
            t_end = time.monotonic() + 2.0
            while len(_values(spy, "watch.bind_lag_s")) < 2:
                if time.monotonic() > t_end:
                    break
                time.sleep(0.01)
            live = len(_values(spy, "watch.bind_lag_s"))
            s3, r3 = open_watch_socket(
                base, path=f"/api/v1/pods?watch=true&resource_version={rv0}"
            )
            replayed = []
            while len(replayed) < 4:
                ev = r3.next_json()
                assert ev is not None
                if ev.get("type") != "SYNC":
                    replayed.append(ev["type"])
            time.sleep(0.2)
            after_replay = len(_values(spy, "watch.bind_lag_s"))
        for s, _r in streams:
            s.close()
        s3.close()
    finally:
        shutdown()
    assert live == 2 and after_replay == 2
    assert all(v >= 0.0 for v in _values(spy, "watch.bind_lag_s"))


# -- every histogram a new metric reads is on /metrics from boot --------------

_BOOT_PROBE = """
import json, urllib.request
from minisched_tpu.__main__ import start
from minisched_tpu.service.config import ProcessConfig
_c, base, stop = start(ProcessConfig(port=0, frontend_url="http://localhost:3000"), device_mode=True)
try:
    with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
        text = r.read().decode()
finally:
    stop()
print("PROBE " + json.dumps(sorted({
    line.split("{")[0].split(" ")[0] for line in text.splitlines()
    if line and not line.startswith("#")
})))
"""


@pytest.fixture(scope="module")
def boot_series():
    r = subprocess.run(
        [sys.executable, "-c", _BOOT_PROBE], cwd=REPO, capture_output=True,
        text=True, timeout=300,
        env={**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"},
    )
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("PROBE ")]
    assert r.returncode == 0 and lines, r.stderr[-3000:]
    return set(json.loads(lines[-1][len("PROBE "):]))


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_the_histogram_a_new_metric_reads_is_on_metrics_from_boot(boot_series, metric):
    """A rename of a histogram would leave the metric reading null in
    every run: the series it names is on a fresh process's ``/metrics``
    before any pod, with its buckets, sum and count."""
    with open(os.path.join(REPO, "benchmarks", "metrics", metric + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] in ("hist_mean", "hist_quantile")
    h = spec["args"]["histogram"]
    assert {h + "_bucket", h + "_sum", h + "_count"} <= boot_series
