"""Benchmark: the five BASELINE.json configs on whatever device JAX gives.

The driver runs ``python bench.py`` and records the ONE stdout JSON line.
Every configured run executes in its OWN subprocess with a fresh backend
(no config inherits another's executables, jit caches or heap; a chip
belongs to one process at a time, so the parent stays off JAX and the
children run one after another); the parent merges each child's JSON into
the single record,
so the artifact is self-sufficient: headline throughput, the <1s
north-star decomposition (build + transfer + schedule), the full-chain
live run, full-chain bit-exact parity at scale, and configs 1-4.

Headline: pods scheduled/sec at 10k nodes × 100k pods — the fused wave
evaluator against a resident node table.  ``vs_baseline`` is the speedup
over the sequential scalar oracle (the faithful re-creation of the
reference's Go filter→score→selectHost loop; the reference publishes no
numbers of its own — BASELINE.md), measured on a pod subsample.

Knobs (env): BENCH_NODES (10000), BENCH_PODS (100000), BENCH_WAVE (8192),
BENCH_PARITY_SAMPLE (500), BENCH_C5 (1), BENCH_FULLCHAIN_PARITY (1),
BENCH_SECONDARY (1 = run configs 1-4).
"""

from __future__ import annotations

import json
import os
import random
import subprocess
import sys
import time
from functools import partial


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _pct(samples, p: float, digits: int = 3) -> float:
    """Nearest-rank percentile over SORTED samples — the one definition
    both latency-headline roles (churn time-to-bind, wirefan delivery)
    gate on.  ceil(p·n)−1, NOT int(p·n): the latter is one rank high
    and makes a small-sample p99 gate on the MAXIMUM, failing a run on
    a single straggler."""
    import math

    idx = min(max(math.ceil(p * len(samples)) - 1, 0), len(samples) - 1)
    return round(samples[idx], digits)


def _crosscheck_live_p99(name: str, sampled_p99: float, role: str) -> dict:
    """Compare a role's OFFLINE sampled p99 against the LIVE histogram's
    p99 bucket (observability/hist) and fail when they disagree beyond
    bucket resolution — the live plane and the bench must tell the same
    story or one of them is lying.  The two measurements bracket
    slightly different windows (e.g. client-create→watch-observed bind
    vs queue-admission→bind-ack), so one factor-2 bucket of slack is
    allowed on each side of the live bucket's bounds."""
    from minisched_tpu.observability import hist

    bounds = hist.quantile_bounds(name, 0.99)
    if bounds is None:
        raise SystemExit(
            f"[{role}] LIVE HISTOGRAM {name!r} IS EMPTY — the telemetry "
            f"instrumentation regressed (sampled p99 {sampled_p99}s exists)"
        )
    lo, hi = bounds
    if not (lo / 2.0 <= sampled_p99 <= hi * 2.0):
        raise SystemExit(
            f"[{role}] LIVE/SAMPLED P99 DISAGREE beyond bucket "
            f"resolution for {name}: sampled {sampled_p99}s vs live "
            f"bucket ({lo}, {hi}]s"
        )
    log(
        f"[{role}] live {name} p99 bucket ({lo}, {hi}]s agrees with "
        f"sampled {sampled_p99}s"
    )
    return {"lo_s": lo, "le_s": hi}


def bench_skip(reason: str) -> None:
    """Abort THIS role as 'skipped' rather than failed: the child prints
    a ``{"skipped": reason}`` record and exits 0, so the merged artifact
    distinguishes 'this environment can't run the role' (e.g. requires a
    real TPU) from a real regression — the ROADMAP's re-earn tracking
    needs that difference to be visible in BENCH_r06+."""
    raise SystemExit(f"BENCH_SKIP: {reason}")


#: stderr patterns that mean "this role needs capabilities the current
#: device doesn't have", not "the code is broken".  Only consulted in
#: the FAILING traceback region of the tail (see _skip_reason) — a
#: benign startup warning elsewhere in the tail must never convert a
#: real failure into a skip.
_TPU_GAP_PATTERNS = (
    r"(?P<reason>Mosaic[^\n]*(?:not supported|unsupported|requires[^\n]*TPU))",
    r"(?P<reason>Pallas[^\n]*(?:not supported|unsupported|only[^\n]*TPU))",
)


def _skip_reason(stderr_tail: str) -> str:
    """Non-empty reason when the failure tail says 'requires TPU' (or a
    role opted out via bench_skip); '' for real failures.  The explicit
    BENCH_SKIP marker matches anywhere; the fuzzy capability patterns
    only match inside the last traceback — the part that actually
    explains the nonzero exit."""
    import re

    m = re.search(r"BENCH_SKIP:\s*(?P<reason>.+)", stderr_tail)
    if m:
        return m.group("reason").strip()
    idx = stderr_tail.rfind("Traceback (most recent call last)")
    if idx < 0:
        return ""
    region = stderr_tail[idx:]
    for pat in _TPU_GAP_PATTERNS:
        m = re.search(pat, region)
        if m:
            return m.group("reason").strip()
    return ""


class BenchChildError(RuntimeError):
    """A child role failed; carries its stderr tail so the merged record
    (and a human reading it) sees WHY, not just ``rc=1``."""

    def __init__(self, msg: str, stderr_tail: str = ""):
        super().__init__(msg)
        self.stderr_tail = stderr_tail


def _mk_cluster(n_nodes: int, n_pods: int, seed: int = 1234, unsched: float = 0.2):
    from minisched_tpu.api.objects import make_node, make_pod

    rng = random.Random(seed)
    nodes = sorted(
        (
            make_node(f"node{i:05d}", unschedulable=rng.random() < unsched)
            for i in range(n_nodes)
        ),
        key=lambda n: n.metadata.name,
    )
    pods = [make_pod(f"pod{i}") for i in range(n_pods)]
    return nodes, pods


def bench_config1() -> dict:
    """README scenario via the live engine (sched.go:70-143)."""
    from minisched_tpu.scenario.runner import ScenarioHarness, readme_scenario
    from minisched_tpu.service.config import default_scheduler_config

    t0 = time.monotonic()
    with ScenarioHarness(default_scheduler_config(time_scale=0.01)) as h:
        bound = readme_scenario(h, log=lambda *_: None)
    assert bound == "node10"
    dt = time.monotonic() - t0
    log(f"[config1] README scenario (event-driven bind): {dt:.2f}s")
    return {"scenario_s": round(dt, 2)}


def bench_config2() -> dict:
    """1k nodes × 1k pods, nodenumber chain, one wave."""
    import jax

    from minisched_tpu.models.tables import build_node_table, build_pod_table
    from minisched_tpu.ops.fused import FusedEvaluator
    from minisched_tpu.plugins.nodenumber import NodeNumber
    from minisched_tpu.plugins.nodeunschedulable import NodeUnschedulable

    nodes, pods = _mk_cluster(1000, 1000, seed=2)
    node_table, _ = build_node_table(nodes)
    pod_table, _ = build_pod_table(pods)
    nn = NodeNumber()
    ev = FusedEvaluator([NodeUnschedulable()], [nn], [nn])
    jax.block_until_ready(ev(pod_table, node_table).choice)  # compile
    best = float("inf")
    for _ in range(3):
        t0 = time.monotonic()
        res = ev(pod_table, node_table)
        jax.block_until_ready(res.choice)
        best = min(best, time.monotonic() - t0)
    log(f"[config2] 1k×1k nodenumber wave: {best*1e3:.1f}ms → {1000/best:,.0f} pods/s")
    return {"wave_ms": round(best * 1e3, 1), "pods_per_sec": round(1000 / best)}


def bench_config3() -> dict:
    """Resource bin-packing, sequential scan (bind-exact), 4k nodes."""
    import jax

    from minisched_tpu.api.objects import make_node, make_pod
    from minisched_tpu.models.tables import build_node_table, build_pod_table
    from minisched_tpu.ops.sequential import SequentialScheduler
    from minisched_tpu.plugins.noderesources import (
        NodeResourcesFit,
        NodeResourcesLeastAllocated,
    )
    from minisched_tpu.plugins.nodeunschedulable import NodeUnschedulable

    rng = random.Random(3)
    n_nodes, n_pods = 4096, int(os.environ.get("BENCH_SCAN_PODS", 4096))
    nodes = sorted(
        (
            make_node(
                f"node{i:05d}",
                capacity={"cpu": rng.choice(["4", "8"]), "memory": "16Gi", "pods": 110},
            )
            for i in range(n_nodes)
        ),
        key=lambda n: n.metadata.name,
    )
    pods = [
        make_pod(
            f"pod{i}",
            requests={"cpu": rng.choice(["500m", "1", "2"]), "memory": "2Gi"},
        )
        for i in range(n_pods)
    ]
    node_table, node_names = build_node_table(nodes)
    pod_table, _ = build_pod_table(pods)
    sched = SequentialScheduler(
        [NodeUnschedulable(), NodeResourcesFit()], [], [NodeResourcesLeastAllocated()]
    )
    t0 = time.monotonic()
    _, choice, _ = sched(pod_table, node_table)
    jax.block_until_ready(choice)
    compile_dt = time.monotonic() - t0
    t0 = time.monotonic()
    _, choice, _ = sched(pod_table, node_table)
    jax.block_until_ready(choice)
    dt = time.monotonic() - t0
    placed = int((choice >= 0).sum())
    log(
        f"[config3] {n_nodes} nodes × {n_pods} pods Fit+LeastAllocated "
        f"SEQUENTIAL scan: {dt:.2f}s → {n_pods/dt:,.0f} pods/s "
        f"({placed} placed; compile {compile_dt:.1f}s)"
    )

    # FULL-run parity vs the stateful vectorized oracle (VERDICT r4
    # item 4: the machinery existed, config3 just didn't use it) — every
    # placement of the run, independent host math, LeastAllocated-only
    # score mode
    import numpy as np

    from minisched_tpu.engine.oracle import FullRosterScanOracle
    from minisched_tpu.models.tables import (
        DEFAULT_NONZERO_CPU,
        DEFAULT_NONZERO_MEM_MIB,
    )

    t0 = time.monotonic()
    vec = FullRosterScanOracle(
        nodes, DEFAULT_NONZERO_CPU, DEFAULT_NONZERO_MEM_MIB,
        with_balanced=False,
    ).place_all(pods)
    vec_dt = time.monotonic() - t0
    got_all = np.asarray(choice.tolist()[:n_pods])
    mismatch = np.flatnonzero(vec != got_all)
    if mismatch.size:
        for i in mismatch[:10]:
            log(
                f"config3 PARITY MISMATCH {pods[i].metadata.name}: "
                f"oracle={int(vec[i])} scan={int(got_all[i])}"
            )
        raise SystemExit(
            f"config3 parity FAILED on {mismatch.size}/{n_pods} pods"
        )
    log(
        f"[config3] FULL-RUN parity vs vectorized oracle OK "
        f"({n_pods} pods in {vec_dt:.1f}s)"
    )

    # scalar prefix still anchors the vectorized oracle to the
    # reference-shaped loop
    k = int(os.environ.get("BENCH_PARITY_PODS", 24))
    from minisched_tpu.engine.scheduler import schedule_pods_sequentially
    from minisched_tpu.framework.nodeinfo import build_node_infos

    oracle = schedule_pods_sequentially(
        [NodeUnschedulable(), NodeResourcesFit()], [],
        [NodeResourcesLeastAllocated()], {}, pods[:k],
        build_node_infos(nodes, []),
    )
    got = [node_names[c] if c >= 0 else "" for c in choice.tolist()[:k]]
    if oracle != got:
        raise SystemExit(f"config3 parity FAILED: {oracle} != {got}")
    log(f"[config3] prefix parity vs stateful oracle OK ({k} pods)")
    return {
        "scan_s": round(dt, 2),
        "pods_per_sec": round(n_pods / dt),
        "parity_checked": n_pods,
        "parity_prefix": k,
    }


def bench_config4() -> dict:
    """InterPodAffinity + PodTopologySpread wave with constraint tables."""
    import jax

    from minisched_tpu.api.objects import (
        Affinity,
        LabelSelector,
        PodAffinity,
        PodAffinityTerm,
        TopologySpreadConstraint,
        make_node,
        make_pod,
    )
    from minisched_tpu.models.constraints import build_constraint_tables
    from minisched_tpu.models.tables import build_node_table, build_pod_table
    from minisched_tpu.ops.fused import FusedEvaluator
    from minisched_tpu.plugins.interpodaffinity import InterPodAffinity
    from minisched_tpu.plugins.nodeunschedulable import NodeUnschedulable
    from minisched_tpu.plugins.podtopologyspread import PodTopologySpread

    rng = random.Random(4)
    zones = [f"z{i}" for i in range(8)]
    n_nodes, n_pods = 2048, 2048
    nodes = sorted(
        (
            make_node(f"node{i:05d}", labels={"zone": rng.choice(zones)})
            for i in range(n_nodes)
        ),
        key=lambda n: n.metadata.name,
    )
    assigned = []
    for i in range(512):
        p = make_pod(f"asg{i}", labels={"app": f"app{rng.randrange(8)}"})
        p.metadata.uid = f"asg{i}"
        p.spec.node_name = rng.choice(nodes).metadata.name
        assigned.append(p)
    pods = []
    for i in range(n_pods):
        app = f"app{rng.randrange(8)}"
        pod = make_pod(f"pod{i}", labels={"app": app})
        pod.spec.affinity = Affinity(
            pod_affinity=PodAffinity(
                required=[
                    PodAffinityTerm(
                        label_selector=LabelSelector(match_labels={"app": app}),
                        topology_key="zone",
                    )
                ]
            )
        )
        pod.spec.topology_spread_constraints = [
            TopologySpreadConstraint(
                max_skew=2,
                topology_key="zone",
                when_unsatisfiable="ScheduleAnyway",
                label_selector=LabelSelector(match_labels={"app": app}),
            )
        ]
        pods.append(pod)
    by_node = {}
    for p in assigned:
        by_node.setdefault(p.spec.node_name, []).append(p)
    # pre-load the packed-transfer splitter executables for these exact
    # capacities (one program load each, persistent-cached): the
    # timed section below measures the steady-state host build.  The
    # constraint planes' shapes are capacity-driven (C/T/C2/Vd pad to 8,
    # D is the MAX_DOMAINS constant), so a 1-pod build with one affinity
    # + one spread term hits the same schema as the full build.
    from minisched_tpu.models.tables import pad_to

    ncap, pcap = pad_to(n_nodes), pad_to(n_pods)
    t0 = time.monotonic()
    build_node_table(nodes[:2], capacity=ncap)
    build_pod_table(pods[:1], capacity=pcap)
    build_constraint_tables(
        pods[:1], nodes[:2], [], pod_capacity=pcap, node_capacity=ncap
    )
    log(f"[config4] splitter warmup: {time.monotonic() - t0:.1f}s")
    t0 = time.monotonic()
    node_table, _ = build_node_table(nodes, by_node)
    pod_table, _ = build_pod_table(pods)
    extra = build_constraint_tables(
        pods, nodes, assigned,
        pod_capacity=pod_table.capacity, node_capacity=node_table.capacity,
    )
    build_dt = time.monotonic() - t0
    ipa, ts = InterPodAffinity(), PodTopologySpread()
    ev = FusedEvaluator([NodeUnschedulable(), ipa, ts], [], [ipa, ts])
    jax.block_until_ready(ev(pod_table, node_table, extra).choice)  # compile
    best = float("inf")
    for _ in range(3):
        t0 = time.monotonic()
        res = ev(pod_table, node_table, extra)
        jax.block_until_ready(res.choice)
        best = min(best, time.monotonic() - t0)
    placed = int((res.choice >= 0).sum())
    log(
        f"[config4] {n_nodes} nodes × {n_pods} pods affinity+spread wave: "
        f"{best*1e3:.1f}ms → {n_pods/best:,.0f} pods/s ({placed} placed; "
        f"host constraint build {build_dt:.1f}s)"
    )
    return {
        "wave_ms": round(best * 1e3, 1),
        "pods_per_sec": round(n_pods / best),
        "host_build_s": round(build_dt, 2),
    }


#: max_skew used by the c5x spread pods AND enforced by the audit
C5_MAX_SKEW = 4


def _c5_cluster(client, n_nodes: int, n_pods: int, n_special: int,
                n_crosspod: int = 0):
    """The config5 cluster: 20% cordoned nodes, plain pods + 2% pods that
    need a node label no node has yet (+ optionally ``n_crosspod`` pods
    carrying a zone topology-spread constraint — they ride the live
    engine's bind-exact sequential scan)."""
    from minisched_tpu.api.objects import (
        LabelSelector,
        TopologySpreadConstraint,
        make_node,
        make_pod,
    )

    rng = random.Random(55)
    normal_nodes = []
    nodes = []
    for i in range(n_nodes):
        node = make_node(
            f"node{i:05d}",
            unschedulable=rng.random() < 0.2,
            capacity={"cpu": "8", "memory": "16Gi", "pods": 110},
            labels={"zone": f"z{i % 16}"},
        )
        nodes.append(node)
        if not node.spec.unschedulable:
            normal_nodes.append(node.metadata.name)
    # batched seed: one store transaction per batch (create() per object
    # paid a lock round-trip + per-watcher fanout each)
    client.nodes().create_many(nodes, return_objects=False)
    client.pods().create_many(
        [
            make_pod(f"pod{i:06d}", requests={"cpu": "500m", "memory": "256Mi"})
            for i in range(n_pods - n_special - n_crosspod)
        ],
        return_objects=False,
    )
    for i in range(n_crosspod):
        app = f"app{i % 32}"
        pod = make_pod(
            f"spread{i:05d}",
            requests={"cpu": "500m", "memory": "256Mi"},
            labels={"app": app},
        )
        pod.spec.topology_spread_constraints = [
            TopologySpreadConstraint(
                max_skew=C5_MAX_SKEW,
                topology_key="zone",
                when_unsatisfiable="DoNotSchedule",
                label_selector=LabelSelector(match_labels={"app": app}),
            )
        ]
        client.pods().create(pod)
    for i in range(n_special):
        client.pods().create(
            make_pod(
                f"special{i:05d}",
                requests={"cpu": "500m", "memory": "256Mi"},
                node_selector={"special": "true"},
            )
        )
    return rng, normal_nodes


def bench_config5_fullchain() -> dict:
    """Best-of-N wrapper around the config-5 full-chain run: the child
    runs the whole e2e twice in one warm process — lap 2 pays only a short
    re-trace, not the executable compiles — and reports the better lap
    (ROADMAP S0 replaces this with medians over recorded laps).
    ``BENCH_C5_RUNS=1`` restores single-shot."""
    runs = max(1, int(os.environ.get("BENCH_C5_RUNS", "2")))
    best = None
    for lap in range(runs):
        rec = _bench_config5_fullchain_once()
        log(
            f"[config5/full-chain] lap {lap + 1}/{runs}: "
            f"{rec['total_s']}s e2e"
        )
        if best is None or rec["total_s"] < best["total_s"]:
            best = rec
    best["laps"] = runs
    return best


def _bench_config5_fullchain_once() -> dict:
    """The REAL config 5 (BASELINE.md:33): full default plugin roster,
    10k nodes × 100k pods, driven through the LIVE DeviceScheduler — the
    scheduling queue in the loop, genuinely-unschedulable pods parked in
    the unschedulableQ, then rescheduled via backoff + event-gated requeue
    when a Node label update makes them feasible (the reference's loop
    semantics, minisched/minisched.go:32-113, at three orders of magnitude
    its scale).  Ends with a safety audit: no node over allocatable.
    """
    import threading

    import jax  # noqa: F401  (device warmup shares the process backend)

    from minisched_tpu.controlplane.client import Client
    from minisched_tpu.observability import counters as _counters
    from minisched_tpu.observability.profiling import CycleMetrics
    from minisched_tpu.service.config import default_full_roster_config
    from minisched_tpu.service.service import SchedulerService

    n_nodes = int(os.environ.get("BENCH_C5_NODES", 10_000))
    n_pods = int(os.environ.get("BENCH_C5_PODS", 100_000))
    # 16384: fewer, bigger waves amortize the per-wave host work
    # (snapshot/build/ingest); measured ~2.7s faster e2e than 8192 at
    # 100k pods with the packed single-program path
    max_wave = int(os.environ.get("BENCH_C5_WAVE", 16_384))
    n_special = max(n_pods // 50, 1)  # 2%: parked until nodes gain the label
    # 5% carry a real topology-spread constraint: they exercise the live
    # engine's bind-exact sequential scan (cross-pod coupling at scale),
    # interleaved with the plain pods' repair waves
    n_crosspod = int(os.environ.get("BENCH_C5_CROSSPOD", "0"))

    client = Client()  # unthrottled: the limiter is for API fairness tests
    t_setup = time.monotonic()
    rng, normal_nodes = _c5_cluster(
        client, n_nodes, n_pods, n_special, n_crosspod
    )
    log(
        f"[config5/full-chain] cluster created in {time.monotonic()-t_setup:.1f}s "
        f"({n_nodes} nodes, {n_pods} pods incl. {n_special} initially-"
        f"unschedulable and {n_crosspod} topology-spread-constrained)"
    )

    # count binds through the decision hook, installed BEFORE the engine
    # thread starts (a hook wrapped afterwards can miss early binds)
    bound_n = 0
    bound_mu = threading.Lock()

    def counting_emit(pod, node_name, status):
        nonlocal bound_n
        if node_name:
            with bound_mu:
                bound_n += 1

    def bound_count() -> int:
        with bound_mu:
            return bound_n

    service = SchedulerService(client)
    metrics = CycleMetrics()
    # prewarm=True: the service compiles/cache-loads the wave executable
    # for the live shapes before the engine thread starts (reported as
    # warmup) — the timed run then measures scheduling, not executable load
    t_warm = time.monotonic()
    sched = service.start_scheduler(
        default_full_roster_config(), device_mode=True, max_wave=max_wave,
        on_decision=counting_emit, metrics=metrics, prewarm=True,
        # the scan/blocked lanes only run when the workload carries
        # cross-pod-constrained pods — plain config5 skips their warms
        prewarm_scan=n_crosspod > 0,
    )
    t0 = time.monotonic()
    log(f"[config5/full-chain] engine warmup+start: {t0-t_warm:.1f}s")

    def wait_until(pred, timeout, what):
        deadline = time.monotonic() + timeout
        last_log = time.monotonic()
        while time.monotonic() < deadline:
            if pred():
                return
            if time.monotonic() - last_log > 15:
                last_log = time.monotonic()
                snap = metrics.snapshot()
                log(
                    f"[config5/full-chain] ... bound={bound_count()} "
                    f"queue={sched.queue.stats()} "
                    f"waves={int(snap.get('wave', {}).get('count', 0))}"
                )
            time.sleep(0.05)  # fine-grained: the poll is part of the metric
        raise SystemExit(f"[config5/full-chain] timed out waiting for {what}")

    target_first = n_pods - n_special
    wait_until(
        lambda: bound_count() >= target_first
        and sched.queue.stats()["unschedulable"] == n_special,
        timeout=1800,
        what=f"{target_first} pods bound + {n_special} parked",
    )
    t_drain = time.monotonic() - t0
    log(
        f"[config5/full-chain] first drain: {target_first} pods bound, "
        f"{n_special} parked unschedulable, {t_drain:.1f}s"
    )

    # make the parked pods feasible: label a slice of schedulable nodes —
    # the Node UPDATE_NODE_LABEL events replay them through backoff.  The
    # slice must supply ample headroom: labeled nodes already carry ~12
    # normal pods (≈6000m of 8000m) so each offers ~3-4 cpu slots; one
    # labeled node per parked pod gives ~3× the needed capacity
    t_label = time.monotonic()
    for name in rng.sample(normal_nodes, min(len(normal_nodes), n_special)):
        node = client.nodes().get(name)
        node.metadata.labels["special"] = "true"
        client.nodes().update(node)
    label_loop_s = time.monotonic() - t_label
    t_wait = time.monotonic()
    wait_until(
        lambda: bound_count() >= n_pods, timeout=600, what=f"all {n_pods} bound"
    )
    bound_wait_s = time.monotonic() - t_wait
    log(
        f"[config5/full-chain] requeue tail: label loop {label_loop_s:.2f}s, "
        f"bound-wait {bound_wait_s:.2f}s"
    )
    elapsed = time.monotonic() - t0
    # snapshot NOW, not after the audits: the engine keeps idling in
    # pop_batch until shutdown, and post-measurement idle would inflate
    # loop_pop past the window the accounting must sum to
    snap = metrics.snapshot()
    service.shutdown_scheduler()

    # ---- safety audit: no node over allocatable --------------------------
    from collections import defaultdict

    cpu = defaultdict(int)
    mem = defaultdict(int)
    cnt = defaultdict(int)
    for p in client.pods().list():
        r = p.resource_requests()
        cpu[p.spec.node_name] += r.milli_cpu
        mem[p.spec.node_name] += r.memory
        cnt[p.spec.node_name] += 1
    over = []
    special_nodes = set()
    for node in client.nodes().list():
        name = node.metadata.name
        alloc = node.status.allocatable
        if cpu[name] > alloc.milli_cpu or mem[name] > alloc.memory or cnt[name] > alloc.pods:
            over.append(name)
        if cnt[name] and node.spec.unschedulable:
            over.append(f"{name} (unschedulable but has pods)")
        if node.metadata.labels.get("special") == "true":
            special_nodes.add(name)
    if over:
        raise SystemExit(f"[config5/full-chain] SAFETY AUDIT FAILED: {over[:10]}")
    misplaced = [
        p.metadata.name
        for p in client.pods().list()
        if p.spec.node_selector and p.spec.node_name not in special_nodes
    ]
    if misplaced:
        raise SystemExit(
            f"[config5/full-chain] selector violation: {misplaced[:10]}"
        )

    if n_crosspod:
        # hard audit of the DoNotSchedule spread constraints: per app,
        # max-min zone spread over schedulable nodes must respect max_skew
        zone_of = {}
        eligible_zones = set()
        for n in client.nodes().list():
            zone_of[n.metadata.name] = n.metadata.labels.get("zone")
            if not n.spec.unschedulable and n.metadata.labels.get("zone"):
                eligible_zones.add(n.metadata.labels["zone"])
        per_app: dict = {}
        for p in client.pods().list():
            if not p.metadata.name.startswith("spread"):
                continue
            app = p.metadata.labels.get("app")
            zone = zone_of.get(p.spec.node_name)
            per_app.setdefault(app, {}).setdefault(zone, 0)
            per_app[app][zone] += 1
        # domains from the cluster itself — only zones a pod COULD land
        # in (a fully-cordoned zone legitimately stays at 0)
        all_zones = sorted(eligible_zones)
        violations = []
        for app, zones in per_app.items():
            counts = [zones.get(z, 0) for z in all_zones]
            if max(counts) - min(counts) > C5_MAX_SKEW:
                violations.append((app, counts))
        if violations:
            raise SystemExit(
                f"[config5/full-chain] SPREAD SKEW VIOLATED: {violations[:3]}"
            )
        log(
            f"[config5/full-chain] spread audit OK: {len(per_app)} apps × "
            f"{len(all_zones)} zones within max_skew={C5_MAX_SKEW}"
        )

    waves = int(snap.get("wave", {}).get("count", 0))
    log(
        f"[config5/full-chain] {n_pods} pods via live wave engine in "
        f"{elapsed:.1f}s → {n_pods/elapsed:,.0f} pods/s end-to-end "
        f"({waves} waves; {n_special} pods parked→requeued→bound; "
        f"safety audit OK over {n_nodes} nodes)"
    )
    log("[config5/full-chain] phase timings:\n" + metrics.report())

    def phase(name, field):
        return round(snap.get(name, {}).get(field, 0.0), 3)

    # engine-thread wall accounting (VERDICT r4 item 3): pop waits +
    # schedule_wave + drain-time scan flushes + GC sweeps must sum to
    # ~total_s; what's left is genuine loop overhead (Python glue between
    # timers) and the bench's own 50ms poll granularity at each boundary
    accounted = (
        phase("loop_pop", "total_s")
        + phase("wave", "total_s")
        + phase("scan_flush", "total_s")
        + phase("loop_gc", "total_s")
    )
    log(
        f"[config5/full-chain] e2e accounting: pop {phase('loop_pop', 'total_s')}s"
        f" + waves {phase('wave', 'total_s')}s"
        f" + scan-flush {phase('scan_flush', 'total_s')}s"
        f" + gc {phase('loop_gc', 'total_s')}s"
        f" = {accounted:.2f}s of {elapsed:.2f}s"
        f" (unaccounted {elapsed - accounted:+.2f}s)"
    )

    return {
        "pods_per_sec_e2e": round(n_pods / elapsed, 1),
        "waves": waves,
        "requeued": n_special,
        "first_drain_s": round(t_drain, 1),
        "requeue_tail_s": round(elapsed - t_drain, 1),
        "requeue_label_loop_s": round(label_loop_s, 2),
        "requeue_bound_wait_s": round(bound_wait_s, 2),
        "total_s": round(elapsed, 1),
        "crosspod_pods": n_crosspod,
        "wave_evaluate_mean_s": phase("wave_evaluate", "mean_s"),
        "wave_evaluate_total_s": phase("wave_evaluate", "total_s"),
        "scan_evaluate_total_s": phase("scan_evaluate", "total_s"),
        "bind_total_s": phase("bind", "total_s"),
        # per-wave breakdown of the evaluate wall (VERDICT r3 item 1):
        # snapshot → table build → constraint build → device call; the
        # device term includes the packed flat-buffer transfer + fetch
        # engine-thread wall accounting: these four sum to ~total_s
        "e2e_accounting": {
            "pop_total_s": phase("loop_pop", "total_s"),
            "wave_total_s": phase("wave", "total_s"),
            "scan_flush_total_s": phase("scan_flush", "total_s"),
            "gc_total_s": phase("loop_gc", "total_s"),
            "unaccounted_s": round(elapsed - accounted, 2),
        },
        "wave_breakdown": {
            "snapshot_total_s": phase("wave_snapshot", "total_s"),
            "assigned_list_total_s": phase("wave_assigned_list", "total_s"),
            "winners_total_s": phase("wave_winners", "total_s"),
            "postfetch_total_s": phase("wave_postfetch", "total_s"),
            "build_tables_total_s": phase("wave_build_tables", "total_s"),
            "build_constraints_total_s": phase(
                "wave_build_constraints", "total_s"
            ),
            "device_total_s": phase("wave_device", "total_s"),
            "device_mean_s": phase("wave_device", "mean_s"),
            "scan_build_total_s": phase("scan_build", "total_s"),
            "scan_build_nodes_total_s": phase("scan_build_nodes", "total_s"),
            "scan_build_pods_total_s": phase("scan_build_pods", "total_s"),
            "scan_build_constraints_total_s": phase(
                "scan_build_constraints", "total_s"
            ),
            "scan_grouping_total_s": phase("scan_grouping", "total_s"),
            "losers_handle_total_s": phase("losers_handle", "total_s"),
            "commit_total_s": phase("commit", "total_s"),
            "constraints_lock_wait_s": phase(
                "constraints_lock_wait", "total_s"
            ),
            "constraints_store_list_s": phase(
                "constraints_store_list", "total_s"
            ),
            # multi-chip live wave engine (ISSUE 7): the mesh factoring
            # this engine acquired (0s = single-device run), sharded-wave
            # and fallback counts, and the pad-waste ledger — all-zero
            # unless the box exposes >1 device (or MINISCHED_MESH=1)
            "wave_mesh": {
                "pod_shards": _counters.get("wave_mesh.pod_shards"),
                "node_shards": _counters.get("wave_mesh.node_shards"),
                "waves": _counters.get("wave_mesh.waves"),
                "fallbacks": _counters.get("wave_mesh.fallbacks"),
                "pad_pod_rows": _counters.get("wave_mesh.pad_pod_rows"),
                "pad_node_rows": _counters.get("wave_mesh.pad_node_rows"),
            },
        },
        # the pipelined wave engine's overlap ledger: stall is loop-thread
        # time the device sat idle waiting for a build; overlap_ratio is
        # the build wall hidden behind device/commit windows
        "pipeline": {
            "enabled": os.environ.get("MINISCHED_PIPELINE", "1")
            not in ("", "0"),
            "waves": _counters.get("wave_pipeline.waves"),
            "build_total_s": phase("wave_pipeline_build", "total_s"),
            "stall_total_s": phase("wave_pipeline_stall", "total_s"),
            "overlap_ratio": (
                round(
                    1.0
                    - phase("wave_pipeline_stall", "total_s")
                    / phase("wave_pipeline_build", "total_s"),
                    3,
                )
                if phase("wave_pipeline_build", "total_s") > 0
                else 0.0
            ),
            "build_fallbacks": _counters.get("wave_pipeline.build_fallback"),
            "rearb_requeued": _counters.get("wave_pipeline.rearb_requeued"),
            "dirty_rows": _counters.get("wave_pipeline.dirty_rows"),
        },
    }


def bench_fullchain_parity() -> dict:
    """Full-chain bit-exact parity at 10k×100k (BASELINE.md's metric is
    pods/sec WITH placement parity): the full-roster sequential device
    scan over the whole config5 cluster — bind-exact by construction —
    prefix-checked against the scalar oracle (the Go-loop re-creation).
    The scan placements of pod i depend only on pods < i, so an oracle
    prefix is an exact check; the scan itself runs the FULL 100k pods
    and its throughput is reported as the bind-exact mode's number."""
    import jax

    from minisched_tpu.controlplane.client import Client
    from minisched_tpu.engine.scheduler import schedule_pods_sequentially
    from minisched_tpu.framework.nodeinfo import build_node_infos
    from minisched_tpu.models.constraints import build_constraint_tables
    from minisched_tpu.models.tables import (
        build_node_table,
        build_pod_table,
        pad_to,
    )
    from minisched_tpu.ops.sequential import SequentialScheduler
    from minisched_tpu.plugins.registry import build_plugins
    from minisched_tpu.service.config import default_full_roster_config
    from minisched_tpu.service.service import _inject

    n_nodes = int(os.environ.get("BENCH_C5_NODES", 10_000))
    n_pods = int(os.environ.get("BENCH_C5_PODS", 100_000))
    # parity is proven by the vectorized oracle over ALL n_pods below; the
    # scalar loop (2-30 pods/s) only anchors that oracle, so a 256-pod
    # prefix keeps the anchor while saving ~6min of bench wall vs 1024
    k = int(os.environ.get("BENCH_FULLCHAIN_PREFIX", 256))

    client = Client()
    t0 = time.monotonic()
    _c5_cluster(client, n_nodes, n_pods, max(n_pods // 50, 1))
    nodes = sorted(client.nodes().list(), key=lambda n: n.metadata.name)
    pods = client.pods().list()  # store order == creation order
    log(f"[fullchain-parity] cluster created in {time.monotonic()-t0:.1f}s")

    cfg = default_full_roster_config()
    chains = build_plugins(cfg)
    for pl in chains.needs_client:
        _inject(pl, "store_client", client)
    sched = SequentialScheduler(
        chains.filter, chains.pre_score, chains.score,
        weights=cfg.score_weights(),
    )
    t0 = time.monotonic()
    node_table, node_names = build_node_table(nodes)
    # one-shot build: the 131k-row slow pod schema's wide affinity/port
    # planes are all-zero here — materialize them on device instead of
    # shipping them (batched_device_put elide_zeros)
    pod_table, _ = build_pod_table(
        pods, capacity=pad_to(n_pods), elide_zeros=True
    )
    extra = build_constraint_tables(
        pods, nodes, [],
        pod_capacity=pod_table.capacity, node_capacity=node_table.capacity,
        scan_planes=True,
    )
    log(f"[fullchain-parity] host build: {time.monotonic()-t0:.1f}s")
    t0 = time.monotonic()
    _, choice, _ = sched(pod_table, node_table, extra)
    jax.block_until_ready(choice)
    compile_dt = time.monotonic() - t0
    t0 = time.monotonic()
    _, choice, _ = sched(pod_table, node_table, extra)
    choice = jax.device_get(choice)
    scan_dt = time.monotonic() - t0
    placed = int((choice[:n_pods] >= 0).sum())
    log(
        f"[fullchain-parity] full-roster sequential scan: {n_pods} pods × "
        f"{n_nodes} nodes in {scan_dt:.1f}s → {n_pods/scan_dt:,.0f} pods/s "
        f"bind-exact ({placed} placed; compile {compile_dt:.1f}s)"
    )

    # layer 1 — the vectorized host oracle verifies EVERY placement of the
    # full run (engine/oracle.py: same decision rule, independent host
    # math; VERDICT r3 item 2 — "bit-exact" must cover the whole run, not
    # a ≤1% sample)
    import numpy as np

    from minisched_tpu.engine.oracle import fullchain_scan_oracle

    t0 = time.monotonic()
    vec_choices = fullchain_scan_oracle(pods, nodes)
    vec_dt = time.monotonic() - t0
    got_all = np.asarray(choice[:n_pods])
    full_mismatch = np.flatnonzero(vec_choices != got_all)
    if full_mismatch.size:
        for i in full_mismatch[:10]:
            log(
                f"FULL-CHAIN PARITY MISMATCH {pods[i].metadata.name}: "
                f"oracle={int(vec_choices[i])} scan={int(got_all[i])}"
            )
        raise SystemExit(
            f"full-chain parity FAILED on {full_mismatch.size}/{n_pods} pods"
        )
    log(
        f"[fullchain-parity] FULL-RUN parity vs vectorized oracle OK "
        f"({n_pods} pods in {vec_dt:.1f}s → {n_pods/vec_dt:,.0f} pods/s)"
    )

    # layer 2 — the scalar reference-shaped loop anchors the vectorized
    # oracle on a prefix (slow: 3-30 pods/s)
    t0 = time.monotonic()
    oracle = schedule_pods_sequentially(
        chains.filter, chains.pre_score, chains.score, cfg.score_weights(),
        [p.clone() for p in pods[:k]], build_node_infos(nodes, []),
    )
    oracle_dt = time.monotonic() - t0
    got = [node_names[c] if c >= 0 else "" for c in choice.tolist()[:k]]
    mismatches = [
        (pods[i].metadata.name, oracle[i], got[i])
        for i in range(k)
        if oracle[i] != got[i]
    ]
    if mismatches:
        for name, want, g in mismatches[:10]:
            log(f"FULL-CHAIN PARITY MISMATCH {name}: oracle={want!r} scan={g!r}")
        raise SystemExit(
            f"full-chain parity FAILED on {len(mismatches)}/{k} prefix pods"
        )
    log(
        f"[fullchain-parity] prefix parity vs scalar oracle OK ({k} pods; "
        f"oracle {oracle_dt:.1f}s → {k/oracle_dt:,.1f} pods/s)"
    )

    # layer 3 — SAMPLED single-step scalar checks across the WHOLE run
    # (VERDICT r4 item 4: a prefix never samples late-run state — nearly
    # full nodes, thin feasible sets).  One forward pass replays the
    # verified placements into NodeInfos; at each sampled index the
    # scalar chain (the reference-shaped decision, minisched.go:50-80)
    # decides pod i against that exact mid-run state and must agree.
    from minisched_tpu.engine.scheduler import schedule_pod_once
    from minisched_tpu.framework.types import FitError as _FitError

    anchor_n = int(os.environ.get("BENCH_ANCHOR_PODS", 1000))
    t0 = time.monotonic()
    sample = set(
        np.linspace(0, n_pods - 1, anchor_n, dtype=int).tolist()
    )
    infos = build_node_infos(nodes, [])
    by_idx = {i: ni for i, ni in enumerate(infos)}
    anchor_mismatch = []
    for i, pod in enumerate(pods):
        if i in sample:
            try:
                want = schedule_pod_once(
                    chains.filter, chains.pre_score, chains.score,
                    cfg.score_weights(), pod.clone(), infos,
                )
            except _FitError:
                want = ""
            c = int(got_all[i])
            have = node_names[c] if c >= 0 else ""
            if want != have:
                anchor_mismatch.append((pod.metadata.name, want, have))
        c = int(got_all[i])
        if c >= 0:
            committed = pod.clone()
            committed.spec.node_name = node_names[c]
            by_idx[c].add_pod(committed)
    anchor_dt = time.monotonic() - t0
    if anchor_mismatch:
        for name, want, have in anchor_mismatch[:10]:
            log(
                f"SCALAR ANCHOR MISMATCH {name}: scalar={want!r} "
                f"scan={have!r}"
            )
        raise SystemExit(
            f"scalar anchor FAILED on {len(anchor_mismatch)}/{anchor_n} "
            "sampled pods"
        )
    log(
        f"[fullchain-parity] scalar anchor OK: {anchor_n} single-step "
        f"checks sampled across the run ({anchor_dt:.1f}s)"
    )
    return {
        "scan_total_s": round(scan_dt, 2),
        "scan_pods_per_sec": round(n_pods / scan_dt),
        "parity_checked_fullchain": n_pods,
        "scalar_anchor_prefix": k,
        "scalar_anchor_sampled": anchor_n,
        "vec_oracle_pods_per_sec": round(n_pods / vec_dt),
        "oracle_pods_per_sec": round(k / oracle_dt, 1),
    }


def bench_headline() -> dict:
    n_nodes = int(os.environ.get("BENCH_NODES", 10_000))
    n_pods = int(os.environ.get("BENCH_PODS", 100_000))
    wave = int(os.environ.get("BENCH_WAVE", 8_192))
    # parity + baseline sample: the SAME ≥500-pod random sample is both
    # oracle-timed (the vs_baseline denominator) and compared placement-by-
    # placement against the wave output (the north star is pods/sec WITH
    # bit-exact parity — BASELINE.md)
    sample_n = int(os.environ.get("BENCH_PARITY_SAMPLE", 500))

    import jax

    from minisched_tpu.engine.scheduler import schedule_pod_once
    from minisched_tpu.framework.nodeinfo import build_node_infos
    from minisched_tpu.framework.types import FitError
    from minisched_tpu.models.tables import (
        build_node_table,
        build_pod_table,
        pad_to,
    )
    from minisched_tpu.ops.fused import BatchContext
    from minisched_tpu.ops.state import wave_step
    from minisched_tpu.plugins.nodenumber import NodeNumber
    from minisched_tpu.plugins.nodeunschedulable import NodeUnschedulable

    log(f"building cluster: {n_nodes} nodes, {n_pods} pods ...")
    nodes, pods = _mk_cluster(n_nodes, n_pods)

    # pre-load the table-splitter executables for the exact capacities the
    # real build uses (persistent-cache hits, but each program still has
    # to be loaded — pay it in the warmup, not in the timed host build)
    t0 = time.monotonic()
    build_node_table(nodes[:2], capacity=pad_to(n_nodes))
    build_pod_table(pods[:1], capacity=max(wave, 128))
    log(f"splitter warmup: {time.monotonic() - t0:.1f}s")

    t0 = time.monotonic()
    node_table, node_names = build_node_table(nodes)
    pod_waves = []
    for start in range(0, n_pods, wave):
        chunk = pods[start : start + wave]
        table, _ = build_pod_table(chunk, capacity=max(wave, 128))
        pod_waves.append(table)
    build_wall = time.monotonic() - t0
    log(f"host table build: {build_wall:.1f}s, {len(pod_waves)} waves")

    nn = NodeNumber()
    use_pallas = (
        os.environ.get("BENCH_KERNEL", "pallas") == "pallas"
        and jax.default_backend() == "tpu"  # Mosaic-only; XLA path elsewhere
    )
    if use_pallas:
        # fully-fused flagship kernel (ops/pallas_kernels.py): only table
        # columns touch HBM; bit-exact with the generic evaluator (tested)
        from minisched_tpu.ops.pallas_kernels import nodenumber_select_hosts
        from minisched_tpu.ops.state import apply_placements

        def _pallas_step(node_table, pod_table):
            choice, best = nodenumber_select_hosts(pod_table, node_table)
            return apply_placements(node_table, pod_table, choice), choice, best

        step = jax.jit(_pallas_step, donate_argnums=(0,))
        log("headline kernel: pallas (fused nodenumber chain)")
    else:
        step = jax.jit(
            partial(
                wave_step,
                filter_plugins=(NodeUnschedulable(),),
                pre_score_plugins=(nn,),
                score_plugins=(nn,),
                ctx=BatchContext(weights=(("NodeNumber", 1),)),
            ),
            donate_argnums=(0,),
        )
        log("headline kernel: xla (generic fused evaluator)")

    # warmup / compile on a DEVICE-SIDE copy: the step donates its
    # node-table argument, so the warmup consumes a clone — round-tripping
    # the table through the host here would poison every later step with
    # per-call host sync against the put-backed buffers
    t0 = time.monotonic()
    clone = jax.jit(lambda t: jax.tree_util.tree_map(lambda a: a.copy(), t))
    warm_nodes, choice, _ = step(clone(node_table), pod_waves[0])
    jax.block_until_ready(choice)
    del warm_nodes
    compile_wall = time.monotonic() - t0
    log(f"compile+warmup: {compile_wall:.1f}s")

    # make every wave table device-resident, timed separately: the headline
    # measures SCHEDULING throughput with state in HBM (the steady-state
    # regime — the resident node table is the design point, SURVEY.md §7
    # stage 7); host build and H2D transfer are reported on their own
    t0 = time.monotonic()
    jax.block_until_ready(pod_waves)  # every leaf of every wave table
    jax.block_until_ready(node_table)
    transfer_wall = time.monotonic() - t0
    log(f"host→device transfer: {transfer_wall:.2f}s")

    # best of 3 repetitions: host dispatch jitter is the same order as the
    # whole 13-wave schedule (placements are identical across reps: the
    # nodenumber chain is bind-independent).  ROADMAP S0 replaces the
    # minimum with medians over recorded laps
    elapsed = float("inf")
    choices = []
    for _rep in range(3):
        t0 = time.monotonic()
        rep_choices = []
        for pod_table in pod_waves:
            node_table, choice, _ = step(node_table, pod_table)
            rep_choices.append(choice)
        jax.block_until_ready(rep_choices)
        rep_elapsed = time.monotonic() - t0
        if rep_elapsed < elapsed:
            elapsed, choices = rep_elapsed, rep_choices
    placed = 0
    for c in choices:
        placed += int((c >= 0).sum())
    pods_per_sec = n_pods / elapsed
    north_star = build_wall + transfer_wall + elapsed
    log(
        f"[config5/headline] scheduled {n_pods} pods ({placed} placed) against "
        f"{n_nodes} nodes in {elapsed:.3f}s device wall-clock (best of 3) "
        f"→ {pods_per_sec:,.0f} pods/s"
    )
    log(
        f"[north-star] host table build + transfer + schedule = "
        f"{north_star:.2f}s wall-clock for "
        f"{n_pods} pods × {n_nodes} nodes (target <1s, BASELINE.md)"
    )

    # baseline + parity: the sequential scalar oracle (the Go-loop
    # re-creation) on a random sample of the SAME cluster.  The nodenumber
    # chain is stateless w.r.t. placements (scores don't read assignments),
    # so per-pod oracle decisions on the fresh snapshot must equal the wave
    # output EXACTLY — any mismatch fails the bench loudly.
    import numpy as np

    all_choices = np.concatenate(
        [np.asarray(c)[: min(wave, n_pods - i * wave)] for i, c in enumerate(choices)]
    )
    # layer 1 — vectorized host oracle over EVERY pod (engine/oracle.py;
    # VERDICT r3 item 2: headline parity covers the full run)
    from minisched_tpu.engine.oracle import headline_oracle

    t0 = time.monotonic()
    vec_choices = headline_oracle(pods, nodes)
    vec_dt = time.monotonic() - t0
    full_mismatch = np.flatnonzero(vec_choices != all_choices[:n_pods])
    if full_mismatch.size:
        for i in full_mismatch[:10]:
            log(
                f"PARITY MISMATCH {pods[i].metadata.name}: "
                f"oracle={int(vec_choices[i])} wave={int(all_choices[i])}"
            )
        raise SystemExit(
            f"headline parity FAILED on {full_mismatch.size}/{n_pods} pods"
        )
    log(
        f"full-run parity vs vectorized oracle OK ({n_pods} pods in "
        f"{vec_dt:.1f}s)"
    )

    # layer 2 — the scalar loop anchors the vectorized oracle on a sample
    # (and times the vs_baseline denominator)
    rng = random.Random(99)
    sample = rng.sample(range(n_pods), min(sample_n, n_pods))
    node_infos = build_node_infos(nodes, [])
    filters, pre_scores, scores = [NodeUnschedulable()], [nn], [nn]
    mismatches = []
    t0 = time.monotonic()
    for i in sample:
        try:
            oracle_name = schedule_pod_once(
                filters, pre_scores, scores, {}, pods[i], node_infos
            )
        except FitError:
            oracle_name = ""
        got = node_names[all_choices[i]] if all_choices[i] >= 0 else ""
        if oracle_name != got:
            mismatches.append((pods[i].metadata.name, oracle_name, got))
    oracle_elapsed = time.monotonic() - t0
    oracle_pods_per_sec = len(sample) / oracle_elapsed
    log(
        f"oracle: {len(sample)} pods in {oracle_elapsed:.2f}s "
        f"→ {oracle_pods_per_sec:,.1f} pods/s"
    )
    if mismatches:
        for name, want, got in mismatches[:10]:
            log(f"PARITY MISMATCH {name}: oracle={want!r} wave={got!r}")
        raise SystemExit(
            f"headline parity FAILED on {len(mismatches)}/{len(sample)} sampled pods"
        )
    log(f"parity vs scalar oracle OK ({len(sample)} sampled pods)")

    return {
        "metric": "pods_scheduled_per_sec_10k_nodes_100k_pods",
        "value": round(pods_per_sec, 1),
        "unit": "pods/s",
        "vs_baseline": round(pods_per_sec / oracle_pods_per_sec, 2),
        "parity_checked": n_pods,
        "scalar_anchor_sample": len(sample),
        "schedule_wall_s": round(elapsed, 4),
        "build_wall_s": round(build_wall, 2),
        "transfer_wall_s": round(transfer_wall, 2),
        "north_star_s": round(north_star, 2),
        "compile_warmup_s": round(compile_wall, 1),
        "oracle_pods_per_sec": round(oracle_pods_per_sec, 1),
    }


def bench_wire() -> dict:
    """Scheduler-over-HTTP (VERDICT r3 item 3): the device wave engine at
    moderate scale with EVERY informer event and every bind crossing the
    REST boundary (controlplane/remote.py — the reference's client-go ↔
    httptest.Server path, scheduler.go:54,72-73).  Reports the e2e cost
    of the wire next to the in-process numbers."""
    import threading

    from minisched_tpu.api.objects import make_node, make_pod
    from minisched_tpu.controlplane.httpserver import start_api_server
    from minisched_tpu.controlplane.remote import RemoteClient
    from minisched_tpu.service.config import default_full_roster_config
    from minisched_tpu.service.service import SchedulerService

    from minisched_tpu.api.objects import LabelSelector, TopologySpreadConstraint

    n_nodes = int(os.environ.get("BENCH_WIRE_NODES", 1_000))
    n_pods = int(os.environ.get("BENCH_WIRE_PODS", 10_000))
    # ≥0 topology-spread-constrained pods: they cross the wire into the
    # deferral + blocked-scan lane, so the scan-backlog flush re-validation
    # (deleted/recreated pods) runs behind the watch boundary the
    # reference exercises on every event (VERDICT r4 item 5)
    # clamped: the wait loop and skew audit assume n_crosspod ≤ n_pods
    n_crosspod = min(
        int(os.environ.get("BENCH_WIRE_CROSSPOD", "0")), n_pods
    )
    _server, base, shutdown = start_api_server()
    try:
        client = RemoteClient(base)
        rng = random.Random(55)
        t0 = time.monotonic()
        # collection POSTs in chunks: one request per object ran ~380
        # obj/s (29s of setup around a 1.7s measurement); the chunk size
        # bounds request bodies to a few MB
        CHUNK = 2000
        nodes = [
            make_node(
                f"node{i:05d}",
                unschedulable=rng.random() < 0.2,
                capacity={"cpu": "8", "memory": "16Gi", "pods": 110},
                labels={"zone": f"z{i % 16}"},
            )
            for i in range(n_nodes)
        ]
        for start in range(0, len(nodes), CHUNK):
            # return_objects=False: the server batch-creates in ONE store
            # transaction and answers {} per item — the seed path was
            # paying a full encode+transfer+decode per created object
            # that this loop immediately dropped
            client.nodes().create_many(
                nodes[start : start + CHUNK], return_objects=False
            )
        pods = [
            make_pod(
                f"pod{i:06d}",
                requests={"cpu": "500m", "memory": "256Mi"},
            )
            for i in range(n_pods - n_crosspod)
        ]
        for i in range(n_crosspod):
            app = f"app{i % 32}"
            pod = make_pod(
                f"spread{i:05d}",
                requests={"cpu": "500m", "memory": "256Mi"},
                labels={"app": app},
            )
            pod.spec.topology_spread_constraints = [
                TopologySpreadConstraint(
                    max_skew=C5_MAX_SKEW,
                    topology_key="zone",
                    when_unsatisfiable="DoNotSchedule",
                    label_selector=LabelSelector(match_labels={"app": app}),
                )
            ]
            pods.append(pod)
        for start in range(0, len(pods), CHUNK):
            client.pods().create_many(
                pods[start : start + CHUNK], return_objects=False
            )
        setup_dt = time.monotonic() - t0
        log(
            f"[wire] cluster created over HTTP in {setup_dt:.1f}s "
            f"({n_nodes} nodes, {n_pods} pods incl. {n_crosspod} "
            f"topology-spread-constrained; batch POSTs of {CHUNK})"
        )

        bound_n = 0
        mu = threading.Lock()

        def counting(pod, node_name, status):
            nonlocal bound_n
            if node_name:
                with mu:
                    bound_n += 1

        svc = SchedulerService(client)
        t_warm = time.monotonic()
        sched = svc.start_scheduler(
            default_full_roster_config(), device_mode=True, max_wave=4096,
            on_decision=counting, prewarm=True,
            # scan-lane warms only when the workload actually rides the
            # scan (they were most of the ~4min wall for the plain run)
            prewarm_scan=n_crosspod > 0,
        )
        t0 = time.monotonic()
        log(f"[wire] engine warmup+start: {t0 - t_warm:.1f}s")
        deadline = time.monotonic() + 600
        while time.monotonic() < deadline:
            with mu:
                if bound_n >= n_pods:
                    break
            time.sleep(0.2)
        elapsed = time.monotonic() - t0
        svc.shutdown_scheduler()
        if bound_n < n_pods:
            raise SystemExit(f"[wire] only {bound_n}/{n_pods} bound")
        if n_crosspod:
            # the same hard max-skew audit the in-process c5x run ends
            # with — over the wire, reading back through the REST API
            zone_of = {}
            eligible_zones = set()
            for n in client.nodes().list():
                zone_of[n.metadata.name] = n.metadata.labels.get("zone")
                if not n.spec.unschedulable and n.metadata.labels.get("zone"):
                    eligible_zones.add(n.metadata.labels["zone"])
            per_app: dict = {}
            for p in client.pods().list():
                if not p.metadata.name.startswith("spread"):
                    continue
                app = p.metadata.labels.get("app")
                zone = zone_of.get(p.spec.node_name)
                per_app.setdefault(app, {}).setdefault(zone, 0)
                per_app[app][zone] += 1
            all_zones = sorted(eligible_zones)
            for app, zones in per_app.items():
                counts = [zones.get(z, 0) for z in all_zones]
                if max(counts) - min(counts) > C5_MAX_SKEW:
                    raise SystemExit(
                        f"[wire] SPREAD SKEW VIOLATED: {app}: {counts}"
                    )
            log(
                f"[wire] spread audit OK: {len(per_app)} apps × "
                f"{len(all_zones)} zones within max_skew={C5_MAX_SKEW}"
            )
        log(
            f"[wire] {n_pods} pods scheduled OVER HTTP in {elapsed:.1f}s "
            f"→ {n_pods/elapsed:,.0f} pods/s e2e (informers + binds on "
            f"the wire)"
        )
        from minisched_tpu.observability import counters as _counters

        csnap = _counters.snapshot()
        return {
            "pods_per_sec_e2e": round(n_pods / elapsed, 1),
            "total_s": round(elapsed, 1),
            "nodes": n_nodes,
            "pods": n_pods,
            "crosspod_pods": n_crosspod,
            "setup_s": round(setup_dt, 1),
            # pooled keep-alive transport evidence (ISSUE 9): reuses must
            # dwarf opens once the pool is warm, and stale reopens stay
            # incidental
            "wire_counters": {
                k: v for k, v in csnap.items()
                if k.startswith("wire.") or k == "watch.disconnects"
            },
        }
    finally:
        shutdown()


class _WireWatcher:
    """Client half of one raw HTTP watch stream for the wire-fanout
    bench: incremental header + chunked-transfer + JSON-line parsing
    with an O(1) rv extractor (full json.loads per delivery would make
    the CLIENT the bottleneck at 1k watchers on one core)."""

    __slots__ = (
        "sock", "idx", "slow", "buf", "payload", "headers_done", "synced",
        "start_rv", "rvs", "eof", "reading", "resumed_from",
    )

    def __init__(self, sock, idx: int, slow: bool, resumed_from=None):
        self.sock = sock
        self.idx = idx
        self.slow = slow
        self.buf = bytearray()
        self.payload = bytearray()
        self.headers_done = False
        self.synced = False
        self.start_rv = 0
        self.rvs: list = []
        self.eof = False
        self.reading = True
        #: rv this stream resumed from (None = original stream)
        self.resumed_from = resumed_from

    @staticmethod
    def _line_rv(line: bytes) -> int:
        # every event line ends ... "rv": N}\n — "rv" is the last key by
        # construction (httpserver SYNC + event_wire_chunk)
        return int(line[line.rfind(b":") + 1:line.rfind(b"}")])

    def feed(self, data: bytes, now: float, on_event) -> None:
        self.buf += data
        if not self.headers_done:
            end = self.buf.find(b"\r\n\r\n")
            if end < 0:
                return
            head = bytes(self.buf[:end])
            status = head.split(b"\r\n", 1)[0]
            if b"200" not in status:
                # surfaced by the establishment/drain gates (a raise here
                # would only kill the reader thread silently)
                log(f"[wirefan] watcher {self.idx}: bad status {status!r}")
                self.eof = True
                return
            del self.buf[: end + 4]
            self.headers_done = True
        # de-chunk
        while True:
            nl = self.buf.find(b"\r\n")
            if nl < 0:
                break
            size = int(bytes(self.buf[:nl]), 16)
            if size == 0:
                self.eof = True
                break
            if len(self.buf) < nl + 2 + size + 2:
                break
            self.payload += self.buf[nl + 2 : nl + 2 + size]
            del self.buf[: nl + 2 + size + 2]
        # JSON lines (keepalive = blank)
        while True:
            nl = self.payload.find(b"\n")
            if nl < 0:
                break
            line = bytes(self.payload[:nl]).strip()
            del self.payload[: nl + 1]
            if not line:
                continue
            if not self.synced:
                # first line is the SYNC marker: its rv is the resume
                # cursor should we be evicted before any event lands
                self.synced = True
                self.start_rv = self._line_rv(line)
                continue
            self.rvs.append(self._line_rv(line))
            on_event(self, now)

    def last_rv(self) -> int:
        return self.rvs[-1] if self.rvs else self.start_rv


def bench_wire_fanout() -> dict:
    """``make bench-wire``: the 1k-watcher wire regime (ISSUE 9, ROADMAP
    churn follow-up 3) — ≥1000 concurrent REAL HTTP watch streams served
    by the selector stream loop while the store mutates behind them, with
    deliberately-wedged slow watchers driving the wire-level eviction +
    resume path.  Headline: **p99 event-delivery latency** (store commit
    → parsed on a live client stream).  FAILS on:

    * server thread count above ``watchers × BENCH_WIRE_THREAD_FRAC``
      (thread-per-watcher would be ~1000; the loop keeps it ~flat);
    * per-watcher encoding (``watch.fanout.encoded`` not ≪ ``shared``);
    * ZERO evictions (the laggard path never exercised), or an evicted
      watcher that misses or duplicates an event across its
      resume/410→relist reconnect;
    * any live watcher missing any event at drain;
    * p99 delivery latency beyond ``BENCH_WIRE_P99_S``.
    """
    import selectors
    import socket
    import threading

    from minisched_tpu.api.objects import make_pod
    from minisched_tpu.controlplane.httpserver import start_api_server
    from minisched_tpu.controlplane.store import ObjectStore
    from minisched_tpu.observability import counters

    if os.environ.get("MINISCHED_STREAMLOOP", "1") == "0":
        bench_skip("MINISCHED_STREAMLOOP=0: stream loop disabled by env")

    n_watchers = int(os.environ.get("BENCH_WIRE_WATCHERS", "1000"))
    n_slow = min(int(os.environ.get("BENCH_WIRE_SLOW", "10")), n_watchers)
    rate = float(os.environ.get("BENCH_WIRE_EVENTS_PER_S", "25"))
    window_s = float(os.environ.get("BENCH_WIRE_WINDOW_S", "8"))
    pad_bytes = int(os.environ.get("BENCH_WIRE_PAD", "1024"))
    outbuf = int(os.environ.get("BENCH_WIRE_OUTBUF", str(64 * 1024)))
    sndbuf = int(os.environ.get("BENCH_WIRE_SNDBUF", str(32 * 1024)))
    p99_gate_s = float(os.environ.get("BENCH_WIRE_P99_S", "5.0"))
    thread_frac = float(os.environ.get("BENCH_WIRE_THREAD_FRAC", "0.1"))
    drain_s = float(os.environ.get("BENCH_WIRE_DRAIN_S", "120"))
    slow_read_events = 3  # a slow watcher parses this many, then wedges

    counters.reset()
    store = ObjectStore()
    server, base, shutdown = start_api_server(
        store, stream_buffer_bytes=outbuf, stream_sndbuf_bytes=sndbuf
    )
    host, port = base.split("//")[1].split(":")
    port = int(port)

    sel = selectors.DefaultSelector()
    stop = threading.Event()
    t_send: dict = {}  # rv → pre-commit stamp (see the window loop)
    # raw (rv, parse stamp) pairs from LIVE original consumers — slow/
    # resumed streams would pollute p99 with their own wedge time.
    # Latencies resolve AFTER the run: a delivery can beat the bench
    # thread's own return from store.create, so a live t_send lookup
    # here would silently drop exactly the fastest samples.
    recv_log: list = []
    watchers: list = []
    drain_mode = threading.Event()

    def on_event(w: _WireWatcher, now: float) -> None:
        if not w.slow and w.resumed_from is None:
            recv_log.append((w.rvs[-1], now))
        if (
            w.slow
            and not drain_mode.is_set()
            and len(w.rvs) >= slow_read_events
            and w.reading
        ):
            # wedge: stop consuming entirely — the server's out-buffer
            # bound must eventually evict us
            w.reading = False
            sel.unregister(w.sock)

    def connect_watcher(
        idx: int, slow: bool, resume_rv=None
    ) -> _WireWatcher:
        s = None
        for attempt in range(20):
            s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            if slow:
                # tiny receive window: the kernel can't absorb the
                # backlog for us, so the server-side out-buffer fills
                # honestly
                s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
            try:
                s.connect((host, port))
                break
            except OSError:
                s.close()
                s = None
                time.sleep(0.05)  # accept backlog burst: retry
        if s is None:
            raise SystemExit(f"[wirefan] watcher {idx} could not connect")
        path = "/api/v1/pods?watch=true"
        if resume_rv is not None:
            path += f"&resource_version={resume_rv}"
        s.sendall(f"GET {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode())
        s.setblocking(False)
        w = _WireWatcher(s, idx, slow, resumed_from=resume_rv)
        sel.register(s, selectors.EVENT_READ, w)
        return w

    def client_loop() -> None:
        while not stop.is_set():
            for key, _mask in sel.select(0.2):
                w: _WireWatcher = key.data
                try:
                    data = w.sock.recv(262144)
                except (BlockingIOError, InterruptedError):
                    continue
                except OSError:
                    data = b""
                if not data:
                    w.eof = True
                    try:
                        sel.unregister(w.sock)
                    except (KeyError, ValueError):
                        pass
                    continue
                w.feed(data, time.monotonic(), on_event)

    reader = threading.Thread(target=client_loop, daemon=True)
    reader.start()
    t0 = time.monotonic()
    try:
        # -- establish the fleet -------------------------------------------
        for i in range(n_watchers):
            watchers.append(connect_watcher(i, slow=i < n_slow))
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if all(w.synced for w in watchers):
                break
            time.sleep(0.05)
        unsynced = sum(1 for w in watchers if not w.synced)
        if unsynced:
            raise SystemExit(
                f"[wirefan] {unsynced}/{n_watchers} streams never SYNCed"
            )
        setup_s = time.monotonic() - t0
        base_threads = threading.active_count()
        log(
            f"[wirefan] {n_watchers} live HTTP watch streams established "
            f"in {setup_s:.1f}s ({base_threads} process threads)"
        )

        # -- mutation window ------------------------------------------------
        pad = "w" * pad_bytes
        all_rvs: list = []
        enc0 = counters.get("watch.fanout.encoded")
        shr0 = counters.get("watch.fanout.shared")
        thread_peak = 0
        tick = 1.0 / rate
        t_window = time.monotonic()
        i = 0
        while time.monotonic() - t_window < window_s:
            p = make_pod(f"ev{i:06d}", labels={"pad": pad})
            # stamp BEFORE the commit: fanout runs inside store.create,
            # so a post-return stamp would measure from after the
            # earliest possible delivery and bias the headline low
            t0_ev = time.monotonic()
            created = store.create("Pod", p)
            rv = created.metadata.resource_version
            t_send[rv] = t0_ev
            all_rvs.append(rv)
            i += 1
            thread_peak = max(thread_peak, threading.active_count())
            time.sleep(tick)
        n_events = len(all_rvs)
        log(
            f"[wirefan] window closed: {n_events} mutations over "
            f"{window_s}s; thread peak {thread_peak}"
        )

        # -- thread-count gate ---------------------------------------------
        thread_gate = max(int(n_watchers * thread_frac), 8)
        if thread_peak > thread_gate:
            raise SystemExit(
                f"[wirefan] SERVER THREAD COUNT UNBOUNDED: {thread_peak} "
                f"threads at {n_watchers} watchers (gate {thread_gate} — "
                f"thread-per-watcher is back?)"
            )

        # -- drain: every live watcher must see every event ----------------
        drain_mode.set()
        deadline = time.monotonic() + drain_s
        pending = [w for w in watchers if not w.slow]
        while time.monotonic() < deadline:
            if all(len(w.rvs) >= n_events for w in pending):
                break
            if any(w.eof for w in pending):
                break
            time.sleep(0.1)
        incomplete = [
            w.idx for w in pending if len(w.rvs) != n_events or w.eof
        ]
        if incomplete:
            raise SystemExit(
                f"[wirefan] {len(incomplete)} live watchers missed events "
                f"(e.g. #{incomplete[:4]}: "
                f"{[len(watchers[j].rvs) for j in incomplete[:4]]}/"
                f"{n_events})"
            )
        # exactness (not just count): FIFO order, no gaps, no dups
        for w in pending[:: max(len(pending) // 50, 1)]:
            if w.rvs != all_rvs:
                raise SystemExit(
                    f"[wirefan] watcher {w.idx} event sequence DIVERGED"
                )

        # -- eviction + resume parity --------------------------------------
        # wedged watchers: wait for the server to evict them (socket
        # death), then resume each from its last parsed rv and require
        # exactly-once across the seam
        for w in watchers[:n_slow]:
            if not w.reading:
                sel.register(w.sock, selectors.EVENT_READ, w)
                w.reading = True
        deadline = time.monotonic() + drain_s
        while time.monotonic() < deadline:
            slows = watchers[:n_slow]
            if all(w.eof or len(w.rvs) >= n_events for w in slows):
                break
            time.sleep(0.1)
        evictions = counters.get("wire.evicted_outbuf") + counters.get(
            "watch.fanout.evicted_slow"
        )
        if evictions == 0:
            raise SystemExit(
                "[wirefan] NO EVICTION: the slow-watcher path was never "
                "exercised (grow BENCH_WIRE_PAD / shrink BENCH_WIRE_OUTBUF)"
            )
        resumed_ok = 0
        for w in watchers[:n_slow]:
            if not w.eof and len(w.rvs) >= n_events:
                if w.rvs != all_rvs:
                    raise SystemExit(
                        f"[wirefan] surviving slow watcher {w.idx} "
                        f"sequence diverged"
                    )
                continue  # laggard survived (buffers absorbed it)
            last = w.last_rv()
            prefix = [rv for rv in all_rvs if rv <= last]
            if w.rvs != prefix:
                raise SystemExit(
                    f"[wirefan] evicted watcher {w.idx} pre-eviction "
                    f"sequence not a clean prefix"
                )
            w2 = connect_watcher(10_000 + w.idx, slow=False, resume_rv=last)
            watchers.append(w2)  # cleanup in finally
            expect = [rv for rv in all_rvs if rv > last]
            deadline2 = time.monotonic() + drain_s
            while (
                len(w2.rvs) < len(expect)
                and not w2.eof
                and time.monotonic() < deadline2
            ):
                time.sleep(0.05)
            if w2.rvs != expect:
                raise SystemExit(
                    f"[wirefan] RESUME PARITY BROKEN for watcher {w.idx}: "
                    f"{len(w2.rvs)}/{len(expect)} after resume from "
                    f"rv {last} (missed or duplicated events)"
                )
            resumed_ok += 1

        # -- encode-once gate ----------------------------------------------
        encoded = counters.get("watch.fanout.encoded") - enc0
        shared = counters.get("watch.fanout.shared") - shr0
        if encoded * 10 > shared:
            raise SystemExit(
                f"[wirefan] ENCODE-ONCE REGRESSED: {encoded} encodes vs "
                f"{shared} shared reuses at {n_watchers} watchers"
            )

        # -- headline: p99 delivery latency --------------------------------
        samples = sorted(
            t_recv - t_send[rv]
            for rv, t_recv in recv_log
            if rv in t_send
        )
        if not samples:
            raise SystemExit("[wirefan] no delivery-latency samples")
        p50 = _pct(samples, 0.50, 4)
        p95 = _pct(samples, 0.95, 4)
        p99 = _pct(samples, 0.99, 4)
        if p99 > p99_gate_s:
            raise SystemExit(
                f"[wirefan] P99 DELIVERY LATENCY REGRESSED: {p99}s > "
                f"gate {p99_gate_s}s (p50 {p50}s, {len(samples)} samples)"
            )
        from minisched_tpu.observability import hist

        live_p99 = _crosscheck_live_p99(
            "watch.delivery_lag_s", p99, "wirefan"
        )
        csnap = counters.snapshot()
        log(
            f"[wirefan] p99 delivery {p99}s (p50 {p50}s, p95 {p95}s) over "
            f"{len(samples)} deliveries to {n_watchers} watchers; "
            f"threads peak {thread_peak} (gate {thread_gate}); "
            f"encoded {encoded} vs shared {shared}; evictions {evictions} "
            f"({resumed_ok} resumed exactly-once)"
        )
        return {
            "watchers": n_watchers,
            "slow_watchers": n_slow,
            "events": n_events,
            "window_s": window_s,
            "setup_s": round(setup_s, 1),
            "delivery_p50_s": p50,
            "delivery_p95_s": p95,
            "delivery_p99_s": p99,
            "delivery_p99_live_bucket_s": live_p99,
            "delivery_gate_s": p99_gate_s,
            "metrics_snapshot": hist.snapshot(),
            "delivery_samples": len(samples),
            "thread_peak": thread_peak,
            "thread_gate": thread_gate,
            "fanout_encoded": encoded,
            "fanout_shared": shared,
            "evictions": evictions,
            "resumed_exactly_once": resumed_ok,
            "total_s": round(time.monotonic() - t0, 1),
            "wire_counters": {
                k: v for k, v in csnap.items()
                if k.startswith("wire.") or k.startswith("watch.")
            },
        }
    finally:
        stop.set()
        reader.join(timeout=5.0)
        for w in watchers:
            try:
                w.sock.close()
            except OSError:
                pass
        try:
            sel.close()
        except Exception:
            pass
        shutdown()


def bench_wave_pipeline() -> dict:
    """``make bench-wave`` micro-role: two pipelined laps of the live
    full-roster wave engine on whatever device JAX gives (CPU in CI),
    gated on the pipeline actually OVERLAPPING: the loop thread's stall
    (time the device sat idle waiting for a build) must stay under the
    total build time — stall ≈ build is exactly what a regression to the
    serial loop looks like.  Ends with the exactly-once + capacity
    audits so 'faster' can never mean 'wrong'."""
    import threading
    from collections import defaultdict

    from minisched_tpu.api.objects import make_node, make_pod
    from minisched_tpu.controlplane.client import Client
    from minisched_tpu.observability import counters
    from minisched_tpu.observability.profiling import CycleMetrics
    from minisched_tpu.service.config import default_full_roster_config
    from minisched_tpu.service.service import SchedulerService

    if os.environ.get("MINISCHED_PIPELINE", "1") in ("", "0"):
        bench_skip("MINISCHED_PIPELINE=0: pipeline disabled by env")
    n_nodes = int(os.environ.get("BENCH_WAVEROLE_NODES", "512"))
    n_pods = int(os.environ.get("BENCH_WAVEROLE_PODS", "6144"))
    max_wave = int(os.environ.get("BENCH_WAVEROLE_WAVE", "1024"))
    laps = max(1, int(os.environ.get("BENCH_WAVEROLE_LAPS", "2")))

    client = Client()
    client.nodes().create_many(
        [
            make_node(
                f"node{i:04d}",
                capacity={"cpu": "64", "memory": "128Gi", "pods": 256},
            )
            for i in range(n_nodes)
        ],
        return_objects=False,
    )
    bound_n = 0
    mu = threading.Lock()

    def counting(pod, node_name, status):
        nonlocal bound_n
        if node_name:
            with mu:
                bound_n += 1

    counters.reset()
    metrics = CycleMetrics()
    svc = SchedulerService(client)
    svc.start_scheduler(
        default_full_roster_config(), device_mode=True, max_wave=max_wave,
        on_decision=counting, metrics=metrics,
    )
    t0 = time.monotonic()
    try:
        target = 0
        for lap in range(laps):
            client.pods().create_many(
                [
                    make_pod(
                        f"wp{lap}-{i:05d}",
                        requests={"cpu": "100m", "memory": "64Mi"},
                    )
                    for i in range(n_pods)
                ],
                return_objects=False,
            )
            target += n_pods
            deadline = time.monotonic() + 600
            while time.monotonic() < deadline:
                with mu:
                    if bound_n >= target:
                        break
                time.sleep(0.05)
            with mu:
                if bound_n < target:
                    raise SystemExit(
                        f"[wave] lap {lap + 1}: only {bound_n}/{target} bound"
                    )
            log(
                f"[wave] lap {lap + 1}/{laps}: {target} pods bound at "
                f"{time.monotonic() - t0:.1f}s"
            )
        elapsed = time.monotonic() - t0
        snap = metrics.snapshot()
    finally:
        svc.shutdown_scheduler()

    # ---- audits: exactly-once + no node over allocatable ----------------
    cpu = defaultdict(int)
    cnt = defaultdict(int)
    for p in client.pods().list():
        if not p.spec.node_name:
            raise SystemExit(f"[wave] pod {p.metadata.name} left unbound")
        cpu[p.spec.node_name] += p.resource_requests().milli_cpu
        cnt[p.spec.node_name] += 1
    for node in client.nodes().list():
        name = node.metadata.name
        alloc = node.status.allocatable
        if cpu[name] > alloc.milli_cpu or cnt[name] > alloc.pods:
            raise SystemExit(f"[wave] NODE OVER ALLOCATABLE: {name}")

    def phase(name, field):
        return round(snap.get(name, {}).get(field, 0.0), 3)

    stall_s = phase("wave_pipeline_stall", "total_s")
    build_s = phase("wave_pipeline_build", "total_s")
    waves = counters.get("wave_pipeline.waves")
    if waves == 0:
        raise SystemExit("[wave] PIPELINE NEVER ENGAGED (0 pipelined waves)")
    if build_s > 0 and stall_s >= build_s:
        raise SystemExit(
            f"[wave] PIPELINE REGRESSED TO SERIAL: stall {stall_s}s >= "
            f"build {build_s}s over {waves} waves"
        )
    overlap = round(1.0 - stall_s / build_s, 3) if build_s > 0 else 0.0
    log(
        f"[wave] {laps * n_pods} pods in {elapsed:.1f}s, {waves} pipelined "
        f"waves: build {build_s}s, stall {stall_s}s (overlap {overlap:.0%}), "
        f"rearb_requeued={counters.get('wave_pipeline.rearb_requeued')}"
    )
    return {
        "pods": laps * n_pods,
        "nodes": n_nodes,
        "laps": laps,
        "total_s": round(elapsed, 1),
        "pods_per_sec_e2e": round(laps * n_pods / elapsed, 1),
        "pipelined_waves": waves,
        "build_total_s": build_s,
        "stall_total_s": stall_s,
        "overlap_ratio": overlap,
        "rearb_requeued": counters.get("wave_pipeline.rearb_requeued"),
        "build_fallbacks": counters.get("wave_pipeline.build_fallback"),
        "dirty_rows": counters.get("wave_pipeline.dirty_rows"),
    }


class _Fd2Tap:
    """Capture everything written to fd 2 while active — including XLA's
    C++ log lines (the >2s slow-constant-folding alarm the mesh child
    gates on), which no Python-level redirect can see.  Lines still
    stream through to the real stderr, so the logs stay watchable."""

    def __enter__(self):
        import threading

        self._saved = os.dup(2)
        r, w = os.pipe()
        os.dup2(w, 2)
        os.close(w)
        self._r = r
        self._chunks = []

        def drain() -> None:
            while True:
                b = os.read(r, 65536)
                if not b:
                    return
                self._chunks.append(b)
                os.write(self._saved, b)

        self._thread = threading.Thread(target=drain, daemon=True)
        self._thread.start()
        return self

    def __exit__(self, *exc):
        sys.stderr.flush()
        os.dup2(self._saved, 2)  # closes the pipe's last write end
        self._thread.join(timeout=5.0)
        os.close(self._r)
        os.close(self._saved)
        return False

    def text(self) -> str:
        return b"".join(self._chunks).decode(errors="replace")


def bench_mesh() -> dict:
    """``make bench-mesh``: the multi-chip LIVE wave engine (ISSUE 7) vs
    the single-device engine on the SAME uid-pinned workload, on an
    8-device host-platform mesh (CPU CI) or real chips.  FAILS when:

    * placements differ (the parity-pinned acceptance criterion);
    * the sharded run's ``device_total_s`` is not strictly below the
      single-device run's (the mesh didn't pay for itself);
    * the pipeline regressed to serial under the mesh (stall >= build);
    * any wave fell back to the single-device evaluator, or none ran
      sharded at all;
    * the exactly-once / capacity audits trip on either run;
    * XLA's >2s slow-constant-folding alarm fires anywhere in the run
      (the BENCH_r06-tail regression the plugin rewrites close), or the
      evaluator warm exceeds BENCH_MESH_COMPILE_BUDGET_S.
    """
    import threading
    from collections import defaultdict

    from minisched_tpu.api.objects import make_node, make_pod
    from minisched_tpu.controlplane.client import Client
    from minisched_tpu.observability import counters
    from minisched_tpu.observability.profiling import CycleMetrics
    from minisched_tpu.parallel.sharding import make_mesh, mesh_shape_key
    from minisched_tpu.service.config import default_full_roster_config
    from minisched_tpu.service.service import SchedulerService

    import jax

    if jax.device_count() < 2:
        bench_skip(
            "mesh role needs >1 device (set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 on CPU)"
        )
    n_nodes = int(os.environ.get("BENCH_MESH_NODES", "512"))
    n_pods = int(os.environ.get("BENCH_MESH_PODS", "6144"))
    max_wave = int(os.environ.get("BENCH_MESH_WAVE", "1024"))
    compile_budget = float(
        os.environ.get("BENCH_MESH_COMPILE_BUDGET_S", "300")
    )

    nodes = [
        make_node(
            f"node{i:04d}",
            capacity={"cpu": "64", "memory": "128Gi", "pods": 256},
        )
        for i in range(n_nodes)
    ]

    def lap(device_mesh, tag: str) -> dict:
        client = Client()
        client.nodes().create_many(
            [n.clone() for n in nodes], return_objects=False
        )
        pods = []
        for i in range(n_pods):
            p = make_pod(
                f"mp{i:05d}", requests={"cpu": "100m", "memory": "64Mi"}
            )
            # uid pinned = tie-break seed pinned: the two laps must be
            # comparable bit-for-bit (the process-global uid counter
            # would otherwise reseed the second lap)
            p.metadata.uid = f"mesh-uid-{i:05d}"
            pods.append(p)
        client.pods().create_many(pods, return_objects=False)
        bound_n = 0
        mu = threading.Lock()

        def counting(pod, node_name, status):
            nonlocal bound_n
            if node_name:
                with mu:
                    bound_n += 1

        counters.reset()
        metrics = CycleMetrics()
        svc = SchedulerService(client)
        t_warm = time.monotonic()
        svc.start_scheduler(
            default_full_roster_config(), device_mode=True,
            max_wave=max_wave, device_mesh=device_mesh,
            on_decision=counting, metrics=metrics, prewarm=True,
            prewarm_scan=False,
        )
        warm_s = time.monotonic() - t_warm
        t0 = time.monotonic()
        try:
            deadline = time.monotonic() + 900
            while time.monotonic() < deadline:
                with mu:
                    if bound_n >= n_pods:
                        break
                time.sleep(0.05)
            with mu:
                if bound_n < n_pods:
                    raise SystemExit(
                        f"[mesh] {tag}: only {bound_n}/{n_pods} bound"
                    )
            elapsed = time.monotonic() - t0
            snap = metrics.snapshot()
        finally:
            svc.shutdown_scheduler()

        # exactly-once + capacity audits — 'faster' may never mean 'wrong'
        placements = {}
        cpu = defaultdict(int)
        cnt = defaultdict(int)
        for p in client.pods().list():
            if not p.spec.node_name:
                raise SystemExit(
                    f"[mesh] {tag}: pod {p.metadata.name} left unbound"
                )
            placements[p.metadata.name] = p.spec.node_name
            cpu[p.spec.node_name] += p.resource_requests().milli_cpu
            cnt[p.spec.node_name] += 1
        for node in client.nodes().list():
            alloc = node.status.allocatable
            name = node.metadata.name
            if cpu[name] > alloc.milli_cpu or cnt[name] > alloc.pods:
                raise SystemExit(f"[mesh] {tag}: NODE OVER ALLOCATABLE {name}")

        def phase(name, field):
            return round(snap.get(name, {}).get(field, 0.0), 3)

        out = {
            "total_s": round(elapsed, 2),
            "warm_s": round(warm_s, 2),
            "pods_per_sec_e2e": round(n_pods / elapsed, 1),
            "device_total_s": phase("wave_device", "total_s"),
            "build_total_s": phase("wave_pipeline_build", "total_s"),
            "stall_total_s": phase("wave_pipeline_stall", "total_s"),
            "pipelined_waves": counters.get("wave_pipeline.waves"),
            "wave_mesh": {
                "pod_shards": counters.get("wave_mesh.pod_shards"),
                "node_shards": counters.get("wave_mesh.node_shards"),
                "waves": counters.get("wave_mesh.waves"),
                "fallbacks": counters.get("wave_mesh.fallbacks"),
                "pad_pod_rows": counters.get("wave_mesh.pad_pod_rows"),
                "pad_node_rows": counters.get("wave_mesh.pad_node_rows"),
            },
        }
        log(
            f"[mesh] {tag}: {n_pods} pods in {elapsed:.1f}s "
            f"(device {out['device_total_s']}s, warm {warm_s:.1f}s, "
            f"mesh waves {out['wave_mesh']['waves']}, "
            f"fallbacks {out['wave_mesh']['fallbacks']})"
        )
        return out, placements

    mesh = make_mesh()
    with _Fd2Tap() as tap:
        # mesh=False pins the baseline single-device EXPLICITLY — with
        # >1 device visible, None would auto-shard and compare the mesh
        # against itself
        single, base_placements = lap(False, "single-device")
        sharded, mesh_placements = lap(mesh, f"mesh {mesh_shape_key(mesh)}")
    alarm = "Constant folding an instruction is taking" in tap.text()

    # ---- gates ----------------------------------------------------------
    if mesh_placements != base_placements:
        diff = sum(
            1
            for k in base_placements
            if mesh_placements.get(k) != base_placements[k]
        )
        raise SystemExit(f"[mesh] PARITY BROKEN: {diff} placements differ")
    if single["wave_mesh"]["waves"]:
        raise SystemExit(
            "[mesh] BASELINE RAN SHARDED — the comparison is meaningless"
        )
    if sharded["wave_mesh"]["waves"] == 0:
        raise SystemExit("[mesh] NO WAVE RAN SHARDED (mesh engine degraded)")
    if sharded["wave_mesh"]["fallbacks"]:
        raise SystemExit(
            f"[mesh] {sharded['wave_mesh']['fallbacks']} waves fell back "
            "to the single-device evaluator"
        )
    if (
        sharded["build_total_s"] > 0
        and sharded["stall_total_s"] >= sharded["build_total_s"]
    ):
        raise SystemExit(
            f"[mesh] PIPELINE REGRESSED TO SERIAL under the mesh: stall "
            f"{sharded['stall_total_s']}s >= build {sharded['build_total_s']}s"
        )
    # the device-time gate is a PERF claim — meaningful only where the
    # mesh's devices are real parallel hardware.  On a host-platform CPU
    # mesh with fewer physical cores than virtual devices (this repo's
    # 1-core re-earn box), sharding adds partition overhead over zero
    # real parallelism and the gate is physically unreachable — a
    # capability gap, not a regression (the BENCH_r06 precedent).  Every
    # CORRECTNESS gate above stays hard everywhere.
    cores = os.cpu_count() or 1
    perf_meaningful = (
        jax.default_backend() != "cpu" or cores >= jax.device_count()
    )
    if sharded["device_total_s"] >= single["device_total_s"]:
        if perf_meaningful:
            raise SystemExit(
                f"[mesh] SHARDED DEVICE TIME NOT BELOW SINGLE-DEVICE: "
                f"{sharded['device_total_s']}s >= {single['device_total_s']}s"
            )
        device_gate = (
            f"skipped: {cores} physical cores for {jax.device_count()} "
            "virtual devices — needs a multi-core or TPU box"
        )
        log(f"[mesh] device-time gate {device_gate}")
    else:
        device_gate = "passed"
    if alarm:
        raise SystemExit(
            "[mesh] XLA slow-constant-folding alarm fired (>2s constant "
            "fold) — the packed-axis plugin rewrites regressed"
        )
    for tag, rec in (("single", single), ("mesh", sharded)):
        if rec["warm_s"] > compile_budget:
            raise SystemExit(
                f"[mesh] {tag} warm {rec['warm_s']}s exceeds compile "
                f"budget {compile_budget}s"
            )
    return {
        "nodes": n_nodes,
        "pods": n_pods,
        "mesh_shape": [list(kv) for kv in mesh_shape_key(mesh)],
        "single_device": single,
        "sharded": sharded,
        "device_speedup": round(
            single["device_total_s"] / max(sharded["device_total_s"], 1e-9), 3
        ),
        "device_gate": device_gate,
        "parity_ok": True,
        "constant_folding_alarm": alarm,
    }


def bench_chaos() -> dict:
    """Chaos soak at bench scale: the device wave engine over a WAL store
    while the fault fabric injects store/bind/watch/WAL failures on a
    seeded schedule (BENCH_CHAOS_SEED reproduces the exact injections).
    Reports convergence + the injected/recovered counts — the product
    claim is 'survives a lossy control plane without leaking capacity',
    so the record carries the leak/double-bind audit results, not just a
    throughput number."""
    import tempfile

    from minisched_tpu.api.objects import make_node, make_pod
    from minisched_tpu.controlplane.client import Client
    from minisched_tpu.controlplane.durable import DurableObjectStore
    from minisched_tpu.faults import FaultFabric
    from minisched_tpu.observability import counters
    from minisched_tpu.service.config import default_full_roster_config
    from minisched_tpu.service.service import SchedulerService

    seed = int(os.environ.get("BENCH_CHAOS_SEED", "1234"))
    n_nodes = int(os.environ.get("BENCH_CHAOS_NODES", "128"))
    n_pods = int(os.environ.get("BENCH_CHAOS_PODS", "2000"))
    wal = os.path.join(tempfile.mkdtemp(prefix="minisched-chaos-"), "c.wal")
    store = DurableObjectStore(wal)
    client = Client(store=store)
    for i in range(n_nodes):
        client.nodes().create(
            make_node(
                f"node{i:04d}",
                unschedulable=i % 16 == 0,
                capacity={"cpu": "64", "memory": "128Gi", "pods": 256},
            )
        )
    client.pods().create_many(
        [
            make_pod(f"cp{i:05d}", requests={"cpu": "500m", "memory": "64Mi"})
            for i in range(n_pods)
        ]
    )
    fabric = (
        FaultFabric(seed)
        .on("store.update", rate=0.10)
        .on("store.get", rate=0.05)
        .on("watch.drop", rate=0.02, max_fires=16, keys={"Pod", "Node"})
        .on("wal.append", rate=0.03, max_fires=16)
        .on("engine.bind", rate=0.05, max_fires=16)
    )
    counters.reset()
    svc = SchedulerService(client)
    sched = svc.start_scheduler(
        default_full_roster_config(), device_mode=True,
        max_wave=int(os.environ.get("BENCH_CHAOS_WAVE", "512")),
    )
    sched.faults = fabric
    sched.assume_ttl_s = 3.0
    store.fault_injector = fabric.as_store_injector()
    store.faults = fabric
    t0 = time.monotonic()
    deadline = t0 + float(os.environ.get("BENCH_CHAOS_DEADLINE_S", "300"))
    bound = 0
    try:
        while time.monotonic() < deadline:
            try:
                bound = sum(
                    1 for p in client.pods().list() if p.spec.node_name
                )
            except Exception:
                continue  # injected list fault on our own poll
            if bound >= n_pods:
                break
            if sched.queue.stats()["unschedulable"]:
                sched.queue.flush_unschedulable_leftover()
                sched.queue.flush_backoff_completed()
            time.sleep(0.25)
        elapsed = time.monotonic() - t0
        # quiesce: the assume ledger must drain (lease confirm path)
        drain_deadline = time.monotonic() + 10 * sched.assume_ttl_s
        leaked = True
        while time.monotonic() < drain_deadline:
            with sched._assumed_lock:
                leaked = bool(sched._assumed)
            if not leaked:
                break
            time.sleep(0.25)
        store.fault_injector = None
        store.faults = None
        # the degraded-mode dashboard line: per-kind cache staleness +
        # reconnect/resume counts AT QUIESCE.  A cache still stale past
        # the threshold means an informer never re-verified itself after
        # the injected outages — fail the run, don't just log it.
        staleness = svc.informer_factory.staleness()
        max_staleness = float(
            os.environ.get("BENCH_CHAOS_MAX_STALENESS_S", "30")
        )
        if bound < n_pods:
            raise SystemExit(
                f"[chaos] DID NOT CONVERGE: {bound}/{n_pods} bound; "
                f"faults={fabric.stats()} counters={counters.snapshot()}"
            )
        if leaked:
            raise SystemExit("[chaos] ASSUMED-CAPACITY LEAK at quiesce")
        for kind, rec in staleness.items():
            if rec["staleness_s"] > max_staleness:
                raise SystemExit(
                    f"[chaos] STALE INFORMER at quiesce: {kind} unverified "
                    f"for {rec['staleness_s']}s (> {max_staleness}s); "
                    f"staleness={staleness}"
                )
    finally:
        svc.shutdown_scheduler()
        store.close()
    # WAL history audit: no pod ever bound to two different nodes
    from minisched_tpu.faults import wal_double_binds

    violations = wal_double_binds(wal)
    if violations:
        raise SystemExit(f"[chaos] DOUBLE BIND: {violations[:5]}")
    stats = fabric.stats()
    log(
        f"[chaos] {n_pods} pods converged under "
        f"{sum(stats['fires'].values())} injected faults in {elapsed:.1f}s "
        f"(seed={seed}; no leak, no double-bind)"
    )
    return {
        "pods": n_pods,
        "nodes": n_nodes,
        "total_s": round(elapsed, 1),
        "seed": seed,
        "injected": stats["fires"],
        "recovered": {
            k: v
            for k, v in counters.snapshot().items()
            if v and not k.startswith("assume.lease_renewed")
        },
        # per-kind staleness gauge + reconnect/resume counts at quiesce
        # (ROADMAP open item: surface SharedInformerFactory.staleness()
        # in the bench records and alert past a threshold)
        "staleness": staleness,
        "leak": False,
        "double_bind": False,
    }


def bench_disk() -> dict:
    """Storage-integrity soak at bench scale: the device wave engine over
    an ARCHIVED WAL store with periodic compaction while the disk fabric
    injects append refusals, a sustained ENOSPC episode, one bit-flip,
    and one checkpoint-rot — the product claim is 'survives a lying
    disk', so the record carries degraded-mode dwell time, the scrub/
    fsck findings (the injected corruption MUST be detected, never
    silently applied), and the exactly-once audit, not just throughput."""
    import tempfile
    import threading

    from minisched_tpu.api.objects import make_node, make_pod
    from minisched_tpu.controlplane.client import Client
    from minisched_tpu.controlplane.durable import DurableObjectStore
    from minisched_tpu.controlplane.fsck import fsck
    from minisched_tpu.faults import FaultFabric
    from minisched_tpu.observability import counters
    from minisched_tpu.service.config import default_full_roster_config
    from minisched_tpu.service.service import SchedulerService

    seed = int(os.environ.get("BENCH_CHAOS_SEED", "1234"))
    n_nodes = int(os.environ.get("BENCH_DISK_NODES", "64"))
    n_pods = int(os.environ.get("BENCH_DISK_PODS", "1500"))
    wal = os.path.join(tempfile.mkdtemp(prefix="minisched-disk-"), "d.wal")
    store = DurableObjectStore(
        wal, archive_compacted=True, probe_interval_s=0.05
    )
    store.start_scrub(interval_s=0.5)
    client = Client(store=store)
    client.nodes().create_many(
        [
            make_node(
                f"node{i:04d}",
                capacity={"cpu": "64", "memory": "128Gi", "pods": 256},
            )
            for i in range(n_nodes)
        ]
    )
    client.pods().create_many(
        [
            make_pod(f"dk{i:05d}", requests={"cpu": "500m", "memory": "64Mi"})
            for i in range(n_pods)
        ]
    )
    # armed AFTER the seed: the workload, not the setup, takes the weather
    fabric = (
        FaultFabric(seed)
        .on("wal.append", rate=0.05)
        .on("disk.enospc", rate=1.0, after=100, max_fires=8)
        .on("wal.bitflip", rate=1.0, after=250, max_fires=1)
        .on("ckpt.corrupt", rate=1.0, after=1, max_fires=1)
    )
    store.faults = fabric
    counters.reset()
    compact_stop = threading.Event()

    def compactor() -> None:
        while not compact_stop.wait(0.5):
            try:
                store.compact()
            except Exception:
                pass  # ENOSPC mid-compaction is exactly this role's weather

    threading.Thread(target=compactor, daemon=True).start()
    svc = SchedulerService(client)
    sched = svc.start_scheduler(
        default_full_roster_config(), device_mode=True,
        max_wave=int(os.environ.get("BENCH_DISK_WAVE", "256")),
    )
    sched.assume_ttl_s = 3.0
    t0 = time.monotonic()
    deadline = t0 + float(os.environ.get("BENCH_DISK_DEADLINE_S", "300"))
    bound = 0
    try:
        while time.monotonic() < deadline:
            try:
                bound = sum(
                    1 for p in client.pods().list() if p.spec.node_name
                )
            except Exception:
                continue
            if bound >= n_pods:
                break
            if sched.queue.stats()["unschedulable"]:
                sched.queue.flush_unschedulable_leftover()
                sched.queue.flush_backoff_completed()
            time.sleep(0.25)
        elapsed = time.monotonic() - t0
        drain_deadline = time.monotonic() + 10 * sched.assume_ttl_s
        leaked = True
        while time.monotonic() < drain_deadline:
            with sched._assumed_lock:
                leaked = bool(sched._assumed)
            if not leaked:
                break
            time.sleep(0.25)
        if bound < n_pods:
            raise SystemExit(
                f"[disk] DID NOT CONVERGE: {bound}/{n_pods} bound; "
                f"faults={fabric.stats()} counters={counters.snapshot()}"
            )
        if leaked:
            raise SystemExit("[disk] ASSUMED-CAPACITY LEAK at quiesce")
    finally:
        compact_stop.set()
        svc.shutdown_scheduler()
        scrub = store.scrub()
        stats = store.storage_stats()
        store.faults = None
        store.close()
    from minisched_tpu.faults import wal_double_binds

    violations = wal_double_binds(wal)
    if violations:
        raise SystemExit(f"[disk] DOUBLE BIND: {violations[:5]}")
    fire_stats = fabric.stats()
    if fire_stats["fires"].get("disk.enospc", 0) < 1:
        raise SystemExit("[disk] ENOSPC episode never fired")
    report = fsck(wal)
    flipped = fire_stats["fires"].get("wal.bitflip", 0)
    crc_findings = sum("crc mismatch" in e for e in report["errors"])
    if flipped and not crc_findings:
        raise SystemExit(
            f"[disk] UNDETECTED BIT-FLIP: {flipped} injected, fsck found "
            f"none — a lying disk went unnoticed; report={report['errors']}"
        )
    log(
        f"[disk] {n_pods} pods converged in {elapsed:.1f}s under "
        f"{sum(fire_stats['fires'].values())} disk faults "
        f"(degraded {stats['degraded_episodes']}x / "
        f"{stats['degraded_dwell_s']}s dwell; {flipped} bit-flip(s) "
        f"detected by fsck; no leak, no double-bind)"
    )
    return {
        "pods": n_pods,
        "nodes": n_nodes,
        "total_s": round(elapsed, 1),
        "seed": seed,
        "injected": fire_stats["fires"],
        "degraded_episodes": stats["degraded_episodes"],
        "degraded_dwell_s": stats["degraded_dwell_s"],
        "scrub_findings": scrub["findings"],
        "fsck_errors": report["errors"],
        "bitflips_detected": crc_findings,
        "group_commit": {
            "groups": counters.get("storage.group_commit.groups"),
            "records": counters.get("storage.group_commit.records"),
            "fsyncs_saved": counters.get("storage.group_commit.fsyncs_saved"),
        },
        "recovered": {
            k: v
            for k, v in counters.snapshot().items()
            if v and (k.startswith("storage.") or k.startswith("remote."))
        },
        "leak": False,
        "double_bind": False,
    }


def bench_wal() -> dict:
    """Group-commit WAL (ISSUE 13): N concurrent HTTP writers over a
    ``file://`` WAL with fsync=True, run twice on the same box — once
    with the MINISCHED_GROUP_COMMIT=0 kill-switch (today's per-mutation
    fsync) and once with the pipeline — gating (a) fsyncs ≪ mutations
    (coalescing ratio recorded), (b) throughput ≥3× the kill-switch
    baseline, (c) post-run fsck clean (which includes rv monotonicity)
    and full replay.  Both phases arm the same MINISCHED_FSYNC_FLOOR_US
    durability-barrier floor (default 50ms, a rotational/cloud disk's
    flush): tmpfs/virtio fsyncs are near-free, which would hide the
    coalescing win this role exists to measure — the floor is recorded
    in the result, and BENCH_WAL_FSYNC_FLOOR_US=0 measures the raw
    device instead."""
    import tempfile
    import threading

    from minisched_tpu.api.objects import make_pod
    from minisched_tpu.controlplane.durable import DurableObjectStore
    from minisched_tpu.controlplane.fsck import fsck
    from minisched_tpu.controlplane.httpserver import start_api_server
    from minisched_tpu.controlplane.remote import RemoteClient
    from minisched_tpu.observability import counters, hist

    n_writers = int(os.environ.get("BENCH_WAL_WRITERS", "12"))
    per_writer = int(os.environ.get("BENCH_WAL_PODS_PER_WRITER", "15"))
    floor_us = int(os.environ.get("BENCH_WAL_FSYNC_FLOOR_US", "50000"))
    n_muts = n_writers * per_writer

    def phase(group_on: bool) -> dict:
        wal = os.path.join(tempfile.mkdtemp(prefix="minisched-wal-"), "w.wal")
        saved = {
            k: os.environ.get(k)
            for k in ("MINISCHED_GROUP_COMMIT", "MINISCHED_FSYNC_FLOOR_US")
        }
        os.environ["MINISCHED_GROUP_COMMIT"] = "1" if group_on else "0"
        os.environ["MINISCHED_FSYNC_FLOOR_US"] = str(floor_us)
        try:  # both knobs are read once, at store construction
            store = DurableObjectStore(wal, fsync=True)
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        server, base, shutdown = start_api_server(store, port=0)
        counters.reset()
        errs: list = []

        def writer(w: int) -> None:
            client = RemoteClient(base)
            try:
                for i in range(per_writer):
                    client.pods().create(
                        make_pod(
                            f"wp{w:02d}-{i:04d}",
                            requests={"cpu": "100m", "memory": "64Mi"},
                        )
                    )
            except Exception as e:
                errs.append(f"writer {w}: {e!r}")

        threads = [
            threading.Thread(target=writer, args=(w,), name=f"wal-writer-{w}")
            for w in range(n_writers)
        ]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.monotonic() - t0
        shutdown()
        store.close()
        if errs:
            raise SystemExit(f"[wal] WRITER FAILED (group={group_on}): {errs[:3]}")
        records = counters.get("storage.group_commit.records")
        saved_fsyncs = counters.get("storage.group_commit.fsyncs_saved")
        groups = counters.get("storage.group_commit.groups")
        # fsync=True: the kill-switch path fsyncs once per append, the
        # pipeline once per fsync-armed group == records - fsyncs_saved
        fsyncs = (records - saved_fsyncs) if group_on else n_muts
        re = DurableObjectStore(wal)
        replayed = sum(1 for _ in re.list("Pod"))
        max_rv = re.resource_version
        re.close()
        report = fsck(wal)
        if report["errors"]:
            raise SystemExit(
                f"[wal] FSCK DIRTY (group={group_on}): {report['errors'][:5]}"
            )
        if replayed != n_muts or max_rv != n_muts:
            raise SystemExit(
                f"[wal] REPLAY LOST ACKED MUTATIONS (group={group_on}): "
                f"{replayed}/{n_muts} pods, max rv {max_rv}"
            )
        return {
            "throughput_per_s": round(n_muts / elapsed, 1),
            "total_s": round(elapsed, 2),
            "fsyncs": fsyncs,
            "groups": groups,
            "records": records,
            "group_wait_p99_s": (
                hist.quantile_bounds("storage.group_wait_s", 0.99) or
                (None, None)
            )[1],
        }

    baseline = phase(False)
    grouped = phase(True)
    ratio = grouped["throughput_per_s"] / max(
        baseline["throughput_per_s"], 1e-9
    )
    coalesce = grouped["records"] / max(grouped["fsyncs"], 1)
    if grouped["fsyncs"] * 2 > n_muts:
        raise SystemExit(
            f"[wal] NO COALESCING: {grouped['fsyncs']} fsyncs for "
            f"{n_muts} mutations under {n_writers} writers"
        )
    if ratio < 3.0:
        raise SystemExit(
            f"[wal] GROUP COMMIT NOT ≥3× KILL-SWITCH: "
            f"{grouped['throughput_per_s']}/s vs "
            f"{baseline['throughput_per_s']}/s ({ratio:.2f}x) at "
            f"fsync floor {floor_us}µs"
        )
    log(
        f"[wal] {n_writers} writers × {per_writer} pods, fsync floor "
        f"{floor_us}µs: {grouped['throughput_per_s']}/s grouped vs "
        f"{baseline['throughput_per_s']}/s kill-switch ({ratio:.1f}x); "
        f"{grouped['fsyncs']} fsyncs for {n_muts} mutations "
        f"({coalesce:.1f} records/fsync); fsck clean, rv dense both ways"
    )
    return {
        "writers": n_writers,
        "mutations": n_muts,
        "fsync_floor_us": floor_us,
        "baseline": baseline,
        "group_commit": grouped,
        "speedup": round(ratio, 2),
        "coalescing_records_per_fsync": round(coalesce, 2),
        "fsck_clean": True,
    }


def bench_repl() -> dict:
    """Replicated control plane (ISSUE 15, DESIGN.md §27): one leader
    plus two followers tailing the WAL stream over real HTTP, quorum
    (1 follower ack) armed at the group-commit barrier, versus the same
    writer load with ``MINISCHED_REPL=0`` semantics (no hub — today's
    single-store plane).  The record carries the replication tax (mutate
    p50/p99 + ``storage.quorum_wait_s``) and the correctness evidence:
    every acked mutation on BOTH followers and follower WALs
    byte-identical to the leader's (``fsck.wal_compare``).  Phase 3
    (ISSUE 16, DESIGN.md §28) is bootstrap-under-load: writers hammer a
    leader whose background compaction ships checkpoint generations; a
    FRESH follower attaches mid-load and must catch up to the leader's
    rv within ``BENCH_REPL_BOOTSTRAP_S`` by seeding from the shipped
    checkpoint — zero offset-0 re-tails — while the leader's WAL stays
    bounded by the compaction interval, not by history.  Opt-in via
    ``BENCH_REPL=1`` — the role boots four HTTP servers and three
    fsync-armed stores, which is chaos-tier cost, not headline-tier."""
    import tempfile
    import threading

    from minisched_tpu.api.objects import make_pod
    from minisched_tpu.controlplane.durable import DurableObjectStore
    from minisched_tpu.controlplane.fsck import wal_compare
    from minisched_tpu.controlplane.httpserver import start_api_server
    from minisched_tpu.controlplane.remote import RemoteClient
    from minisched_tpu.controlplane.repl import ReplRuntime, WalFollower
    from minisched_tpu.observability import counters, hist

    if os.environ.get("BENCH_REPL", "0") == "0":
        bench_skip("BENCH_REPL unset: replicated-plane role is opt-in")

    n_writers = int(os.environ.get("BENCH_REPL_WRITERS", "8"))
    per_writer = int(os.environ.get("BENCH_REPL_PODS_PER_WRITER", "25"))
    n_muts = n_writers * per_writer

    def run_writers(base: str) -> list:
        lat: list = []
        errs: list = []
        mu = threading.Lock()

        def writer(w: int) -> None:
            client = RemoteClient(base)
            mine = []
            try:
                for i in range(per_writer):
                    t0 = time.monotonic()
                    client.pods().create(
                        make_pod(
                            f"rp{w:02d}-{i:04d}",
                            requests={"cpu": "100m", "memory": "64Mi"},
                        )
                    )
                    mine.append(time.monotonic() - t0)
            except Exception as e:
                errs.append(f"writer {w}: {e!r}")
            with mu:
                lat.extend(mine)

        threads = [
            threading.Thread(target=writer, args=(w,), name=f"repl-w{w}")
            for w in range(n_writers)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errs:
            raise SystemExit(f"[repl] WRITER FAILED: {errs[:3]}")
        return sorted(lat)

    # -- phase 1: kill-switch baseline (no hub, single store) ---------------
    base_dir = tempfile.mkdtemp(prefix="minisched-repl-")
    base_wal = os.path.join(base_dir, "baseline.wal")
    store_b = DurableObjectStore(base_wal, fsync=True)
    server_b, url_b, shutdown_b = start_api_server(store_b, port=0)
    t0 = time.monotonic()
    lat_b = run_writers(url_b)
    elapsed_b = time.monotonic() - t0
    shutdown_b()
    store_b.close()

    # -- phase 2: 3-replica plane, quorum armed -----------------------------
    counters.reset()
    leader_wal = os.path.join(base_dir, "leader.wal")
    leader = DurableObjectStore(leader_wal, fsync=True)
    runtime = ReplRuntime(
        leader, "r0", peers=[], cluster_size=3, ack_timeout_s=15.0
    )
    runtime.promote()
    server_l, url_l, shutdown_l = start_api_server(
        leader, port=0, repl=runtime
    )
    followers = []
    for fid in ("r1", "r2"):
        fstore = DurableObjectStore(
            os.path.join(base_dir, f"{fid}.wal"), fsync=True
        )
        fstore.fence("r0")
        tail = WalFollower(fstore, url_l, fid)
        tail.start()
        followers.append((fid, fstore, tail))
    t0 = time.monotonic()
    lat_r = run_writers(url_l)
    elapsed_r = time.monotonic() - t0
    # quorum means ONE follower proved durability per group; wait for
    # both to finish catching up before auditing the full copies
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline and any(
        f[1].resource_version < leader.resource_version for f in followers
    ):
        time.sleep(0.05)
    qp = hist.quantile_bounds("storage.quorum_wait_s", 0.99) or (None, None)
    shutdown_l()
    for _fid, fstore, tail in followers:
        tail.stop()
        fstore.close()
    leader.close()
    runtime.close()

    # -- audits -------------------------------------------------------------
    lost = []
    for fid, fstore, _tail in followers:
        replayed = DurableObjectStore(fstore._path)
        n = sum(1 for _ in replayed.list("Pod"))
        replayed.close()
        if n != n_muts:
            lost.append(f"{fid}: {n}/{n_muts} pods")
        cmp = wal_compare(leader_wal, fstore._path)
        if not (cmp.get("identical") or cmp.get("prefix")):
            lost.append(f"{fid}: WAL diverged {cmp.get('diverged')}")
    if lost:
        raise SystemExit(f"[repl] ACKED WRITES MISSING ON FOLLOWERS: {lost}")
    if counters.get("storage.repl.quorum_timeouts"):
        raise SystemExit("[repl] QUORUM TIMEOUTS on a healthy local plane")

    # -- phase 3: fresh-follower bootstrap under load (DESIGN.md §28) -------
    compact_every_s = float(
        os.environ.get("BENCH_REPL_COMPACT_EVERY_S", "0.5")
    )
    bootstrap_budget_s = float(
        os.environ.get("BENCH_REPL_BOOTSTRAP_S", "20.0")
    )
    boot_writers = int(os.environ.get("BENCH_REPL_BOOT_WRITERS", "6"))
    counters.reset()
    wal3 = os.path.join(base_dir, "leader3.wal")
    leader3 = DurableObjectStore(wal3, fsync=True)
    runtime3 = ReplRuntime(
        leader3, "r0", peers=[], cluster_size=3, ack_timeout_s=15.0
    )
    runtime3.promote()
    server3, url3, shutdown3 = start_api_server(
        leader3, port=0, repl=runtime3
    )
    standing = DurableObjectStore(
        os.path.join(base_dir, "standing.wal"), fsync=True
    )
    standing.fence("r0")
    standing_tail = WalFollower(standing, url3, "r1", leader_id="r0")
    standing_tail.start()

    stop = threading.Event()
    errs3: list = []

    def boot_writer(w: int) -> None:
        client = RemoteClient(url3, timeout_s=30.0)
        i = 0
        try:
            while not stop.is_set():
                client.pods().create(
                    make_pod(
                        f"bl{w:02d}-{i:05d}",
                        requests={"cpu": "100m", "memory": "64Mi"},
                    )
                )
                i += 1
        except Exception as e:
            errs3.append(f"boot writer {w}: {e!r}")

    def compactor() -> None:
        while not stop.is_set():
            stop.wait(compact_every_s)
            if stop.is_set():
                return
            try:
                leader3.compact()
            except Exception as e:  # pragma: no cover - audit below
                errs3.append(f"compactor: {e!r}")
                return

    wal_samples: list = []
    total_growth = [0]

    def sampler() -> None:
        prev = 0
        while not stop.is_set():
            cur = leader3.wal_end()
            wal_samples.append(cur)
            if cur > prev:
                total_growth[0] += cur - prev
            prev = cur
            stop.wait(0.05)

    threads3 = [
        threading.Thread(target=boot_writer, args=(w,), name=f"boot-w{w}")
        for w in range(boot_writers)
    ]
    threads3 += [
        threading.Thread(target=compactor, name="boot-compactor"),
        threading.Thread(target=sampler, name="boot-sampler"),
    ]
    for t in threads3:
        t.start()
    # wait for ≥2 shipped generations so the fresh follower's seed is a
    # MID-STREAM checkpoint, not the boot state
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline and (
        counters.get("storage.repl.ckpt_published") < 2 and not errs3
    ):
        time.sleep(0.05)
    if errs3 or counters.get("storage.repl.ckpt_published") < 2:
        stop.set()
        raise SystemExit(
            f"[repl] PHASE-3 WARMUP FAILED: {errs3[:3] or 'no generations'}"
        )
    bstore = DurableObjectStore(
        os.path.join(base_dir, "boot.wal"), fsync=True
    )
    bstore.fence("r0")
    target_rv = leader3.resource_version
    t_attach = time.monotonic()
    boot_tail = WalFollower(bstore, url3, "boot", leader_id="r0")
    boot_tail.start()
    deadline = time.monotonic() + bootstrap_budget_s
    while time.monotonic() < deadline and (
        bstore.resource_version < target_rv and not errs3
    ):
        time.sleep(0.02)
    bootstrap_s = time.monotonic() - t_attach
    caught_up = bstore.resource_version >= target_rv
    stop.set()
    for t in threads3:
        t.join(timeout=30.0)
    # let the tails drain the last groups before auditing convergence
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline and (
        bstore.resource_version < leader3.resource_version
        or standing.resource_version < leader3.resource_version
    ):
        time.sleep(0.05)
    bq = hist.quantile_bounds("storage.repl.bootstrap_s", 0.99) or (
        None, None,
    )
    # stop the tails BEFORE the server so their stream sockets close
    # client-side (no reset noise from the handler threads)
    for tail in (standing_tail, boot_tail):
        tail.stop()
        tail.join(timeout=5.0)
    shutdown3()
    runtime3.close()

    if errs3:
        raise SystemExit(f"[repl] PHASE-3 WRITERS FAILED: {errs3[:3]}")
    if not caught_up:
        raise SystemExit(
            f"[repl] BOOTSTRAP BLEW THE BUDGET: follower at rv "
            f"{bstore.resource_version} < {target_rv} after "
            f"{bootstrap_budget_s}s"
        )
    if counters.get("storage.repl.full_retails"):
        raise SystemExit(
            "[repl] OFFSET-0 RE-TAIL: a follower replayed history "
            "instead of seeding from the shipped checkpoint"
        )
    if counters.get("storage.repl.ckpt_seeds") < 2 or not (
        bstore.checkpoint_rv > 0
    ):
        raise SystemExit(
            "[repl] fresh follower did not seed from a shipped checkpoint"
        )
    if counters.get("storage.repl.compact_deferred"):
        raise SystemExit(
            "[repl] COMPACTION DEFERRED under a hub — the WAL is unbounded"
        )
    # WAL boundedness: the peak never reaches the full appended history
    # and stays within ~2 compaction intervals of growth
    drops, seg, max_seg = 0, 0, 0
    prev = 0
    for cur in wal_samples:
        if cur < prev:
            drops += 1
            max_seg = max(max_seg, seg)
            seg = cur
        else:
            seg += cur - prev
        prev = cur
    max_seg = max(max_seg, seg)
    peak = max(wal_samples) if wal_samples else 0
    if drops < 2:
        raise SystemExit(
            f"[repl] WAL NEVER TRUNCATED under load ({drops} drops)"
        )
    if peak > 2 * max_seg + 65536 or peak >= total_growth[0]:
        raise SystemExit(
            f"[repl] WAL UNBOUNDED: peak {peak}B vs per-interval growth "
            f"{max_seg}B (total appended {total_growth[0]}B)"
        )
    if bstore.resource_version != leader3.resource_version or (
        standing.resource_version != leader3.resource_version
    ):
        raise SystemExit("[repl] PHASE-3 REPLICAS NEVER CONVERGED")
    boot_pods = {p.metadata.name for p in bstore.list("Pod")}
    lead_pods = {p.metadata.name for p in leader3.list("Pod")}
    if boot_pods != lead_pods:
        raise SystemExit(
            f"[repl] BOOTSTRAPPED STATE DIVERGED: "
            f"{len(lead_pods ^ boot_pods)} names differ"
        )
    n_boot = len(lead_pods)
    leader3.close()
    standing.close()
    bstore.close()
    log(
        f"[repl] bootstrap-under-load: fresh follower caught "
        f"{n_boot} pods / rv {target_rv} in {bootstrap_s:.2f}s "
        f"(budget {bootstrap_budget_s}s) off generation "
        f"{counters.get('storage.repl.ckpt_published')} ships; WAL peak "
        f"{peak}B ≤ 2× interval growth {max_seg}B across {drops} "
        f"truncations; zero offset-0 re-tails"
    )

    def _p(lat: list, q: float) -> float:
        return round(lat[min(len(lat) - 1, int(q * len(lat)))], 4)

    tax = _p(lat_r, 0.50) - _p(lat_b, 0.50)
    log(
        f"[repl] {n_writers} writers × {per_writer} pods: quorum plane "
        f"{n_muts / elapsed_r:.0f}/s (p50 {_p(lat_r, 0.50)}s, p99 "
        f"{_p(lat_r, 0.99)}s) vs kill-switch {n_muts / elapsed_b:.0f}/s "
        f"(p50 {_p(lat_b, 0.50)}s); quorum-wait p99 ≤ {qp[1]}s; both "
        f"followers byte-identical, zero acked writes lost"
    )
    return {
        "writers": n_writers,
        "mutations": n_muts,
        "baseline": {
            "throughput_per_s": round(n_muts / elapsed_b, 1),
            "mutate_p50_s": _p(lat_b, 0.50),
            "mutate_p99_s": _p(lat_b, 0.99),
        },
        "replicated": {
            "throughput_per_s": round(n_muts / elapsed_r, 1),
            "mutate_p50_s": _p(lat_r, 0.50),
            "mutate_p99_s": _p(lat_r, 0.99),
            "quorum_wait_p99_bucket_s": qp[1],
            "groups": counters.get("storage.repl.groups"),
            "acks": counters.get("storage.repl.acks"),
            "resyncs": counters.get("storage.repl.resyncs"),
        },
        "replication_tax_p50_s": round(tax, 4),
        "followers_identical": True,
        "acked_writes_lost": 0,
        "bootstrap": {
            "budget_s": bootstrap_budget_s,
            "bootstrap_s": round(bootstrap_s, 3),
            "bootstrap_p99_bucket_s": bq[1],
            "target_rv": target_rv,
            "generations_shipped": counters.get(
                "storage.repl.ckpt_published"
            ),
            "ckpt_seeds": counters.get("storage.repl.ckpt_seeds"),
            "full_retails": 0,
            "wal_peak_bytes": peak,
            "wal_interval_growth_bytes": max_seg,
            "wal_truncations": drops,
        },
    }


def bench_ha() -> dict:
    """HA plane at bench scale: N active-active sharded engines over one
    WAL store, one engine hard-killed mid-run (lease abandoned — peers
    must time it out).  The record carries the product claims: TTL-bounded
    rebalance, convergence, exactly-once binds across the FULL history,
    and the ha.* lease/membership counters."""
    import tempfile

    from minisched_tpu.api.objects import make_node, make_pod
    from minisched_tpu.controlplane.client import Client
    from minisched_tpu.controlplane.durable import DurableObjectStore
    from minisched_tpu.ha import start_ha_engine
    from minisched_tpu.observability import counters
    from minisched_tpu.service.config import default_full_roster_config

    n_engines = int(os.environ.get("BENCH_HA_ENGINES", "3"))
    n_nodes = int(os.environ.get("BENCH_HA_NODES", "48"))
    n_pods = int(os.environ.get("BENCH_HA_PODS", "1200"))
    ttl_s = float(os.environ.get("BENCH_HA_TTL_S", "2.0"))
    wal = os.path.join(tempfile.mkdtemp(prefix="minisched-ha-"), "ha.wal")
    store = DurableObjectStore(wal, archive_compacted=True)
    setup = Client(store=store)
    setup.nodes().create_many(
        [
            make_node(
                f"node{i:04d}",
                capacity={"cpu": "64", "memory": "128Gi", "pods": 256},
            )
            for i in range(n_nodes)
        ]
    )
    pods = [
        make_pod(f"hp{i:05d}", requests={"cpu": "500m", "memory": "64Mi"})
        for i in range(n_pods)
    ]
    first = (2 * n_pods) // 3
    setup.pods().create_many(pods[:first])
    counters.reset()
    t0 = time.monotonic()
    engines = [
        start_ha_engine(
            Client(store=store), f"engine-{i}",
            cfg=default_full_roster_config(), ttl_s=ttl_s,
        )
        for i in range(n_engines)
    ]

    def bound() -> int:
        return sum(1 for p in setup.pods().list() if p.spec.node_name)

    deadline = time.monotonic() + float(
        os.environ.get("BENCH_HA_DEADLINE_S", "240")
    )
    while time.monotonic() < deadline and bound() < first:
        time.sleep(0.2)
    if bound() < first:
        raise SystemExit(f"[ha] first burst stalled: {bound()}/{first}")

    # hard-kill one engine (no lease release), keep the load coming
    victim = engines[len(engines) // 2]
    survivors = [e for e in engines if e is not victim]
    t_kill = time.monotonic()
    victim.kill()
    setup.pods().create_many(pods[first:])
    rebalance_s = None
    while time.monotonic() < deadline:
        if all(
            victim.membership.member_id not in e.membership.members()
            for e in survivors
        ):
            rebalance_s = time.monotonic() - t_kill
            break
        time.sleep(0.05)
    if rebalance_s is None:
        raise SystemExit("[ha] survivors never dropped the dead member")
    bound_n = 0
    while time.monotonic() < deadline:
        bound_n = bound()
        if bound_n >= n_pods:
            break
        time.sleep(0.2)
    elapsed = time.monotonic() - t0
    for e in survivors:
        e.stop()
    store.close()
    if bound_n < n_pods:
        raise SystemExit(f"[ha] DID NOT CONVERGE: {bound_n}/{n_pods} bound")
    # rebalance bounded by the lease TTL (+ a heartbeat tick and margin)
    if rebalance_s > ttl_s + ttl_s / 3.0 + 1.5:
        raise SystemExit(f"[ha] SLOW REBALANCE: {rebalance_s:.2f}s")
    from minisched_tpu.faults import wal_double_binds

    violations = wal_double_binds(wal)
    if violations:
        raise SystemExit(f"[ha] DOUBLE BIND: {violations[:5]}")
    log(
        f"[ha] {n_pods} pods, {n_engines} engines, 1 kill: converged in "
        f"{elapsed:.1f}s, rebalance {rebalance_s:.2f}s (ttl {ttl_s}s)"
    )
    return {
        "pods": n_pods,
        "nodes": n_nodes,
        "engines": n_engines,
        "kills": 1,
        "lease_ttl_s": ttl_s,
        "total_s": round(elapsed, 1),
        "rebalance_s": round(rebalance_s, 2),
        "double_bind": False,
        # the lease/membership ledger (ROADMAP: surfaced in bench records)
        "counters": {
            k: v
            for k, v in counters.snapshot().items()
            if k.startswith("ha.")
        },
    }


def bench_gang() -> dict:
    """Gang + topology-aware placement under mixed gang+singleton churn
    (ISSUE 6): rounds of gangs (all-or-nothing, slice-local preference)
    interleaved with singleton pods over a sliced torus cluster, then a
    DEADLOCK PROBE — two gangs competing for overlapping capacity that
    cannot hold both, resolved by freeing filler pods.  Audits are the
    product claims: ZERO stranded partial gangs (every gang fully bound,
    permit ledger empty), deadlock-freedom (both competing gangs
    eventually place; TTL releases observed in between are the mechanism,
    not a failure), the assume ledger drains to zero, and no node over
    allocatable.  Locality is reported (fraction of gangs fully on one
    slice), not gated — it is a preference, never feasibility."""
    import threading
    from collections import defaultdict

    from minisched_tpu.api.objects import (
        gang_key,
        make_gang_pods,
        make_node,
        make_pod,
    )
    from minisched_tpu.controlplane.client import Client
    from minisched_tpu.observability import counters
    from minisched_tpu.service.config import gang_roster_config
    from minisched_tpu.service.service import SchedulerService

    n_slices = int(os.environ.get("BENCH_GANG_SLICES", "4"))
    hosts = int(os.environ.get("BENCH_GANG_HOSTS", "8"))
    rounds = int(os.environ.get("BENCH_GANG_ROUNDS", "4"))
    gang_size = int(os.environ.get("BENCH_GANG_SIZE", "8"))
    singles_per_round = int(os.environ.get("BENCH_GANG_SINGLES", "24"))
    ttl_s = float(os.environ.get("BENCH_GANG_TTL_S", "5.0"))
    deadline_s = float(os.environ.get("BENCH_GANG_DEADLINE_S", "420"))

    client = Client()
    nodes = []
    for s in range(n_slices):
        for h in range(hosts):
            nodes.append(
                make_node(
                    f"slice{s:02d}-host{h:02d}",
                    capacity={"cpu": "8", "memory": "32Gi", "pods": 64},
                    slice_id=f"slice{s:02d}",
                    torus=(h % 4, h // 4, 0),
                    host_index=h,
                )
            )
    client.nodes().create_many(nodes, return_objects=False)
    n_nodes = len(nodes)

    bound_n = 0
    mu = threading.Lock()

    def counting(pod, node_name, status):
        nonlocal bound_n
        if node_name:
            with mu:
                bound_n += 1

    counters.reset()
    svc = SchedulerService(client)
    sched = svc.start_scheduler(
        gang_roster_config(), device_mode=True,
        max_wave=int(os.environ.get("BENCH_GANG_WAVE", "256")),
        on_decision=counting,
    )
    cosched = next(
        p for p in sched.permit_plugins if p.name() == "Coscheduling"
    )
    # short assume-lease TTL: the quiesce audit waits for the ledger to
    # drain via the idle-path lease confirm (default 30s is the window)
    sched.assume_ttl_s = 3.0
    t0 = time.monotonic()
    deadline = t0 + deadline_s

    def wait_bound(target: int, what: str) -> None:
        while time.monotonic() < deadline:
            with mu:
                if bound_n >= target:
                    return
            time.sleep(0.1)
        raise SystemExit(
            f"[gang] DEADLOCK/timeout waiting for {what}: "
            f"{bound_n}/{target} bound; queue={sched.queue.stats()} "
            f"pending_gangs={cosched.pending_gangs()} "
            f"gang_counters={ {k: v for k, v in counters.snapshot().items() if k.startswith('gang.')} }"
        )

    # ---- phase 1: mixed gang+singleton churn ----------------------------
    target = 0
    gang_names = []
    for r in range(rounds):
        name = f"train-{r}"
        gang_names.append(name)
        batch = make_gang_pods(
            name, gang_size, ttl_s=ttl_s,
            requests={"cpu": "500m", "memory": "256Mi"},
        ) + [
            make_pod(
                f"single-{r}-{i:03d}",
                requests={"cpu": "250m", "memory": "64Mi"},
            )
            for i in range(singles_per_round)
        ]
        client.pods().create_many(batch, return_objects=False)
        target += len(batch)
        wait_bound(target, f"churn round {r + 1}/{rounds}")
        log(
            f"[gang] round {r + 1}/{rounds}: {target} pods bound at "
            f"{time.monotonic() - t0:.1f}s"
        )
    churn_s = time.monotonic() - t0

    # ---- phase 2: deadlock probe ----------------------------------------
    # fill the cluster until free cpu holds ~1.5 gangs, then launch TWO
    # gangs that cannot both fit: they compete (partial placements TTL-
    # release), and freeing the filler must let BOTH land — the
    # deadlock-freedom criterion.
    used = defaultdict(int)
    for p in client.pods().list():
        used[p.spec.node_name] += p.resource_requests().milli_cpu
    # count whole 2-cpu SLOTS per node (total free milli-cpu over-counts:
    # the churn singles leave sub-2cpu holes no 2-cpu pod can use)
    free_slots = sum(
        max(n.status.allocatable.milli_cpu - used[n.metadata.name], 0) // 2000
        for n in nodes
    )
    filler = [
        make_pod(f"filler-{i:04d}", requests={"cpu": "2", "memory": "64Mi"})
        for i in range(max(free_slots - int(1.5 * gang_size), 0))
    ]
    client.pods().create_many(filler, return_objects=False)
    target += len(filler)
    wait_bound(target, "deadlock-probe filler")
    probe = make_gang_pods(
        "probe-a", gang_size, ttl_s=ttl_s, requests={"cpu": "2"}
    ) + make_gang_pods(
        "probe-b", gang_size, ttl_s=ttl_s, requests={"cpu": "2"}
    )
    client.pods().create_many(probe, return_objects=False)
    gang_names += ["probe-a", "probe-b"]
    # one probe gang fits in the remaining headroom and must land even
    # while the other competes for the SAME capacity
    t_probe = time.monotonic()
    wait_bound(target + gang_size, "first probe gang vs competitor")
    ttl_during_probe = counters.get("gang.ttl_expired")
    # free the filler: the loser's members must now place too
    for p in filler:
        client.pods().delete(p.metadata.name, p.metadata.namespace)
    target += 2 * gang_size
    wait_bound(target, "second probe gang after capacity freed")
    probe_s = time.monotonic() - t_probe
    elapsed = time.monotonic() - t0

    # ---- quiesce + audits ------------------------------------------------
    drain_deadline = time.monotonic() + 30
    leaked = True
    while time.monotonic() < drain_deadline:
        with sched._assumed_lock:
            leaked = bool(sched._assumed)
        if not leaked:
            break
        time.sleep(0.1)
    pending = cosched.pending_gangs()
    svc.shutdown_scheduler()
    if leaked:
        raise SystemExit("[gang] ASSUMED-CAPACITY LEAK at quiesce")
    if pending:
        raise SystemExit(f"[gang] STRANDED PARTIAL GANGS at permit: {pending}")

    # zero stranded partial gangs: every gang fully bound, exactly size
    members = defaultdict(list)
    for p in client.pods().list():
        k = gang_key(p)
        if k is not None:
            members[k].append(p)
    partial = {
        k: sum(1 for p in v if p.spec.node_name)
        for k, v in members.items()
        if sum(1 for p in v if p.spec.node_name) not in (0, len(v))
    }
    if partial:
        raise SystemExit(f"[gang] PARTIAL GANGS BOUND: {partial}")
    unbound_gangs = [
        k for k, v in members.items() if not all(p.spec.node_name for p in v)
    ]
    if unbound_gangs:
        raise SystemExit(f"[gang] GANGS NEVER PLACED: {unbound_gangs}")

    # capacity audit: no node over allocatable
    cpu = defaultdict(int)
    cnt = defaultdict(int)
    for p in client.pods().list():
        if p.spec.node_name:
            cpu[p.spec.node_name] += p.resource_requests().milli_cpu
            cnt[p.spec.node_name] += 1
    for node in client.nodes().list():
        alloc = node.status.allocatable
        nm = node.metadata.name
        if cpu[nm] > alloc.milli_cpu or cnt[nm] > alloc.pods:
            raise SystemExit(f"[gang] NODE OVER ALLOCATABLE: {nm}")

    # locality: fraction of gangs fully on one slice (reported, not gated)
    slice_of = {n.metadata.name: n.spec.slice_id for n in nodes}
    one_slice = sum(
        1
        for v in members.values()
        if len({slice_of.get(p.spec.node_name) for p in v}) == 1
    )
    gang_counters = {
        k: v for k, v in counters.snapshot().items() if k.startswith("gang.")
    }
    log(
        f"[gang] {target} pods ({len(members)} gangs × {gang_size} + "
        f"singletons/filler) on {n_nodes} nodes in {elapsed:.1f}s; "
        f"deadlock probe resolved in {probe_s:.1f}s "
        f"({ttl_during_probe} TTL releases observed); "
        f"{one_slice}/{len(members)} gangs slice-local; no partial gangs, "
        f"no leak, no overcommit"
    )
    return {
        "pods": target,
        "nodes": n_nodes,
        "gangs": len(members),
        "gang_size": gang_size,
        "rounds": rounds,
        "total_s": round(elapsed, 1),
        "churn_s": round(churn_s, 1),
        "deadlock_probe_s": round(probe_s, 1),
        "gangs_slice_local": one_slice,
        "counters": gang_counters,
        "stranded_partial_gangs": 0,
        "leak": False,
    }


def _fanout_microbench() -> dict:
    """Shared-payload watch fanout (ISSUE 8): N watcher streams
    serializing one mutation must pay ONE encode — the framed wire chunk
    memoizes on the event object the store fans out.  Runs the same
    event volume at 1 watcher and at ≥100 watchers, consuming every
    queue and encoding every delivery exactly as the HTTP streams do;
    FAILS when the encode counter scales with watcher count (the shared
    payload regressed to per-stream serialization) or any delivery is
    lost.  Timing is recorded for the report; the GATE is the counter —
    deterministic on a noisy 1-core box."""
    from minisched_tpu.api.objects import make_pod
    from minisched_tpu.controlplane.httpserver import event_wire_chunk
    from minisched_tpu.controlplane.store import ObjectStore
    from minisched_tpu.observability import counters

    n_events = int(os.environ.get("BENCH_CHURN_FANOUT_EVENTS", "300"))
    big_w = max(int(os.environ.get("BENCH_CHURN_FANOUT_WATCHERS", "120")), 100)
    out = {}
    for W in (1, big_w):
        store = ObjectStore()
        pods = [
            make_pod(f"f{i:05d}", requests={"cpu": "100m"})
            for i in range(n_events)
        ]
        for p in pods:
            store.create("Pod", p)
        watchers = [
            store.watch("Pod", send_initial=False)[0] for _ in range(W)
        ]
        enc0 = counters.get("watch.fanout.encoded")
        t0 = time.perf_counter()
        for p in pods:
            store.mutate(
                "Pod", p.metadata.namespace, p.metadata.name, lambda o: o
            )
        delivered = 0
        for w in watchers:
            got = 0
            while got < n_events:
                batch = w.next_batch(timeout=2.0)
                if not batch:
                    break
                for ev in batch:
                    event_wire_chunk(ev)
                got += len(batch)
            delivered += got
        wall = time.perf_counter() - t0
        encoded = counters.get("watch.fanout.encoded") - enc0
        for w in watchers:
            w.stop()
        if delivered != W * n_events:
            raise SystemExit(
                f"[churn] FANOUT LOST EVENTS: {delivered}/{W * n_events} "
                f"delivered at {W} watchers"
            )
        out[f"w{W}"] = {
            "watchers": W,
            "events": n_events,
            "encoded": encoded,
            "wall_s": round(wall, 3),
            "encode_per_event": round(encoded / n_events, 3),
        }
    # the flatness claim: the encode count at ≥100 watchers is the same
    # O(events) as at 1 (serial consumption here makes it exact; a tiny
    # slack absorbs future concurrent-consumer variants)
    if out[f"w{big_w}"]["encoded"] > n_events * 1.25:
        raise SystemExit(
            f"[churn] FANOUT ENCODE NOT SHARED: {out[f'w{big_w}']['encoded']} "
            f"encodes for {n_events} events at {big_w} watchers"
        )
    return out


def bench_churn() -> dict:
    """``make bench-churn``: sustained-churn serving (ISSUE 8, the
    "Priority Matters" regime) — Poisson pod arrivals and departures plus
    priority-preemption bursts over an env-scalable window, multi-tenant
    namespaces with per-namespace quota admission at the queue, and a
    quiet tail proving the idle-wave gate.  Headline metric: **p99
    time-to-bind** (arrival timestamp → bind decision), not drain
    throughput.  FAILS on:

    * p99 time-to-bind beyond ``BENCH_CHURN_P99_S``;
    * a stranded partial gang (the resident low-priority gang must
      survive every preemption burst WHOLE — the gang shield's claim —
      and burst gangs must land all-or-nothing);
    * any sampled tenant exceeding its namespace quota;
    * a quiet tail with ZERO zero-build waves (``wave_build.skipped``
      must move while nothing changes);
    * the fanout microbench encoding per-watcher instead of per-event;
    * the standing audits: double-bind, node over allocatable,
      assume-ledger leak at quiesce.
    """
    import random
    import threading
    from collections import defaultdict

    from minisched_tpu.api.objects import (
        gang_key,
        make_gang_pods,
        make_node,
        make_pod,
    )
    from minisched_tpu.controlplane.client import Client
    from minisched_tpu.observability import counters
    from minisched_tpu.observability.profiling import CycleMetrics
    from minisched_tpu.service.config import gang_roster_config
    from minisched_tpu.service.service import SchedulerService

    n_nodes = int(os.environ.get("BENCH_CHURN_NODES", "48"))
    window_s = float(os.environ.get("BENCH_CHURN_WINDOW_S", "12"))
    rate = float(os.environ.get("BENCH_CHURN_ARRIVALS_PER_S", "30"))
    lifetime_s = float(os.environ.get("BENCH_CHURN_LIFETIME_S", "6"))
    tenants = int(os.environ.get("BENCH_CHURN_TENANTS", "3"))
    # sized to BIND under the default smoke (tenant pending peaks ~5-6):
    # holds must actually happen for the admission audit to mean anything
    quota = int(os.environ.get("BENCH_CHURN_QUOTA", "4"))
    bursts = int(os.environ.get("BENCH_CHURN_BURSTS", "2"))
    burst_pods = int(os.environ.get("BENCH_CHURN_BURST_PODS", "16"))
    gang_size = int(os.environ.get("BENCH_CHURN_GANG_SIZE", "4"))
    max_wave = int(os.environ.get("BENCH_CHURN_WAVE", "256"))
    p99_gate_s = float(os.environ.get("BENCH_CHURN_P99_S", "45"))
    seed = int(os.environ.get("BENCH_CHURN_SEED", "1234"))
    n_watchers = int(os.environ.get("BENCH_CHURN_WATCHERS", "16"))
    quiet_s = float(os.environ.get("BENCH_CHURN_QUIET_S", "4"))
    drain_s = float(os.environ.get("BENCH_CHURN_DRAIN_S", "120"))
    fill_frac = float(os.environ.get("BENCH_CHURN_FILL", "0.8"))

    rng = random.Random(seed)
    fanout = _fanout_microbench()
    big_key = max(fanout, key=lambda k: fanout[k]["watchers"])
    log(
        f"[churn] fanout microbench: encode_per_event "
        f"{fanout['w1']['encode_per_event']} @1 watcher vs "
        f"{fanout[big_key]['encode_per_event']} "
        f"@{fanout[big_key]['watchers']} watchers"
    )

    client = Client()
    client.nodes().create_many(
        [
            make_node(
                f"node{i:03d}",
                capacity={"cpu": "8", "memory": "32Gi", "pods": 64},
            )
            for i in range(n_nodes)
        ],
        return_objects=False,
    )

    # -- observability hooks ------------------------------------------------
    mu = threading.Lock()
    arrival_ts: dict = {}  # pod name → monotonic arrival stamp
    bind_ts: dict = {}  # pod name → monotonic bind stamp
    bind_counts: dict = defaultdict(int)  # double-bind audit
    bound_churn: dict = {}  # name → namespace, currently-bound churn pods

    last_reject: dict = {}  # diagnostics: last non-bind decision per pod

    def counting(pod, node_name, status):
        t = time.monotonic()
        name = pod.metadata.name
        if not node_name:
            if name.startswith("burst"):  # burst-audit diagnostics only
                with mu:
                    last_reject[name] = str(status)[:90]
            return
        with mu:
            bind_counts[name] += 1
            if name in arrival_ts and name not in bind_ts:
                bind_ts[name] = t
            if name.startswith("churn-"):
                bound_churn[name] = pod.metadata.namespace

    counters.reset()
    metrics = CycleMetrics()
    cfg = gang_roster_config()
    tenant_ns = [f"ten-{i}" for i in range(tenants)]
    cfg.queue_opts["namespace_quota"] = {ns: quota for ns in tenant_ns}
    svc = SchedulerService(client)
    sched = svc.start_scheduler(
        cfg, device_mode=True, max_wave=max_wave, on_decision=counting,
        metrics=metrics, prewarm=True, prewarm_scan=False,
    )
    sched.assume_ttl_s = 3.0

    # staleness watchers: K live Pod streams consumed concurrently; the
    # sampler reads how far the slowest lags the store's rv
    watcher_rv = [0] * n_watchers
    watcher_stop = threading.Event()
    watchers = [
        client.store.watch("Pod", send_initial=False)[0]
        for _ in range(n_watchers)
    ]

    def _consume(i: int) -> None:
        while not watcher_stop.is_set():
            for ev in watchers[i].next_batch(timeout=0.2):
                if ev.rv > watcher_rv[i]:
                    watcher_rv[i] = ev.rv
            if watchers[i].stopped:
                return

    watcher_threads = [
        threading.Thread(target=_consume, args=(i,), daemon=True)
        for i in range(n_watchers)
    ]
    for t in watcher_threads:
        t.start()

    t0 = time.monotonic()
    try:
        # -- prefill: drive occupancy to ~fill_frac so bursts must preempt
        total_cpu = n_nodes * 8000
        n_fill = max(int(total_cpu * fill_frac) // 2000 - gang_size, 0)
        filler = [
            make_pod(
                f"fill-{i:04d}", namespace="resident",
                requests={"cpu": "2", "memory": "64Mi"},
            )
            for i in range(n_fill)
        ]
        resident_gang = make_gang_pods(
            "resident-gang", gang_size, namespace="resident",
            ttl_s=10.0, requests={"cpu": "2", "memory": "64Mi"}, priority=0,
        )
        client.pods().create_many(
            filler + resident_gang, return_objects=False
        )
        prefill_target = len(filler) + len(resident_gang)
        deadline = time.monotonic() + drain_s
        while time.monotonic() < deadline:
            with mu:
                done = sum(
                    1 for n in bind_counts if not n.startswith("churn-")
                )
            if done >= prefill_target:
                break
            time.sleep(0.1)
        else:
            raise SystemExit(
                f"[churn] prefill never bound ({done}/{prefill_target})"
            )
        log(
            f"[churn] prefill: {prefill_target} resident pods "
            f"({fill_frac:.0%} cpu) bound at {time.monotonic() - t0:.1f}s"
        )

        # -- churn window ---------------------------------------------------
        tick = 0.1
        burst_at = [
            window_s * (k + 1) / (bursts + 1) for k in range(bursts)
        ]
        fired = [False] * bursts
        seq = 0
        max_staleness_rv = 0
        quota_peak: dict = defaultdict(int)
        t_window = time.monotonic()
        while (elapsed := time.monotonic() - t_window) < window_s:
            # Poisson arrivals, spread across tenant namespaces
            n_arr = sum(
                1 for _ in range(int(rate * tick * 4))
                if rng.random() < 0.25
            )
            if n_arr:
                batch = []
                now = time.monotonic()
                for _ in range(n_arr):
                    ns = tenant_ns[rng.randrange(tenants)]
                    name = f"churn-{seq:06d}"
                    seq += 1
                    batch.append(
                        make_pod(
                            name, namespace=ns,
                            requests={"cpu": "250m", "memory": "32Mi"},
                        )
                    )
                    arrival_ts[name] = now
                client.pods().create_many(batch, return_objects=False)
            # Poisson departures over currently-bound churn pods
            with mu:
                bound_now = list(bound_churn.items())
            for name, ns in bound_now:
                if rng.random() < tick / lifetime_s:
                    try:
                        client.pods().delete(name, ns)
                    except KeyError:
                        pass
                    with mu:
                        bound_churn.pop(name, None)
            # priority-preemption bursts: high-priority singles + a gang
            for k, at in enumerate(burst_at):
                if not fired[k] and elapsed >= at:
                    fired[k] = True
                    now = time.monotonic()
                    burst = [
                        make_pod(
                            f"burst{k}-{i:03d}", namespace="burst",
                            requests={"cpu": "2", "memory": "64Mi"},
                            priority=100,
                        )
                        for i in range(burst_pods)
                    ] + make_gang_pods(
                        f"burst{k}-gang", gang_size, namespace="burst",
                        ttl_s=10.0, requests={"cpu": "2", "memory": "64Mi"},
                        priority=100,
                    )
                    for p in burst:
                        arrival_ts[p.metadata.name] = now
                    client.pods().create_many(burst, return_objects=False)
                    log(f"[churn] burst {k + 1}/{bursts} injected at {at:.1f}s")
            # samplers: watcher staleness + quota admission audit
            rv = client.store.resource_version
            lag = rv - min(watcher_rv)
            if lag > max_staleness_rv and min(watcher_rv) > 0:
                max_staleness_rv = lag
            # peaks recorded only: admitted > limit alone is NOT a
            # violation (requeues and gang members re-admit past the cap
            # by contract), and a held pod under an open cap is a
            # LEGITIMATE transient while a pop_batch gathers (promotions
            # defer to the batch seal).  The hard gates are the queue's
            # own tripwire counter (checked after shutdown) and the
            # drain phase below requiring every hold to clear.
            for ns, st in sched.queue.quota_stats().items():
                quota_peak[ns] = max(quota_peak[ns], st["admitted"])
            time.sleep(tick)
        arrivals = seq
        log(
            f"[churn] window closed: {arrivals} arrivals over {window_s}s "
            f"({len(bind_ts)} bound so far)"
        )

        # -- drain: bursts must land; then the quiet tail -------------------
        burst_names = {n for n in arrival_ts if n.startswith("burst")}
        deadline = time.monotonic() + drain_s
        while time.monotonic() < deadline:
            with mu:
                missing = [n for n in burst_names if n not in bind_ts]
            qstats = sched.queue.stats()
            # quota_held must clear too: a hold that never promotes once
            # slots free is the stalled-promotion bug (deterministic
            # here — arrivals stopped, so holds only ever drain)
            if (
                not missing
                and qstats["active"] == 0
                and qstats["backoff"] == 0
                and qstats.get("quota_held", 0) == 0
            ):
                break
            time.sleep(0.2)
        qstats = sched.queue.stats()
        if qstats.get("quota_held", 0):
            raise SystemExit(
                f"[churn] QUOTA HOLD STALLED at drain: {qstats} with "
                f"arrivals stopped — held pods must promote as slots free"
            )
        with mu:
            missing = [n for n in burst_names if n not in bind_ts]
        if missing:
            # diagnostics: where ARE they? (store state + engine ledgers)
            sample = {}
            for n in sorted(missing)[:4]:
                try:
                    p = client.pods().get(n, "burst")
                    sample[n] = (
                        p.spec.node_name or "-",
                        p.status.nominated_node_name or "-",
                    )
                except KeyError:
                    sample[n] = "GONE"
            with sched._assumed_lock:
                n_assumed = len(sched._assumed)
            uid_of = {}
            for n in sorted(missing)[:4]:
                try:
                    uid_of[n] = client.pods().get(n, "burst").metadata.uid
                except KeyError:
                    pass
            with sched.queue._cond:
                tracked = {
                    n: (u in sched.queue._queued_uids,
                        u in sched.queue._held_uids)
                    for n, u in uid_of.items()
                }
            raise SystemExit(
                f"[churn] PREEMPTION BURST NEVER LANDED: {len(missing)} "
                f"high-priority pods unbound after {drain_s}s "
                f"(e.g. {sample}); queue={sched.queue.stats()} "
                f"assumed={n_assumed} backlog={len(sched._scan_backlog)} "
                f"waiting={len(getattr(sched, '_waiting_pods', {}))} "
                f"tracked(queued,held)={tracked} "
                f"last_reject={ {n: last_reject.get(n) for n in sorted(missing)[:4]} }"
            )

        # quiet tail: rounds of infeasible probe pods — every pop makes a
        # wave, nothing moves in the cluster, so from the second round on
        # the builder must reuse tables wholesale (wave_build.skipped)
        skipped_before = counters.get("wave_build.skipped")
        rounds = max(int(quiet_s / 0.5), 3)
        for r in range(rounds):
            probes = [
                make_pod(
                    f"probe-{r}-{i}", namespace="probe",
                    requests={"cpu": "64"},  # larger than any node
                )
                for i in range(8)
            ]
            client.pods().create_many(probes, return_objects=False)
            time.sleep(0.5)
        zero_build_tail = (
            counters.get("wave_build.skipped") - skipped_before
        )
        if zero_build_tail == 0:
            raise SystemExit(
                "[churn] IDLE-WAVE GATE NEVER FIRED on the quiet tail "
                f"(wave_build.skipped stayed {skipped_before} over "
                f"{rounds} probe rounds)"
            )
        elapsed = time.monotonic() - t0

        # -- quiesce: the assume ledger must drain --------------------------
        drain_deadline = time.monotonic() + 30
        leaked = True
        while time.monotonic() < drain_deadline:
            with sched._assumed_lock:
                leaked = bool(sched._assumed)
            if not leaked:
                break
            time.sleep(0.1)
        snap = metrics.snapshot()
    finally:
        watcher_stop.set()
        for w in watchers:
            w.stop()
        svc.shutdown_scheduler()

    if leaked:
        raise SystemExit("[churn] ASSUMED-CAPACITY LEAK at quiesce")
    if counters.get("queue.quota_violation"):
        raise SystemExit(
            f"[churn] NAMESPACE QUOTA VIOLATED: "
            f"{counters.get('queue.quota_violation')} non-gang arrivals "
            f"admitted past their cap"
        )

    # -- audits ------------------------------------------------------------
    # exactly-once: no pod ever received two successful bind decisions
    doubles = {n: c for n, c in bind_counts.items() if c > 1}
    if doubles:
        raise SystemExit(f"[churn] DOUBLE BINDS: {doubles}")
    # capacity: no node over allocatable
    cpu = defaultdict(int)
    cnt = defaultdict(int)
    final_pods = client.pods().list()
    for p in final_pods:
        if p.spec.node_name:
            cpu[p.spec.node_name] += p.resource_requests().milli_cpu
            cnt[p.spec.node_name] += 1
    for node in client.nodes().list():
        alloc = node.status.allocatable
        nm = node.metadata.name
        if cpu[nm] > alloc.milli_cpu or cnt[nm] > alloc.pods:
            raise SystemExit(f"[churn] NODE OVER ALLOCATABLE: {nm}")
    # gang integrity: every gang all-or-nothing; the RESIDENT gang must
    # have survived both preemption bursts fully bound (the shield)
    members = defaultdict(list)
    for p in final_pods:
        k = gang_key(p)
        if k is not None:
            members[k].append(p)
    partial = {
        k: sum(1 for p in v if p.spec.node_name)
        for k, v in members.items()
        if sum(1 for p in v if p.spec.node_name) not in (0, len(v))
    }
    if partial:
        raise SystemExit(f"[churn] PARTIAL GANGS BOUND: {partial}")
    res = members.get("resident/resident-gang", [])
    if len(res) != gang_size or not all(p.spec.node_name for p in res):
        raise SystemExit(
            f"[churn] RESIDENT GANG STRANDED by preemption: "
            f"{sum(1 for p in res if p.spec.node_name)}/{gang_size} bound"
        )

    # -- headline: p99 time-to-bind over churn + burst arrivals ------------
    ttbs = sorted(
        bind_ts[n] - arrival_ts[n] for n in bind_ts if n in arrival_ts
    )
    if not ttbs:
        raise SystemExit("[churn] no time-to-bind samples recorded")

    p50, p95, p99 = _pct(ttbs, 0.50), _pct(ttbs, 0.95), _pct(ttbs, 0.99)
    if p99 > p99_gate_s:
        raise SystemExit(
            f"[churn] P99 TIME-TO-BIND REGRESSED: {p99}s > gate "
            f"{p99_gate_s}s (p50 {p50}s, {len(ttbs)} samples)"
        )
    from minisched_tpu.observability import hist

    live_p99 = _crosscheck_live_p99("sched.time_to_bind_s", p99, "churn")
    waves = counters.get("wave_pipeline.waves") or 1
    zero_ratio = round(counters.get("wave_build.skipped") / waves, 3)
    csnap = counters.snapshot()
    log(
        f"[churn] p99 time-to-bind {p99}s (p50 {p50}s, p95 {p95}s, "
        f"{len(ttbs)} binds) over {arrivals} arrivals; zero-build waves "
        f"{counters.get('wave_build.skipped')}/{waves} "
        f"(tail {zero_build_tail}); max watcher lag {max_staleness_rv} rv; "
        f"preempt shielded {csnap.get('gang.preempt_shielded', 0)}; "
        f"quota peaks {dict(quota_peak)}"
    )
    return {
        "nodes": n_nodes,
        "window_s": window_s,
        "arrivals": arrivals,
        "bound": len(ttbs),
        "total_s": round(elapsed, 1),
        "ttb_p50_s": p50,
        "ttb_p95_s": p95,
        "ttb_p99_s": p99,
        "ttb_p99_live_bucket_s": live_p99,
        "ttb_gate_s": p99_gate_s,
        "metrics_snapshot": hist.snapshot(),
        "zero_build_waves": counters.get("wave_build.skipped"),
        "zero_build_tail": zero_build_tail,
        "zero_build_ratio": zero_ratio,
        "pipelined_waves": counters.get("wave_pipeline.waves"),
        "max_watcher_staleness_rv": max_staleness_rv,
        "watch_evictions": csnap.get("watch.fanout.evicted_slow", 0),
        "fanout_encoded": csnap.get("watch.fanout.encoded", 0),
        "fanout_shared": csnap.get("watch.fanout.shared", 0),
        "preempt_shielded": csnap.get("gang.preempt_shielded", 0),
        "quota_peaks": dict(quota_peak),
        "quota_held_total": csnap.get("queue.quota_held", 0),
        "quota_admitted": csnap.get("queue.quota_admitted", 0),
        "gang_counters": {
            k: v for k, v in csnap.items() if k.startswith("gang.")
        },
        "fanout_microbench": fanout,
        "stall_total_s": round(
            snap.get("wave_pipeline_stall", {}).get("total_s", 0.0), 3
        ),
        "build_total_s": round(
            snap.get("wave_pipeline_build", {}).get("total_s", 0.0), 3
        ),
    }


def bench_relist() -> dict:
    """``make bench-relist``: the relist-storm regime (ISSUE 14) — the
    COW read plane serving a thundering herd of full state reads.  Two
    storms over a REAL HTTP façade plus a byte-parity audit:

    * **410 storm** — W clients hold a resume cursor the history ring
      has compacted away, every watch-open answers 410 Gone at once
      (SIGKILL-free eviction: ring compaction, not process death), and
      all W relist simultaneously while a writer keeps mutating.
      Gates: p99 list latency, and ZERO write-path stalls (storm write
      p99 within a factor of the quiet baseline — reads never hold the
      write lock).
    * **cold-boot storm** — W informer-boot lists at one quiet rv.
      Gate: encode-once (`store.list_cache.encodes` delta ≤ a few
      benign double-encode races, the rest `hits` streaming shared
      bytes).
    * **kill-switch parity** — identical seeded stores under
      MINISCHED_COW_READS=1 and =0 answer byte-identical list bodies,
      full and namespace-filtered.

    FAILS on: encodes NOT ≪ requests, sampled p99 over the gate, the
    live ``http.list_s`` histogram disagreeing with the sampled p99
    beyond bucket resolution, write-path stalls during the storm, or
    any parity break."""
    import threading
    import urllib.error
    import urllib.request

    from minisched_tpu.api.objects import make_pod
    from minisched_tpu.controlplane.httpserver import start_api_server
    from minisched_tpu.controlplane.store import ObjectStore
    from minisched_tpu.observability import counters

    W = int(os.environ.get("BENCH_RELIST_WATCHERS", "220"))
    n_obj = int(os.environ.get("BENCH_RELIST_OBJECTS", "300"))
    p99_gate_s = float(os.environ.get("BENCH_RELIST_P99_S", "1.0"))
    stall_factor = float(os.environ.get("BENCH_RELIST_STALL_FACTOR", "30"))
    stall_floor_s = float(os.environ.get("BENCH_RELIST_STALL_FLOOR_S", "0.25"))

    counters.reset()
    store = ObjectStore(history_events=64)
    if store.read_plane() is None:
        bench_skip("MINISCHED_COW_READS=0: the relist role benches the COW plane")
    server, base, shutdown = start_api_server(store)

    def get_raw(path: str) -> bytes:
        with urllib.request.urlopen(f"{base}{path}") as r:
            return r.read()

    list_lat: list = []
    lat_mu = threading.Lock()

    def timed_list() -> bytes:
        t0 = time.monotonic()
        body = get_raw("/api/v1/pods")
        dt = time.monotonic() - t0
        with lat_mu:
            list_lat.append(dt)
        return body

    try:
        seeds = [make_pod(f"seed-{i:04d}") for i in range(n_obj)]
        for p in seeds:
            store.create("Pod", p)
        stale_rv = store.resource_version

        def touch(i: int) -> None:
            # rv churn WITHOUT set growth (an update, not a create): the
            # list body stays n_obj pods, so the storm measures serving,
            # not an ever-fatter payload
            p = store.get("Pod", "default", seeds[i % n_obj].metadata.name)
            p.metadata.labels["touched"] = str(i)
            store.update("Pod", p)

        # quiet write baseline: per-mutation latency with no storm around
        quiet_w: list = []
        for i in range(200):
            t0 = time.monotonic()
            touch(i)
            quiet_w.append(time.monotonic() - t0)
        quiet_w.sort()
        quiet_write_p99 = _pct(quiet_w, 0.99, 6)

        # churn past the 64-event history ring so the stale cursor is
        # compacted: every resume below answers 410 (the SIGKILL-free
        # mass eviction)
        for i in range(120):
            touch(i)

        log(f"[relist] 410 storm: {W} watchers resuming at rv {stale_rv}")
        storm_gate = threading.Barrier(W + 1)
        got_410 = [0]
        errs: list = []

        def storm_client(idx: int) -> None:
            try:
                try:
                    with urllib.request.urlopen(
                        f"{base}/api/v1/pods?watch=true"
                        f"&resource_version={stale_rv}"
                    ) as r:
                        r.read(1)
                    raise AssertionError("stale resume was not evicted")
                except urllib.error.HTTPError as e:
                    assert e.code == 410, f"expected 410, got {e.code}"
                    e.read()
                with lat_mu:
                    got_410[0] += 1
                storm_gate.wait()  # ... and everyone relists AT ONCE
                timed_list()
            except BaseException as e:  # surfaced by the gate below
                errs.append(e)
                try:
                    storm_gate.abort()
                except BaseException:
                    pass

        writer_stop = threading.Event()
        storm_w: list = []

        def storm_writer() -> None:
            # ~30 writes/s: every write swaps the snapshot (invalidating
            # the list cache wholesale), so the write cadence bounds how
            # many distinct payloads the storm can possibly encode.  A
            # writer whose period is at or below the single-encode cost
            # (~4ms for a few hundred pods under the GIL) would force
            # EVERY list onto a fresh snapshot — a treadmill no cache
            # can win — without resembling any real plane, where relist
            # bursts are orders of magnitude denser than mutations.
            i = 0
            while not writer_stop.is_set():
                t0 = time.monotonic()
                touch(i)
                storm_w.append(time.monotonic() - t0)
                i += 1
                time.sleep(0.03)

        threads = [
            threading.Thread(target=storm_client, args=(i,)) for i in range(W)
        ]
        wt = threading.Thread(target=storm_writer)
        for t in threads:
            t.start()
        wt.start()
        try:
            storm_gate.wait()
        except threading.BrokenBarrierError:
            pass  # a client failed pre-barrier; surfaced via errs below
        t_storm0 = time.monotonic()
        for t in threads:
            t.join(timeout=60)
        storm_s = time.monotonic() - t_storm0
        writer_stop.set()
        wt.join(timeout=10)
        if errs:
            raise SystemExit(f"[relist] STORM CLIENT FAILED: {errs[0]!r}")
        if got_410[0] != W:
            raise SystemExit(
                f"[relist] EVICTION INCOMPLETE: {got_410[0]}/{W} saw 410"
            )
        storm_w.sort()
        storm_write_p99 = _pct(storm_w, 0.99, 6) if storm_w else 0.0
        write_stall_gate_s = max(stall_floor_s, quiet_write_p99 * stall_factor)
        if storm_w and storm_write_p99 > write_stall_gate_s:
            raise SystemExit(
                f"[relist] WRITE PATH STALLED DURING STORM: p99 "
                f"{storm_write_p99}s vs quiet {quiet_write_p99}s "
                f"(gate {write_stall_gate_s:.4f}s) — reads are holding "
                f"the write lock"
            )

        # cold-boot storm: W informer-boot lists at ONE quiet rv —
        # the encode-once regime the cache exists for
        log(f"[relist] cold-boot storm: {W} lists at one rv")
        enc_before = counters.get("store.list_cache.encodes")
        boot_gate = threading.Barrier(W)
        bodies: dict = {}

        def boot_client(idx: int) -> None:
            try:
                boot_gate.wait()
                bodies[idx] = timed_list()
            except BaseException as e:
                errs.append(e)

        threads = [
            threading.Thread(target=boot_client, args=(i,)) for i in range(W)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        if errs:
            raise SystemExit(f"[relist] BOOT CLIENT FAILED: {errs[0]!r}")
        if len({bodies[i] for i in bodies}) != 1:
            raise SystemExit(
                "[relist] COLD-BOOT BODIES DIVERGED at one rv"
            )
        boot_encodes = counters.get("store.list_cache.encodes") - enc_before
        if boot_encodes > 1:  # misses serialize: one build per (ns, rv)
            raise SystemExit(
                f"[relist] ENCODE-ONCE BROKEN: {boot_encodes} encodes "
                f"for {W} cold-boot lists at one rv"
            )

        encodes = counters.get("store.list_cache.encodes")
        hits = counters.get("store.list_cache.hits")
        requests = counters.get("wire.relist_requests")
        if encodes > 0.25 * requests:
            raise SystemExit(
                f"[relist] ENCODES NOT ≪ REQUESTS: {encodes} encodes "
                f"for {requests} list requests"
            )
        list_lat.sort()
        sampled_p99 = _pct(list_lat, 0.99, 4)
        if sampled_p99 > p99_gate_s:
            raise SystemExit(
                f"[relist] LIST P99 {sampled_p99}s OVER GATE {p99_gate_s}s"
            )
        # live/sampled crosscheck on a QUIET sequential probe: the storm
        # samples above are client end-to-end and include the 220-thread
        # client's own GIL queuing, which the server-side ``http.list_s``
        # observation can never contain — comparing those two windows
        # would gate on the bench client, not the plane.  A single probe
        # client makes the windows coincide.
        from minisched_tpu.observability import hist as _hist

        _hist.reset()
        probe: list = []
        for _ in range(80):
            t0 = time.monotonic()
            get_raw("/api/v1/pods")
            probe.append(time.monotonic() - t0)
        probe.sort()
        probe_p99 = _pct(probe, 0.99, 4)
        live = _crosscheck_live_p99("http.list_s", probe_p99, "relist")
    finally:
        shutdown()

    # kill-switch byte parity: the COW cached/chunked path and the
    # locked re-encode path must answer the SAME bytes — uid and
    # creation_timestamp pinned so both stores hold identical content
    def seeded(cow: str):
        os.environ["MINISCHED_COW_READS"] = cow
        try:
            st = ObjectStore()
        finally:
            os.environ.pop("MINISCHED_COW_READS", None)
        for i in range(40):
            p = make_pod(
                f"par-{i:03d}",
                namespace="default" if i % 4 else "kube-system",
            )
            p.metadata.uid = f"uid-{i:03d}"
            p.metadata.creation_timestamp = 1700000000.0 + i
            st.create("Pod", p)
        return st

    parity: dict = {}
    for cow in ("1", "0"):
        st = seeded(cow)
        srv, b2, shut2 = start_api_server(st)
        try:
            with urllib.request.urlopen(f"{b2}/api/v1/pods") as r:
                full = r.read()
            with urllib.request.urlopen(
                f"{b2}/api/v1/namespaces/kube-system/pods"
            ) as r:
                ns = r.read()
            parity[cow] = (full, ns)
        finally:
            shut2()
    if parity["1"] != parity["0"]:
        raise SystemExit(
            "[relist] KILL-SWITCH PARITY BROKEN: MINISCHED_COW_READS=0 "
            "and =1 answered different list bytes"
        )
    log("[relist] kill-switch parity: list bodies byte-identical")

    return {
        "watchers": W,
        "objects": n_obj,
        "storm_410_s": round(storm_s, 3),
        "list_requests": requests,
        "list_cache_encodes": encodes,
        "list_cache_hits": hits,
        "cold_boot_encodes": boot_encodes,
        "relist_bytes_shared": counters.get("wire.relist_bytes_shared"),
        "list_p50_s": _pct(list_lat, 0.50, 4),
        "list_p99_s": sampled_p99,
        "probe_list_p99_s": probe_p99,
        "live_list_p99_bucket": live,
        "quiet_write_p99_s": quiet_write_p99,
        "storm_write_p99_s": storm_write_p99,
        "write_stall_gate_s": round(write_stall_gate_s, 4),
        "parity_bytes": len(parity["1"][0]) + len(parity["1"][1]),
    }


def bench_readscale() -> dict:
    """``make bench-readscale`` (ISSUE 17, DESIGN.md §29): the
    follower-serving read plane must BUY capacity, not just redundancy.
    Opt-in via ``BENCH_READSCALE=1`` — the role boots a 3-replica
    process plane twice over plus an in-process triple.  Three phases:

    * **scaling storm** — the process plane seeded with
      BENCH_READSCALE_OBJECTS pods; W keep-alive clients run the same
      fixed list window twice: every client on the leader alone, then
      spread across all three replica façades.  Gate: spread rate ≥
      BENCH_READSCALE_GATE × the single-replica rate (default 1.7×).
    * **encode-once everywhere** — an IN-PROCESS leader + two served
      followers (counters are process-global there, so the deltas are
      visible) absorb a quiet list storm spread across all three
      façades at one rv.  Gate: every serving replica answered from
      its own memoized COW payload — ``store.list_cache.encodes``
      delta between 1 and 2 per replica for hundreds of requests.
    * **read availability across leader kill** — endpoint-aware
      readers (min_rv-bounded, session-monotonic rv) list continuously
      for BENCH_READ_FAILOVER_S while the leader is SIGKILLed
      mid-window and a writer keeps advancing rv through the failover.
      Gates: zero read errors, zero rv regressions, and the longest
      gap between successive successful reads ≤ BENCH_READSCALE_GAP_S
      (reads must ride the surviving followers THROUGH the election,
      not wait it out).
    """
    import http.client
    import tempfile
    import threading
    import urllib.parse
    import urllib.request

    from minisched_tpu.api.objects import make_pod
    from minisched_tpu.controlplane.durable import DurableObjectStore
    from minisched_tpu.controlplane.httpserver import start_api_server
    from minisched_tpu.controlplane.remote import RemoteClient, RemoteStore
    from minisched_tpu.controlplane.repl import ReplRuntime, WalFollower
    from minisched_tpu.controlplane.replproc import ReplicatedPlane
    from minisched_tpu.observability import counters

    if os.environ.get("BENCH_READSCALE", "0") == "0":
        bench_skip("BENCH_READSCALE unset: read-scaling role is opt-in")

    P = int(os.environ.get("BENCH_READSCALE_PROCS", "4"))
    W = int(os.environ.get("BENCH_READSCALE_CLIENTS", "8"))  # per proc
    n_obj = int(os.environ.get("BENCH_READSCALE_OBJECTS", "300"))
    window_s = float(os.environ.get("BENCH_READSCALE_WINDOW_S", "2.0"))
    gate = float(os.environ.get("BENCH_READSCALE_GATE", "1.7"))
    fail_s = float(os.environ.get("BENCH_READ_FAILOVER_S", "6.0"))
    gap_gate_s = float(os.environ.get("BENCH_READSCALE_GAP_S", "2.0"))
    ttl_s = 1.0

    counters.reset()

    # ---- phase 1+3 topology: the real process plane -------------------
    tmp = tempfile.mkdtemp(prefix="bench-readscale-")

    # the storm drives from SEPARATE client processes: the replicas are
    # each their own process, so a single GIL-bound bench client would
    # measure its own ceiling, not the plane's serving capacity
    helper = os.path.join(tmp, "_list_storm.py")
    with open(helper, "w") as f:
        f.write(
            "import http.client, sys, threading, time, urllib.parse\n"
            "urls = sys.argv[1].split(',')\n"
            "window_s, W, off = float(sys.argv[2]), int(sys.argv[3]), "
            "int(sys.argv[4])\n"
            "counts = [0] * W\n"
            "stop = threading.Event()\n"
            "errs = []\n"
            "def client(i):\n"
            "    u = urllib.parse.urlparse(urls[(off + i) % len(urls)])\n"
            "    conn = http.client.HTTPConnection(u.hostname, u.port,"
            " timeout=10)\n"
            "    try:\n"
            "        while not stop.is_set():\n"
            "            conn.request('GET', '/api/v1/pods')\n"
            "            r = conn.getresponse()\n"
            "            body = r.read()\n"
            "            if r.status != 200:\n"
            "                errs.append('HTTP %d: %r' % (r.status,"
            " body[:80]))\n"
            "                return\n"
            "            counts[i] += 1\n"
            "    except Exception as e:\n"
            "        if not stop.is_set():\n"
            "            errs.append(repr(e))\n"
            "    finally:\n"
            "        conn.close()\n"
            "threads = [threading.Thread(target=client, args=(i,))"
            " for i in range(W)]\n"
            "for t in threads:\n"
            "    t.start()\n"
            "time.sleep(window_s)\n"
            "stop.set()\n"
            "for t in threads:\n"
            "    t.join(timeout=30)\n"
            "if errs:\n"
            "    print(errs[0], file=sys.stderr)\n"
            "    sys.exit(1)\n"
            "print(sum(counts))\n"
        )

    def storm(urls: list, label: str) -> float:
        """Fixed-window keep-alive list storm: P client processes × W
        connections each, round-robin across façades; returns lists/s."""
        procs = [
            subprocess.Popen(
                [
                    sys.executable, helper, ",".join(urls),
                    str(window_s), str(W), str(k),
                ],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            )
            for k in range(P)
        ]
        total = 0
        for p in procs:
            out, err = p.communicate(timeout=window_s + 60)
            if p.returncode != 0:
                raise SystemExit(
                    f"[readscale] {label} CLIENT FAILED: "
                    f"{err.decode(errors='replace')[-200:]}"
                )
            total += int(out.strip())
        rate = total / window_s
        log(
            f"[readscale] {label}: {rate:.0f} lists/s "
            f"({P}x{W} client connections)"
        )
        return rate

    plane = ReplicatedPlane(tmp, n=3, fsync=False, ttl_s=ttl_s)
    try:
        url = plane.start()
        client = RemoteClient(url, timeout_s=10.0)
        for i in range(n_obj):
            client.pods().create(make_pod(f"seed-{i:04d}"))
        seed_rv = int(client.store.list_with_rv("Pod")[1])
        bases = [r.base_url for r in plane.replicas]
        # every replica must have applied the seed before the storm —
        # the bounded read IS the convergence probe
        for b in bases:
            deadline = time.monotonic() + 15.0
            while True:
                try:
                    with urllib.request.urlopen(
                        f"{b}/api/v1/pods?min_rv={seed_rv}"
                    ) as r:
                        r.read()
                    break
                except urllib.error.HTTPError as e:
                    e.read()
                    if e.code != 504 or time.monotonic() > deadline:
                        raise SystemExit(
                            f"[readscale] {b} never applied rv {seed_rv} "
                            f"(HTTP {e.code})"
                        )
                    time.sleep(0.05)

        leader = plane.leader()
        leader_base = leader.base_url
        rate_1 = storm([leader_base], "1-replica storm")
        rate_3 = storm(bases, "3-replica storm")
        scaling = rate_3 / rate_1 if rate_1 else 0.0
        # the scaling gate needs hardware that can EXPRESS scaling: three
        # server processes plus the client fleet on fewer than 4 cores
        # all share the same silicon, so wall-clock throughput is pinned
        # at ~1x no matter how good the read plane is.  Same philosophy
        # as the TPU-gap skips: a capability gap is not a regression.
        cores = os.cpu_count() or 1
        scaling_gated = cores >= 4
        if scaling_gated and scaling < gate:
            raise SystemExit(
                f"[readscale] SCALING UNDER GATE: {rate_3:.0f}/s across 3 "
                f"replicas vs {rate_1:.0f}/s on 1 = {scaling:.2f}x < "
                f"{gate}x — followers are not buying read capacity"
            )
        if not scaling_gated:
            log(
                f"[readscale] scaling gate SKIPPED: {cores} CPU core(s) "
                f"— replicas share the silicon, wall-clock scaling is "
                f"bounded at ~1x (measured {scaling:.2f}x, recorded "
                f"informationally; gate re-arms on >=4 cores)"
            )
        else:
            log(f"[readscale] read scaling 1->3 replicas: {scaling:.2f}x")

        # ---- phase 3: availability across a leader SIGKILL ------------
        R = int(os.environ.get("BENCH_READSCALE_READERS", "6"))
        stop_all = threading.Event()
        rerrs: list = []
        werrs: list = []
        done_ts: list = []
        lats: list = []
        mu = threading.Lock()

        def reader(i: int) -> None:
            home = bases[i % len(bases)]
            rs = RemoteStore(
                home, endpoints=[b for b in bases if b != home],
                timeout_s=10.0,
            )
            last_rv = 0
            try:
                while not stop_all.is_set():
                    t0 = time.monotonic()
                    try:
                        _pods, rv = rs.list_with_rv("Pod")
                    except Exception as e:
                        rerrs.append(f"reader {i}: {e!r}")
                        return
                    now = time.monotonic()
                    if rv < last_rv:
                        rerrs.append(
                            f"reader {i}: rv regressed {last_rv}->{rv}"
                        )
                        return
                    last_rv = rv
                    with mu:
                        done_ts.append(now)
                        lats.append(now - t0)
            finally:
                rs.close()

        def writer() -> None:
            rs = RemoteStore(bases[1], endpoints=bases, timeout_s=10.0)
            i = 0
            acked = 0
            try:
                while not stop_all.is_set():
                    try:
                        rs.create("Pod", make_pod(f"fo-{i:05d}"))
                        acked += 1
                    except Exception:
                        time.sleep(0.2)  # mid-election: retry fresh
                    i += 1
                    time.sleep(0.02)
            finally:
                rs.close()
            if acked == 0:
                werrs.append("failover writer never acked a write")

        threads = [
            threading.Thread(target=reader, args=(i,)) for i in range(R)
        ]
        wt = threading.Thread(target=writer)
        log(
            f"[readscale] failover window: {R} bounded readers, leader "
            f"SIGKILL at t+{fail_s / 3:.1f}s of {fail_s:.1f}s"
        )
        for t in threads:
            t.start()
        wt.start()
        time.sleep(fail_s / 3)
        victim = plane.leader()
        t_kill = time.monotonic()
        victim.kill()
        plane.wait_for_leader(
            timeout_s=10 * ttl_s, exclude=victim.replica_id
        )
        time.sleep(max(0.0, fail_s - (time.monotonic() - t_kill)))
        stop_all.set()
        for t in threads:
            t.join(timeout=30)
        wt.join(timeout=30)
        if rerrs or werrs:
            raise SystemExit(
                f"[readscale] FAILOVER WINDOW FAILED: {(rerrs + werrs)[0]}"
            )
        done_ts.sort()
        gaps = [
            b - a for a, b in zip(done_ts, done_ts[1:])
            if b >= t_kill  # only gaps that could span the kill matter
        ]
        max_gap_s = max(gaps) if gaps else 0.0
        if max_gap_s > gap_gate_s:
            raise SystemExit(
                f"[readscale] READ GAP {max_gap_s:.2f}s ACROSS THE KILL "
                f"> {gap_gate_s}s — reads waited out the election "
                f"instead of riding the followers"
            )
        lats.sort()
        read_p99_s = _pct(lats, 0.99, 4)
        log(
            f"[readscale] {len(done_ts)} reads through the kill, max "
            f"gap {max_gap_s:.3f}s, p99 {read_p99_s}s"
        )
    finally:
        plane.stop()

    # ---- phase 2: encode-once on EVERY serving replica (in-process,
    # where the counters of all three stores share one registry) -------
    tmp2 = tempfile.mkdtemp(prefix="bench-readscale-inproc-")
    leader = DurableObjectStore(os.path.join(tmp2, "l.wal"), fsync=False)
    if leader.read_plane() is None:
        leader.close()
        bench_skip(
            "MINISCHED_COW_READS=0: readscale benches the COW read plane"
        )
    runtime = ReplRuntime(leader, "r0", peers=[], cluster_size=3)
    runtime.promote()
    _srv, lurl, lshutdown = start_api_server(leader, port=0, repl=runtime)
    followers = []
    for i in range(2):
        fid = f"r{i + 1}"
        fstore = DurableObjectStore(
            os.path.join(tmp2, f"{fid}.wal"), fsync=False
        )
        fstore.fence("r0")
        tail = WalFollower(fstore, lurl, fid)
        tail.start()
        _fs, furl, fshutdown = start_api_server(fstore, port=0)
        followers.append((fstore, tail, furl, fshutdown))
    try:
        for i in range(n_obj):
            leader.create("Pod", make_pod(f"enc-{i:04d}"))
        want = leader.resource_version
        deadline = time.monotonic() + 15.0
        while any(f[0].resource_version < want for f in followers):
            if time.monotonic() > deadline:
                raise SystemExit(
                    "[readscale] in-process followers never converged"
                )
            time.sleep(0.02)
        urls = [lurl] + [f[2] for f in followers]
        enc0 = counters.get("store.list_cache.encodes")
        req0 = counters.get("wire.relist_requests")
        per_url = 60

        def lister(u: str) -> None:
            for _ in range(per_url):
                with urllib.request.urlopen(f"{u}/api/v1/pods") as r:
                    r.read()

        lthreads = [
            threading.Thread(target=lister, args=(u,))
            for u in urls for _ in range(3)
        ]
        for t in lthreads:
            t.start()
        for t in lthreads:
            t.join(timeout=60)
        encodes = counters.get("store.list_cache.encodes") - enc0
        requests = counters.get("wire.relist_requests") - req0
        if requests < 3 * 3 * per_url:
            raise SystemExit(
                f"[readscale] encode-once storm too quiet: {requests} "
                f"list requests"
            )
        if not (3 <= encodes <= 6):
            raise SystemExit(
                f"[readscale] ENCODE-ONCE BROKEN ON A REPLICA: {encodes} "
                f"encodes for {requests} quiet lists across 3 façades "
                f"(want one per replica, ≤2 with benign races)"
            )
        log(
            f"[readscale] encode-once everywhere: {encodes} encodes for "
            f"{requests} lists across 3 serving replicas"
        )
    finally:
        for _fs, _tail, _furl, fshutdown in followers:
            fshutdown()
        lshutdown()
        for fstore, tail, _furl, _sd in followers:
            tail.stop()
        for fstore, tail, _furl, _sd in followers:
            tail.join(timeout=5.0)
            fstore.close()
        runtime.close()
        leader.close()

    return {
        "clients": W,
        "objects": n_obj,
        "window_s": window_s,
        "rate_1_replica_s": round(rate_1, 1),
        "rate_3_replicas_s": round(rate_3, 1),
        "read_scaling_x": round(scaling, 2),
        "scaling_gate_x": gate,
        "scaling_gated": scaling_gated,
        "cpu_cores": cores,
        "failover_reads": len(done_ts),
        "failover_read_p99_s": read_p99_s,
        "failover_max_gap_s": round(max_gap_s, 3),
        "gap_gate_s": gap_gate_s,
        "read_failovers": counters.get("remote.read_failover"),
        "not_yet_observed": counters.get("remote.not_yet_observed"),
        "leader_discoveries": counters.get("remote.leader_discoveries"),
        "encode_once_encodes": encodes,
        "encode_once_requests": requests,
    }


def bench_shard() -> dict:
    """``make bench-shard`` (DESIGN.md §30): the sharded write plane
    must BUY write throughput, not just partition it.  Opt-in via
    ``BENCH_SHARD=1``.  Two phases:

    * **1-vs-2-group write storm** — the same W (≥6) HTTP writer
      PROCESSES, each creating pods in its own namespace through the
      shard router, against a K=1 plane and then a K=2 plane (same
      replica count per group, same fsync floor).  Namespaces are
      pre-picked to land half on each K=2 group, so the K=2 run splits
      the identical load across two independent group-commit barriers.
      The fsync floor (``BENCH_SHARD_FSYNC_FLOOR_US``, default 2000µs)
      makes the durability barrier cost something real — on tmpfs an
      fsync is near-free and no amount of sharding shows.  Gate: K=2
      rate ≥ BENCH_SHARD_GATE × K=1 rate (default 1.5×), armed only on
      ≥4 cores (readscale precedent: on fewer cores every server
      process shares the silicon and wall-clock scaling is pinned at
      ~1× regardless of architecture); always measured and recorded.
    * **cross-shard batch tax** — on the K=2 plane: p50/p99 latency of
      single-group bind batches vs batches spanning both groups (the
      two-shard commit pays two HTTP round trips + two barriers in
      parallel).  Informational, recorded separately — the tax is the
      price of exactly-once across groups, not a regression.
    * **skewed-load autosplit** (DESIGN.md §31) — every writer hammers
      one g0-owned namespace on a fresh K=2 plane with the in-process
      load watcher armed (low thresholds via ``BENCH_AUTOSPLIT_P99_S``).
      Gates: the watcher splits the hot namespace to g1 within
      ``BENCH_AUTOSPLIT_DEADLINE_S`` (default 60s) with
      ``shard.autosplit.triggered`` counted, AND the source group's
      windowed ``storage.group_wait_s`` p99 — computed from cumulative
      /metrics bucket deltas — recovers after the flip.
    """
    import tempfile
    import threading

    from minisched_tpu.api.objects import Binding, make_node, make_pod
    from minisched_tpu.controlplane.shards import ShardedPlane, ShardTopology
    from minisched_tpu.observability import counters

    if os.environ.get("BENCH_SHARD", "0") == "0":
        bench_skip("BENCH_SHARD unset: sharded write plane role is opt-in")

    W = max(int(os.environ.get("BENCH_SHARD_WRITERS", "6")), 6)
    window_s = float(os.environ.get("BENCH_SHARD_WINDOW_S", "2.0"))
    gate = float(os.environ.get("BENCH_SHARD_GATE", "1.5"))
    floor_us = os.environ.get("BENCH_SHARD_FSYNC_FLOOR_US", "2000")
    batches = int(os.environ.get("BENCH_SHARD_BIND_BATCHES", "30"))
    ttl_s = 1.0

    counters.reset()
    tmp = tempfile.mkdtemp(prefix="bench-shard-")

    # writer namespaces balanced across the K=2 topology up front, so
    # both runs carry the identical client load and only the group
    # count differs
    probe = ShardTopology({"g0": ["http://a"], "g1": ["http://b"]})
    per_group: dict = {"g0": [], "g1": []}
    i = 0
    while any(len(v) < (W + 1) // 2 for v in per_group.values()):
        ns = f"bench-ns-{i:03d}"
        per_group[probe.owner(ns)].append(ns)
        i += 1
    writer_ns = [
        per_group[gid][j]
        for j in range((W + 1) // 2)
        for gid in ("g0", "g1")
    ][:W]

    helper = os.path.join(tmp, "_write_storm.py")
    repo_dir = os.path.dirname(os.path.abspath(__file__))
    with open(helper, "w") as f:
        f.write(
            "import sys, time\n"
            f"sys.path.insert(0, {repo_dir!r})\n"
            "from minisched_tpu.api.objects import make_pod\n"
            "from minisched_tpu.controlplane.shards import ShardedStore\n"
            "seed, ns, window_s = sys.argv[1], sys.argv[2], "
            "float(sys.argv[3])\n"
            "ss = ShardedStore(seeds=[seed], timeout_s=10.0, retries=2)\n"
            "n = 0\n"
            "deadline = time.monotonic() + window_s\n"
            "try:\n"
            "    while time.monotonic() < deadline:\n"
            "        ss.create('Pod', make_pod('%s-%06d' % (ns, n), "
            "namespace=ns))\n"
            "        n += 1\n"
            "finally:\n"
            "    ss.close()\n"
            "print(n)\n"
        )

    def storm(seed_url: str, label: str) -> float:
        procs = [
            subprocess.Popen(
                [sys.executable, helper, seed_url, writer_ns[w],
                 str(window_s)],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            )
            for w in range(W)
        ]
        total = 0
        for p in procs:
            out, err = p.communicate(timeout=window_s + 120)
            if p.returncode != 0:
                raise SystemExit(
                    f"[shard] {label} WRITER FAILED: "
                    f"{err.decode(errors='replace')[-300:]}"
                )
            total += int(out.strip())
        rate = total / window_s
        log(f"[shard] {label}: {rate:.0f} creates/s ({W} writer procs)")
        return rate

    old_floor = os.environ.get("MINISCHED_FSYNC_FLOOR_US")
    os.environ["MINISCHED_FSYNC_FLOOR_US"] = floor_us
    try:
        rates = {}
        for k in (1, 2):
            plane = ShardedPlane(
                os.path.join(tmp, f"k{k}"), k=k, replicas_per_group=1,
                fsync=True, ttl_s=ttl_s,
            )
            try:
                seeds = plane.start()
                rates[k] = storm(seeds[0], f"K={k} write storm")
            finally:
                plane.stop()

        scaling = rates[2] / rates[1] if rates[1] else 0.0
        cores = os.cpu_count() or 1
        scaling_gated = cores >= 4
        if scaling_gated and scaling < gate:
            raise SystemExit(
                f"[shard] WRITE SCALING UNDER GATE: {rates[2]:.0f}/s on 2 "
                f"groups vs {rates[1]:.0f}/s on 1 = {scaling:.2f}x < "
                f"{gate}x — a second leader group is not buying write "
                f"throughput"
            )
        if not scaling_gated:
            log(
                f"[shard] scaling gate SKIPPED: {cores} CPU core(s) — "
                f"groups share the silicon (measured {scaling:.2f}x, "
                f"recorded informationally; gate re-arms on >=4 cores)"
            )
        else:
            log(f"[shard] write scaling 1->2 groups: {scaling:.2f}x")

        # ---- cross-shard batch tax (K=2, measured separately) ---------
        plane = ShardedPlane(
            os.path.join(tmp, "tax"), k=2, replicas_per_group=1,
            fsync=True, ttl_s=ttl_s,
        )
        try:
            plane.start()
            ss = plane.client(timeout_s=10.0, retries=2)
            # placement hashes only group ids, so the probe buckets hold
            ns0, ns1 = per_group["g0"][0], per_group["g1"][0]
            ss.create("Node", make_node("bn1", capacity={
                "cpu": "64", "memory": "256Gi", "pods": 8 * batches,
            }))
            for b in range(batches):
                ss.create("Pod", make_pod(f"s{b:03d}", namespace=ns0))
                ss.create("Pod", make_pod(f"t{b:03d}", namespace=ns0))
                ss.create("Pod", make_pod(f"x{b:03d}", namespace=ns0))
                ss.create("Pod", make_pod(f"y{b:03d}", namespace=ns1))
            single_lat, cross_lat = [], []
            for b in range(batches):
                t0 = time.monotonic()
                res = ss.bind_many_remote(
                    [Binding(pod_name=f"s{b:03d}", pod_namespace=ns0,
                             node_name="bn1"),
                     Binding(pod_name=f"t{b:03d}", pod_namespace=ns0,
                             node_name="bn1")],
                    return_objects=False,
                )
                single_lat.append(time.monotonic() - t0)
                if any(isinstance(r, BaseException) for r in res):
                    raise SystemExit(f"[shard] single-group bind: {res}")
                t0 = time.monotonic()
                res = ss.bind_many_remote(
                    [Binding(pod_name=f"x{b:03d}", pod_namespace=ns0,
                             node_name="bn1"),
                     Binding(pod_name=f"y{b:03d}", pod_namespace=ns1,
                             node_name="bn1")],
                    return_objects=False,
                )
                cross_lat.append(time.monotonic() - t0)
                if any(isinstance(r, BaseException) for r in res):
                    raise SystemExit(f"[shard] cross-shard bind: {res}")
            ss.close()
        finally:
            plane.stop()
        single_lat.sort()
        cross_lat.sort()
        single_p50 = _pct(single_lat, 0.50, 4)
        cross_p50 = _pct(cross_lat, 0.50, 4)
        tax = cross_p50 / single_p50 if single_p50 else 0.0
        log(
            f"[shard] cross-shard batch tax: single p50 {single_p50}s vs "
            f"cross p50 {cross_p50}s = {tax:.2f}x"
        )

        # ---- skewed-load autosplit phase (DESIGN.md §31 leg 2) --------
        # every writer hammers ONE g0-owned namespace; the per-group
        # load watcher inside g0's replica must notice the saturated
        # group-commit barrier and split the hot namespace to g1 with
        # no operator in the loop.  Two gates: the split FIRES within
        # the deadline, and the source group's windowed group_wait p99
        # RECOVERS once the load has moved.
        import urllib.request as _urlreq

        auto_env = {
            "MINISCHED_AUTOSPLIT": "1",
            "MINISCHED_AUTOSPLIT_P99_S": os.environ.get(
                "BENCH_AUTOSPLIT_P99_S", "0.004"
            ),
            "MINISCHED_AUTOSPLIT_HOT": "2",
            "MINISCHED_AUTOSPLIT_INTERVAL_S": "0.25",
            "MINISCHED_AUTOSPLIT_COOLDOWN_S": "3600",
        }
        saved_env = {k: os.environ.get(k) for k in auto_env}
        os.environ.update(auto_env)

        def _scrape_wait(base: str):
            """(cumulative group_wait buckets {le: count}, autosplit
            trigger count) off one replica's /metrics exposition."""
            with _urlreq.urlopen(base + "/metrics", timeout=5.0) as r:
                text = r.read().decode()
            buckets: dict = {}
            fired = 0
            for line in text.splitlines():
                if line.startswith("storage_group_wait_seconds_bucket"):
                    le_s = line.split('le="', 1)[1].split('"', 1)[0]
                    le = float("inf") if le_s == "+Inf" else float(le_s)
                    val = line.split("} ", 1)[1].split(" #", 1)[0]
                    buckets[le] = buckets.get(le, 0) + int(float(val))
                elif line.startswith("shard_autosplit_triggered "):
                    fired = int(float(line.split()[1]))
            return buckets, fired

        def _window_p99(before: dict, after: dict) -> float:
            """Nearest-rank p99 of the observations BETWEEN two scrapes
            (cumulative-bucket deltas); 0.0 for an empty window."""
            bounds = sorted(set(before) | set(after))
            delta = {
                le: after.get(le, 0) - before.get(le, 0) for le in bounds
            }
            n = delta.get(float("inf"), 0)
            if n <= 0:
                return 0.0
            rank = max(1, int(n * 0.99 + 0.999999))
            # buckets are cumulative per scrape, so the delta at each le
            # is already cumulative across the window
            for le in bounds:
                if delta[le] >= rank:
                    return le
            return float("inf")

        split_deadline_s = float(
            os.environ.get("BENCH_AUTOSPLIT_DEADLINE_S", "60")
        )
        post_window_s = float(
            os.environ.get("BENCH_AUTOSPLIT_POST_WINDOW_S", "3.0")
        )
        plane = ShardedPlane(
            os.path.join(tmp, "auto"), k=2, replicas_per_group=1,
            fsync=True, ttl_s=ttl_s,
        )
        try:
            plane.start()
            hot_ns = per_group["g0"][0]
            g0_url = plane.groups["g0"].replicas[0].base_url
            stop_evt = threading.Event()
            write_errors: list = []

            def skew_writer(widx: int) -> None:
                ss = plane.client(timeout_s=10.0, retries=4)
                i = 0
                try:
                    while not stop_evt.is_set():
                        try:
                            ss.create("Pod", make_pod(
                                f"skew-{widx}-{i:05d}", namespace=hot_ns,
                            ))
                            i += 1
                        except Exception as e:  # noqa: BLE001
                            write_errors.append(repr(e))
                            time.sleep(0.1)
                finally:
                    ss.close()

            writers = [
                threading.Thread(target=skew_writer, args=(w,), daemon=True)
                for w in range(W)
            ]
            for t in writers:
                t.start()
            s0, _ = _scrape_wait(g0_url)
            t0 = time.monotonic()
            fired_at = None
            while time.monotonic() - t0 < split_deadline_s:
                try:
                    with _urlreq.urlopen(
                        g0_url + "/shards/status", timeout=5.0
                    ) as r:
                        doc = json.loads(r.read())
                except OSError:
                    time.sleep(0.25)
                    continue
                if doc["topology"].get("overrides", {}).get(hot_ns) \
                        == "g1":
                    fired_at = time.monotonic() - t0
                    break
                time.sleep(0.25)
            s1, fired_count = _scrape_wait(g0_url)
            if fired_at is None:
                stop_evt.set()
                raise SystemExit(
                    f"[shard] AUTOSPLIT NEVER FIRED within "
                    f"{split_deadline_s}s (hot p99 threshold "
                    f"{auto_env['MINISCHED_AUTOSPLIT_P99_S']}s, "
                    f"writer errors {len(write_errors)})"
                )
            pre_p99 = _window_p99(s0, s1)
            # the override flips BEFORE the watcher's trigger counter
            # bumps (the split's purge still runs) — give the counter a
            # moment instead of racing it
            cdl = time.monotonic() + 10.0
            while fired_count < 1 and time.monotonic() < cdl:
                time.sleep(0.25)
                _b, fired_count = _scrape_wait(g0_url)
            time.sleep(1.5)  # purge tail + frozen retries chase over
            s2, _ = _scrape_wait(g0_url)
            time.sleep(post_window_s)
            s3, _ = _scrape_wait(g0_url)
            post_p99 = _window_p99(s2, s3)
            stop_evt.set()
            for t in writers:
                t.join(timeout=30.0)
            log(
                f"[shard] autosplit fired after {fired_at:.1f}s "
                f"(trigger count {fired_count}); source group_wait p99 "
                f"{pre_p99:.4f}s before -> {post_p99:.4f}s after"
            )
            if fired_count < 1:
                raise SystemExit(
                    "[shard] override flipped but shard.autosplit."
                    "triggered never counted — split did not come from "
                    "the watcher"
                )
            recovered = post_p99 < pre_p99 or post_p99 == 0.0
            if scaling_gated and not recovered:
                # same arming rule as the write-scaling gate: on <4
                # cores the moved load still shares the silicon with
                # the source group, so recovery is recorded but not
                # gated
                raise SystemExit(
                    f"[shard] GROUP WAIT DID NOT RECOVER: p99 "
                    f"{pre_p99:.4f}s before the split vs "
                    f"{post_p99:.4f}s after — moving the hot namespace "
                    f"bought nothing"
                )
            if not scaling_gated and not recovered:
                log(
                    f"[shard] recovery gate SKIPPED: {cores} CPU "
                    f"core(s) — recorded informationally"
                )
        finally:
            for k, v in saved_env.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            plane.stop()
    finally:
        if old_floor is None:
            os.environ.pop("MINISCHED_FSYNC_FLOOR_US", None)
        else:
            os.environ["MINISCHED_FSYNC_FLOOR_US"] = old_floor

    return {
        "writers": W,
        "window_s": window_s,
        "fsync_floor_us": float(floor_us),
        "rate_1_group_s": round(rates[1], 1),
        "rate_2_groups_s": round(rates[2], 1),
        "write_scaling_x": round(scaling, 2),
        "scaling_gate_x": gate,
        "scaling_gated": scaling_gated,
        "cpu_cores": cores,
        "bind_batches": batches,
        "single_group_bind_p50_s": single_p50,
        "single_group_bind_p99_s": _pct(single_lat, 0.99, 4),
        "cross_shard_bind_p50_s": cross_p50,
        "cross_shard_bind_p99_s": _pct(cross_lat, 0.99, 4),
        "cross_shard_tax_x": round(tax, 2),
        "cross_bind_batches": counters.get("shard.cross_bind_batches"),
        "wrong_shard_chased": counters.get("shard.wrong_shard_chased"),
        "autosplit_fired_after_s": round(fired_at, 2),
        "autosplit_trigger_count": fired_count,
        "autosplit_pre_p99_s": round(pre_p99, 4),
        "autosplit_post_p99_s": round(post_p99, 4),
    }


ROLES = {
    "headline": bench_headline,
    "c5": bench_config5_fullchain,
    "fullchain_parity": bench_fullchain_parity,
    "wire": bench_wire,
    "wirefan": bench_wire_fanout,
    "wave": bench_wave_pipeline,
    "mesh": bench_mesh,
    "chaos": bench_chaos,
    "disk": bench_disk,
    "wal": bench_wal,
    "repl": bench_repl,
    "ha": bench_ha,
    "gang": bench_gang,
    "churn": bench_churn,
    "relist": bench_relist,
    "readscale": bench_readscale,
    "shard": bench_shard,
    "c1": bench_config1,
    "c2": bench_config2,
    "c3": bench_config3,
    "c4": bench_config4,
}


def _run_child(role: str, extra_env: dict = None, label: str = None) -> dict:
    """One config in its own process (fresh backend; the persistent
    compile cache makes re-init cheap).  Returns the child's JSON dict.
    ``label`` names the run in logs when one role serves two configs.

    The child's stderr is TEED: streamed through live (the logs stay
    watchable) while the last ~120 lines are retained, so a failure
    raises BenchChildError carrying the tail — a bare ``exited rc=1``
    told BENCH_r05 readers nothing about c3/c5x/fullchain_parity."""
    import threading
    from collections import deque

    label = label or role
    t0 = time.monotonic()
    env = dict(os.environ)
    env.update(extra_env or {})
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--only", role],
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        cwd=os.path.dirname(os.path.abspath(__file__)),
        env=env,
    )
    tail: deque = deque(maxlen=120)

    def _tee() -> None:
        for raw in proc.stderr:
            line = raw.decode(errors="replace")
            sys.stderr.write(line)
            sys.stderr.flush()
            tail.append(line)

    tee = threading.Thread(target=_tee, name=f"bench-tee-{label}", daemon=True)
    tee.start()
    stdout = proc.stdout.read()
    proc.wait()
    tee.join(timeout=5.0)
    tail_text = "".join(tail)
    if proc.returncode != 0:
        raise BenchChildError(
            f"bench child {label!r} exited rc={proc.returncode}", tail_text
        )
    lines = [l for l in stdout.decode().splitlines() if l.strip()]
    if not lines:
        raise BenchChildError(
            f"bench child {label!r} produced no JSON", tail_text
        )
    out = json.loads(lines[-1])
    if isinstance(out, dict) and out.get("skipped"):
        log(f"[bench] {label} SKIPPED: {out['skipped']}")
    else:
        log(f"[bench] {label} done in {time.monotonic()-t0:.0f}s")
    return out


def main() -> None:
    if len(sys.argv) > 2 and sys.argv[1] == "--only":
        from minisched_tpu.utils.compilecache import enable_persistent_cache

        cache_dir = enable_persistent_cache()
        import jax

        log(f"[{sys.argv[2]}] devices: {jax.devices()} (cache: {cache_dir})")
        try:
            result = ROLES[sys.argv[2]]()
        except SystemExit as err:
            msg = str(err)
            if msg.startswith("BENCH_SKIP:"):
                # the role opted out (bench_skip) — a structured skip
                # record, not a failure (rc stays 0)
                print(
                    json.dumps(
                        {"skipped": msg[len("BENCH_SKIP:"):].strip()}
                    ),
                    flush=True,
                )
                return
            raise
        print(json.dumps(result), flush=True)
        return

    record = _run_child("headline")  # a headline failure fails the bench
    optional = []  # (record field, cli role, extra env, label)
    if os.environ.get("BENCH_C5", "1") != "0":
        optional.append(("config5_full_chain", "c5", None, "c5"))
    if os.environ.get("BENCH_C5X", "1") != "0":
        # config5 with 5% topology-spread-constrained pods: the live
        # engine routes them through the bind-exact sequential scan,
        # interleaved with the plain repair waves, and the run ends with
        # a hard max-skew audit.  A malformed BENCH_C5_PODS must not
        # crash main() before the headline record prints.
        try:
            crosspod = str(int(os.environ.get("BENCH_C5_PODS", 100_000)) // 20)
        except ValueError as err:
            log(f"[bench] c5x skipped: bad BENCH_C5_PODS ({err})")
        else:
            optional.append(
                ("config5_crosspod", "c5", {"BENCH_C5_CROSSPOD": crosspod}, "c5x")
            )
    if os.environ.get("BENCH_FULLCHAIN_PARITY", "1") != "0":
        optional.append(
            ("fullchain_parity", "fullchain_parity", None, "fullchain_parity")
        )
    if os.environ.get("BENCH_WIRE", "1") != "0":
        optional.append(("scheduler_over_http", "wire", None, "wire"))
        # cross-pod pods over the wire (VERDICT r4 item 5): the deferral +
        # blocked-scan lane behind the serialization boundary, with the
        # max-skew audit read back through REST
        optional.append(
            (
                "scheduler_over_http_crosspod",
                "wire",
                # overridable so CPU re-earn runs can scale the scan-lane
                # load down with the rest of the knobs
                {
                    "BENCH_WIRE_CROSSPOD": os.environ.get(
                        "BENCH_WIRE_CROSSPOD", "5000"
                    )
                },
                "wire-crosspod",
            )
        )
        # 1k-watcher wire fanout (ISSUE 9): selector stream loop at real
        # HTTP scale — thread-count / encode-once / eviction-resume
        # gates + the p99 delivery-latency headline
        optional.append(("wire_fanout", "wirefan", None, "wirefan"))
    if os.environ.get("BENCH_CHAOS", "1") != "0":
        # degraded-mode soak: convergence + leak/double-bind audits under
        # a seeded fault schedule (BENCH_CHAOS_SEED reproduces it)
        optional.append(("chaos_soak", "chaos", None, "chaos"))
    if os.environ.get("BENCH_DISK", "1") != "0":
        # lying-disk soak: degraded-mode dwell, scrub/fsck detection of
        # injected corruption, and the exactly-once audit in the record
        optional.append(("disk_integrity", "disk", None, "disk"))
    if os.environ.get("BENCH_HA", "1") != "0":
        # HA plane: sharded active-active engines, one hard kill, with
        # TTL-bounded rebalance + exactly-once audits in the record
        optional.append(("ha_plane", "ha", None, "ha"))
    if os.environ.get("BENCH_REPL", "0") != "0":
        # replicated plane (ISSUE 15, opt-in): quorum-ack WAL shipping —
        # mutate p50/p99 tax vs the MINISCHED_REPL=0 kill-switch, plus
        # zero-acked-loss + byte-identical-follower audits
        optional.append(("repl_plane", "repl", None, "repl"))
    if os.environ.get("BENCH_READSCALE", "0") != "0":
        # follower-serving read plane (ISSUE 17, opt-in): 1->3 replica
        # list-rate scaling gate, encode-once on every serving replica,
        # and read availability across a leader SIGKILL
        optional.append(("read_scaling", "readscale", None, "readscale"))
    if os.environ.get("BENCH_SHARD", "0") != "0":
        # sharded write plane (ISSUE 18, opt-in): 1-vs-2-group write
        # throughput under an fsync floor (gate arms on >=4 cores), plus
        # the cross-shard bind batch tax measured separately
        optional.append(("shard_plane", "shard", None, "shard"))
    if os.environ.get("BENCH_MESH", "1") != "0":
        # multi-chip live wave engine (ISSUE 7): sharded vs single-device
        # on the same workload, parity-pinned, device_total_s gated.
        # BENCH_MESH_FORCE_HOST=1 (default) forces an 8-virtual-device
        # CPU mesh so the child runs anywhere; TPU re-earn boxes set 0 to
        # shard over the real chips.
        mesh_env = {"MINISCHED_PIPELINE": "1"}
        if os.environ.get("BENCH_MESH_FORCE_HOST", "1") != "0":
            mesh_env["JAX_PLATFORMS"] = "cpu"
            mesh_env["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8"
            ).strip()
        optional.append(("wave_mesh", "mesh", mesh_env, "mesh"))
    if os.environ.get("BENCH_GANG", "1") != "0":
        # gang churn: mixed gang+singleton rounds + a two-gang deadlock
        # probe, audited for zero stranded partial gangs and
        # deadlock-freedom (ISSUE 6)
        optional.append(("gang_churn", "gang", None, "gang"))
    if os.environ.get("BENCH_CHURN", "1") != "0":
        # sustained-churn serving (ISSUE 8): Poisson arrivals/departures +
        # priority-preemption bursts, p99 time-to-bind headline, idle-wave
        # gate + shared-fanout + quota audits
        optional.append(("churn_serving", "churn", None, "churn"))
    if os.environ.get("BENCH_RELIST", "1") != "0":
        # relist storm (ISSUE 14): 410 mass-eviction + cold-boot list
        # storms off the COW read plane — encode-once, p99 list latency,
        # zero write stalls, kill-switch byte parity
        optional.append(("relist_storm", "relist", None, "relist"))
    if os.environ.get("BENCH_SECONDARY", "1") != "0":
        optional += [
            ("config1", "c1", None, "c1"), ("config2", "c2", None, "c2"),
            ("config3", "c3", None, "c3"), ("config4", "c4", None, "c4"),
        ]
    for field, role, extra_env, label in optional:
        # an optional config's crash must not discard the headline record
        try:
            record[field] = _run_child(role, extra_env=extra_env, label=label)
        except BaseException as err:
            tail = getattr(err, "stderr_tail", "")
            skip = _skip_reason(tail)
            if skip:
                # a capability gap (needs a real TPU), not a regression —
                # recorded as skipped so the re-earn status stays legible
                log(f"[bench] {label} SKIPPED: {skip}")
                record[field] = {"skipped": skip}
                continue
            log(f"[bench] {label} FAILED: {err!r}")
            rec = {"error": str(err)}
            if tail:
                rec["stderr_tail"] = tail[-2000:]
            record[field] = rec
    print(json.dumps(record), flush=True)


if __name__ == "__main__":
    main()
