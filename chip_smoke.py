#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path starts on the chip.

One process — the one that holds the chip — boots the stack the way
``python -m minisched_tpu`` does (``minisched_tpu.__main__.start``: store →
REST façade → informers → queue → pipelined wave build → device evaluate →
commit → bind ack; full default roster, the engine defaults ``start()``
gives), and drives it ONLY over loopback HTTP with
``controlplane.remote.RemoteClient``:

* 5,000 nodes (the Kubernetes scalability envelope and upstream
  ``scheduler_perf``'s 5000Nodes size: 8 cpu / 16Gi / 110 pods, 16 zones,
  20 % unschedulable) and 10,000 pods (``SchedulingBasic/
  5000Nodes_10000Pods``) generated from ``--seed``: 8,880 plain
  ``500m/256Mi`` pods, a minority with a node selector or a toleration so
  both packed pod schemas compile; then 1,100 ``DoNotSchedule`` topology-
  spread pods in ONE burst (> SCAN_BLOCK_SIZE: the blocked scan lane; >
  SCAN_MAX_CHUNK, so more than one narrow call holds and the grouping
  keeps its layout by fill), the first half spread over 32 services,
  whose blocks fill (the wide layout and its P=32 Pallas step), the
  second half all of one service, whose blocks hold one pod each (the
  narrow layout, a pod a row: P=1 and the XLA tail); then 20 more (≤ 32:
  the exact P=1 lane and the XLA tail).
  All four device programs the engine can dispatch compile and run inside
  the real loop.
* The safety audit reads back through REST; parks, dispatch heals, the
  device and the wave outputs' placement are read off ``/metrics`` and
  ``/debug/trace``.
* The Pallas kernels are checked directly against the XLA tail at every
  tiling the engine can reach, and the lowered text of the live wave and
  blocked-scan programs must contain the Mosaic custom call.

It passes only on a TPU: anywhere else it fails at the first check and says
what it saw.  It sets no JAX_PLATFORMS, no MINISCHED_TPU_PALLAS and no
MINISCHED_MESH; with more than one chip visible it runs the same drain
however ``start()`` resolves the mesh and additionally requires the mesh
path to have carried the waves.  Any failed phase is a non-zero exit with
the phase named; the last stdout line of a pass is one JSON object.
Timings printed along the way are information, not results.

    python3 chip_smoke.py [--seed N]
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import random
import sys
import time
import urllib.request
from dataclasses import dataclass

N_ZONES = 16
N_APPS = 32
MAX_SKEW = 1
NODE_PODS = 110


@dataclass(frozen=True)
class Sizes:
    nodes: int
    plain: int  # plain pods (a minority carry a selector or a toleration)
    burst: int  # spread pods sent in one burst (> SCAN_BLOCK_SIZE; wide
    # and narrow only where > SCAN_MAX_CHUNK, else one narrow call)
    tail: int  # spread pods sent after it (<= SCAN_BLOCK_SIZE)

    @property
    def pods(self) -> int:
        return self.plain + self.burst + self.tail


FULL = Sizes(nodes=5000, plain=8880, burst=1100, tail=20)


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


@contextlib.contextmanager
def phase(name: str):
    """Name the phase on the way in and, if it raises, on the way out —
    the exception still ends the run."""
    t0 = time.monotonic()
    say(f"phase {name} ...")
    try:
        yield
    except BaseException as err:
        print(
            f"[chip_smoke] FAILED phase={name}: {type(err).__name__}: {err}",
            file=sys.stderr,
            flush=True,
        )
        raise
    say(f"phase {name} ok ({time.monotonic() - t0:.1f}s)")


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# -- phase: device -----------------------------------------------------------


def check_device() -> dict:
    """Fail unless JAX's default device is a TPU; says what it saw."""
    import importlib.metadata as md

    import jax
    import jaxlib

    seen = f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}"
    devices = jax.devices()
    dev = devices[0]
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    say(
        f"platform={dev.platform} device_kind={dev.device_kind!r} "
        f"devices={len(devices)} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu} {seen}"
    )
    require(
        dev.platform == "tpu",
        f"no TPU: {seen}, jax.devices()={devices!r}",
    )
    return {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
    }


# -- phase: kernels ----------------------------------------------------------


def _seed_for_max_hash(idx: int) -> int:
    """The tie-break seed under which node ``idx`` hashes to 0xFFFFFFFF —
    the value both select_hosts forms also use as their "not a candidate"
    sentinel, so a real candidate ties with every non-candidate."""
    from minisched_tpu.engine.tiebreak import mix32

    m = 1 << 32
    x = 0xFFFFFFFF
    x ^= x >> 16
    x = x * pow(0xC2B2AE35, -1, m) % m
    x ^= (x >> 13) ^ (x >> 26)
    x = x * pow(0x85EBCA6B, -1, m) % m
    x ^= x >> 16
    seed = x ^ (idx * 0x9E3779B9 % m)
    require(mix32(seed, idx) == 0xFFFFFFFF, "mix32 inversion is wrong")
    return seed


def _kernel_case(P: int, N: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    scores = rng.choice(np.array([0, 10], np.int32), size=(P, N))
    mask = rng.random((P, N)) < 0.6
    seeds = rng.integers(0, 1 << 32, size=P, dtype=np.uint32)
    mask[0, :] = False  # no feasible node at all
    scores[1, :], mask[1, :] = 7, True  # every node ties: the hash decides
    # forced hash tie: the only top-score candidate sits in the LAST node
    # tile and hashes to the sentinel every non-candidate carries
    scores[2, :], mask[2, :] = 0, True
    scores[2, N - 1] = 10
    seeds[2] = _seed_for_max_hash(N - 1)
    return scores, mask, seeds


def check_kernels(seed: int) -> None:
    """Mosaic-compiled kernels against the XLA tail, bit for bit, at every
    tiling the engine can reach."""
    import jax
    import numpy as np

    from minisched_tpu.ops import fused
    from minisched_tpu.ops.pallas_kernels import (
        _tiling,
        nodenumber_select_hosts,
        select_hosts_pallas,
    )

    def xla_tail(scores, mask, seeds):
        # the route is chosen at trace time; a fresh lambda is a fresh trace
        was = fused._USE_PALLAS
        fused.set_pallas(False)
        try:
            return jax.jit(lambda *a: fused.select_hosts(*a))(
                scores, mask, seeds
            )
        finally:
            fused.set_pallas(was)

    # wave (1024 pods x 5,000 nodes padded to 5,120), blocked-scan step
    # (32 pods), and a cluster whose node capacity divides NODE_TILE
    for (P, N), tiles in (
        ((1024, 5120), (128, 128)),
        ((32, 5120), (8, 128)),
        ((1024, 8192), (128, 2048)),
    ):
        require(_tiling(P, N)[:2] == tiles, f"{(P, N)} tiles {_tiling(P, N)}")
        require(fused._pallas_shape_ok(P, N), f"{(P, N)} not routed to Pallas")
        scores, mask, seeds = _kernel_case(P, N, seed + P + N)
        t0 = time.monotonic()
        got = jax.device_get(select_hosts_pallas(scores, mask, seeds))
        dt = time.monotonic() - t0
        want = jax.device_get(xla_tail(scores, mask, seeds))
        for g, w, what in zip(got, want, ("choice", "best_score")):
            require(
                np.array_equal(g, w),
                f"select_hosts_pallas{(P, N)} {what} differs from the XLA "
                f"tail in {int((g != w).sum())} rows",
            )
        require(got[0][0] == -1, "all-infeasible row must choose -1")
        require(got[0][2] == N - 1, "forced hash tie must keep the candidate")
        say(
            f"select_hosts_pallas {(P, N)} tiles {tiles}: bit-equal to the "
            f"XLA tail (cold compile+run {dt:.2f}s)"
        )
    # the exact scan lane evaluates one pod per step: that shape must take
    # the XLA tail by the shape rule, not by a caught exception
    require(not fused._pallas_shape_ok(1, 5120), "P=1 must take the XLA tail")

    # nodenumber_select_hosts (no caller on the served path; ROADMAP D8 decides its fate)
    from minisched_tpu.api.objects import Toleration, make_node, make_pod
    from minisched_tpu.models.tables import build_node_table, build_pod_table
    from minisched_tpu.plugins.nodenumber import NodeNumber
    from minisched_tpu.plugins.nodeunschedulable import NodeUnschedulable

    rng = random.Random(seed)
    nodes = [
        make_node(f"node{i:04d}", unschedulable=rng.random() < 0.4)
        for i in range(4096)
    ]
    tol = Toleration(
        key="node.kubernetes.io/unschedulable", operator="Exists",
        effect="NoSchedule",
    )
    pods = [
        make_pod(f"pod{i}", tolerations=[tol] if rng.random() < 0.3 else [])
        for i in range(1024)
    ]
    node_table, _ = build_node_table(nodes)
    pod_table, _ = build_pod_table(pods)
    nn = NodeNumber()
    ref = fused.FusedEvaluator([NodeUnschedulable()], [nn], [nn])(
        pod_table, node_table
    )
    got = jax.device_get(nodenumber_select_hosts(pod_table, node_table))
    want = jax.device_get((ref.choice, ref.best_score))
    require(
        np.array_equal(got[0], want[0]) and np.array_equal(got[1], want[1]),
        "nodenumber_select_hosts (1024, 4096) differs from FusedEvaluator",
    )
    say("nodenumber_select_hosts (1024, 4096): bit-equal to FusedEvaluator")


# -- phase: serve ------------------------------------------------------------


@contextlib.contextmanager
def booted_stack():
    """(base_url, SchedulerService) of a stack booted by the function
    ``python -m minisched_tpu`` runs, with the device engine on."""
    from minisched_tpu.__main__ import start
    from minisched_tpu.service.config import ProcessConfig

    _client, base, stop = start(
        ProcessConfig(port=0, frontend_url="http://localhost:3000"),
        device_mode=True,
    )
    try:
        yield base, stop.service
    finally:
        stop()


def _scrape(base: str):
    """(/metrics samples as {name: value}, /debug/trace spans)."""
    from minisched_tpu.observability.hist import parse_prometheus

    with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
        _types, samples = parse_prometheus(r.read().decode())
    with urllib.request.urlopen(base + "/debug/trace", timeout=30) as r:
        spans = [json.loads(line) for line in r.read().decode().splitlines()]
    return {name: val for name, labels, val in samples if not labels}, spans


def simple_head(sizes: Sizes) -> int:
    """Leading plain pods with no selector or toleration: two full waves'
    worth when the backlog allows, so whole waves take the fast schema."""
    return min(2048, sizes.plain // 2)


def _make_cluster(sizes: Sizes, seed: int):
    from minisched_tpu.api.objects import (
        LabelSelector,
        Toleration,
        TopologySpreadConstraint,
        make_node,
        make_pod,
    )

    rng = random.Random(seed)
    nodes = [
        make_node(
            f"node{i:05d}",
            unschedulable=rng.random() < 0.2,
            capacity={"cpu": "8", "memory": "16Gi", "pods": NODE_PODS},
            labels={"zone": f"z{i % N_ZONES}"},
        )
        for i in range(sizes.nodes)
    ]
    requests = {"cpu": "500m", "memory": "256Mi"}
    plain = []
    for i in range(sizes.plain):
        spec = {}
        # the head stays simple (the fast packed pod schema); after it every
        # 10th pod is not (the full schema)
        if i >= simple_head(sizes):
            if i % 20 == 3:
                spec["node_selector"] = {
                    "zone": f"z{rng.randrange(N_ZONES)}"
                }
            elif i % 20 == 13:
                spec["tolerations"] = [
                    Toleration(key="dedicated", operator="Exists")
                ]
        plain.append(make_pod(f"pod{i:05d}", requests=requests, **spec))

    def spread(i: int):
        # the burst's first half goes round the services, so its blocks
        # fill; from there on every pod is the last service's, a block each
        app = f"app{i % N_APPS if i < sizes.burst // 2 else N_APPS - 1}"
        pod = make_pod(f"spread{i:04d}", requests=requests, labels={"app": app})
        pod.spec.topology_spread_constraints = [
            TopologySpreadConstraint(
                max_skew=MAX_SKEW,
                topology_key="zone",
                when_unsatisfiable="DoNotSchedule",
                label_selector=LabelSelector(match_labels={"app": app}),
            )
        ]
        return pod

    burst = [spread(i) for i in range(sizes.burst)]
    tail = [spread(sizes.burst + i) for i in range(sizes.tail)]
    return nodes, plain, burst, tail


def _audit(nodes, pods, sizes: Sizes) -> None:
    """The safety audit over what REST returned: every pod bound, none on
    an unschedulable node, per-node requests and pod count within
    allocatable, selectors honoured, every spread app within max_skew over
    the eligible zones."""
    require(len(nodes) == sizes.nodes, f"{len(nodes)} nodes, sent {sizes.nodes}")
    require(len(pods) == sizes.pods, f"{len(pods)} pods, sent {sizes.pods}")
    by_name = {n.metadata.name: n for n in nodes}
    used = {}
    per_app = {}
    for p in pods:
        node = by_name.get(p.spec.node_name)
        require(node is not None, f"{p.metadata.key} on {p.spec.node_name!r}")
        require(
            not node.spec.unschedulable,
            f"{p.metadata.key} bound to unschedulable {node.metadata.name}",
        )
        for key, want in (p.spec.node_selector or {}).items():
            require(
                node.metadata.labels.get(key) == want,
                f"{p.metadata.key} selector {key}={want} not honoured",
            )
        req = p.resource_requests()
        u = used.setdefault(node.metadata.name, [0, 0, 0])
        u[0] += req.milli_cpu
        u[1] += req.memory
        u[2] += 1
        app = p.metadata.labels.get("app")
        if app is not None:
            zone = node.metadata.labels["zone"]
            zones = per_app.setdefault(app, {})
            zones[zone] = zones.get(zone, 0) + 1
    for name, (cpu, mem, count) in used.items():
        alloc = by_name[name].status.allocatable
        require(
            cpu <= alloc.milli_cpu and mem <= alloc.memory
            and count <= alloc.pods,
            f"{name} over allocatable: {cpu}m {mem}B {count} pods",
        )
    eligible = sorted(
        {
            n.metadata.labels["zone"]
            for n in nodes
            if not n.spec.unschedulable
        }
    )
    require(
        sum(sum(z.values()) for z in per_app.values())
        == sizes.burst + sizes.tail,
        "spread pods missing from the audit",
    )
    for app, zones in per_app.items():
        counts = [zones.get(z, 0) for z in eligible]
        require(
            max(counts) - min(counts) <= MAX_SKEW,
            f"{app} skew {max(counts) - min(counts)} > {MAX_SKEW}: {counts}",
        )
    say(
        f"audit ok: {len(pods)}/{sizes.pods} pods bound on "
        f"{len(used)} of {len(nodes)} nodes, none unschedulable, none over "
        f"allocatable; {len(per_app)} spread apps within max_skew="
        f"{MAX_SKEW} over {len(eligible)} zones"
    )


def drive_and_audit(
    base: str, service, sizes: Sizes, seed: int, platform: str,
    deadline_s: float = 600.0,
) -> dict:
    """Create the cluster and the three pod batches over HTTP, wait for
    every bind, then audit through REST, /metrics and /debug/trace.
    ``platform`` is where the wave outputs must have lived.  Returns
    ``DeviceScheduler.dispatched_programs()`` (re-lowering them is not
    free: one call serves this audit and check_programs)."""
    from minisched_tpu.controlplane.remote import RemoteClient
    from minisched_tpu.engine.device_scheduler import DeviceScheduler

    sched = service.scheduler
    require(isinstance(sched, DeviceScheduler), f"engine is {type(sched)}")
    client = RemoteClient(base)
    before, _ = _scrape(base)
    t_start = time.monotonic()  # the trace ring's clock
    nodes, plain, burst, tail = _make_cluster(sizes, seed)

    CHUNK = 2000
    for i in range(0, len(nodes), CHUNK):
        client.nodes().create_many(nodes[i : i + CHUNK], return_objects=False)
    # readiness gate only: a first wave against a half-synced roster would
    # compile a second node capacity
    node_informer = service.informer_factory.informer_for("Node")
    t_end = time.monotonic() + 60
    while len(node_informer.lister()) < sizes.nodes:
        require(time.monotonic() < t_end, "node informer never synced")
        time.sleep(0.05)

    poll_s = min(3.0, max(0.2, sizes.pods / 3000))
    sent = 0

    def send_and_wait(batch, label: str) -> None:
        nonlocal sent
        t0 = time.monotonic()
        for i in range(0, len(batch), CHUNK):
            client.pods().create_many(
                batch[i : i + CHUNK], return_objects=False
            )
        sent += len(batch)
        bound = 0
        while bound < sent:
            require(
                time.monotonic() - t0 < deadline_s,
                f"{label}: only {bound}/{sent} pods bound in {deadline_s}s",
            )
            time.sleep(poll_s)
            bound = sum(1 for p in client.pods().list() if p.spec.node_name)
        say(
            f"{label}: {len(batch)} pods bound, {bound}/{sizes.pods} so far "
            f"({time.monotonic() - t0:.1f}s wall incl. cold compiles, "
            f"{platform})"
        )

    send_and_wait(plain, "plain waves")
    send_and_wait(burst, "spread burst (blocked scan lane, wide and narrow)")
    send_and_wait(tail, "spread tail (exact scan lane)")

    _audit(client.nodes().list(), client.pods().list(), sizes)

    after, spans = _scrape(base)

    def delta(name: str) -> float:
        return after.get(name, 0) - before.get(name, 0)

    require(delta("wave_parked") == 0, f"{delta('wave_parked')} waves parked")
    require(
        delta("wave_dispatch_healed") == 0,
        f"{delta('wave_dispatch_healed')} wrong-arity dispatches healed",
    )
    recent = [s for s in spans if s["ts"] >= t_start]
    require(
        not [s for s in recent if s["stage"] == "wave_park"],
        "/debug/trace holds a wave_park span",
    )
    evaluated = [s for s in recent if s["stage"] == "wave_evaluate"]
    require(bool(evaluated), "/debug/trace holds no wave_evaluate span")
    # one record a bound pod, on the monotonic clock, with the id of the
    # evaluate span that carried it (wave or scan call) and its lane
    binds = [s for s in recent if s["stage"] == "bind"]
    require(bool(binds), "/debug/trace holds no bind record")
    require(
        len({s["pod"] for s in binds}) == len(binds),
        "/debug/trace holds two bind records of one pod",
    )
    lanes: dict = {}
    for s in binds:
        require(
            ("wave" in s or "call" in s) and "lane" in s,
            f"bind record of {s['pod']} names no evaluate: {s}",
        )
        stamps = [
            s[k] for k in ("born", "enqueued", "popped", "evaluated", "bound")
            if k in s
        ]
        require(
            {"popped", "evaluated", "bound"} <= set(s)
            and stamps == sorted(stamps) and t_start <= stamps[0],
            f"bind record of {s['pod']} is not one monotonic path: {s}",
        )
        lanes[s["lane"]] = lanes.get(s["lane"], 0) + 1
    unborn = sum(1 for s in binds if "born" not in s or "enqueued" not in s)
    for s in evaluated:
        require(
            all(d.startswith(platform + ":") for d in s["devices"]),
            f"wave {s['wave']} outputs lived on {s['devices']}",
        )
    gauges = [k for k in after if k.startswith(f"engine_device_{platform}_")]
    require(bool(gauges), f"/metrics has no engine_device_{platform}_* gauge")
    say(
        f"/metrics: wave.parked +0, {gauges[0]}={after[gauges[0]]:.0f}; "
        f"/debug/trace: {len(evaluated)} wave_evaluate spans on {platform}, "
        f"no wave_park, {len(binds)} bind records by lane {lanes} "
        f"({unborn} without the store's or the queue's stamp)"
    )
    say(
        "wave phases count/total s (information, not results; the device "
        f"phase includes cold compiles; {platform}): "
        + ", ".join(
            f"{p} {delta(f'sched_wave_{p}_seconds_count'):.0f}/"
            f"{delta(f'sched_wave_{p}_seconds_sum'):.2f}"
            for p in ("build", "device", "commit", "stall")
        )
    )
    # every lane really ran: each has dispatched at least one program — the
    # wave lane one per packed pod schema, when a whole wave of simple pods
    # leads the backlog
    programs = sched.dispatched_programs()
    counts = {lane: len(texts) for lane, texts in programs.items()}
    wave_schemas = 2 if simple_head(sizes) >= sched.max_wave else 1
    require(
        counts["wave"] >= wave_schemas and counts["blocked_scan"] >= 1
        and counts["narrow_scan"] >= 1 and counts["exact_scan"] >= 1,
        f"programs dispatched per lane: {counts}",
    )
    say(f"programs dispatched per lane: {counts}")
    return programs


def check_programs(service, programs: dict, n_devices: int) -> None:
    """What the live programs are made of: single-device, the Mosaic custom
    call must be IN the wave and blocked-scan programs ("ran on TPU" must
    not mean "ran the XLA tail on TPU"); on a mesh, the sharded path must
    have carried every wave."""
    from minisched_tpu.observability import counters
    from minisched_tpu.parallel.sharding import _CompiledShardedStep

    sched = service.scheduler
    if sched.mesh is None:
        require(n_devices == 1, f"{n_devices} devices but no mesh resolved")
        for lane in ("wave", "blocked_scan"):
            for text in programs[lane]:
                require(
                    "tpu_custom_call" in text,
                    f"a {lane} program holds no Mosaic custom call",
                )
        for lane in ("narrow_scan", "exact_scan"):
            require(
                not any("tpu_custom_call" in t for t in programs[lane]),
                f"a P=1 {lane} program should take the XLA tail",
            )
        say(
            "Mosaic custom call present in every wave and (wide) blocked-"
            "scan program; the narrow and the exact scan take the XLA tail"
        )
        return
    snap = counters.snapshot()
    static_devices = sched._table_builder.static_devices()
    say(
        f"mesh {dict(sched.mesh.shape)}: wave_mesh.waves="
        f"{snap.get('wave_mesh.waves', 0)} fallbacks="
        f"{snap.get('wave_mesh.fallbacks', 0)} heals="
        f"{_CompiledShardedStep.heal_count}, static node columns on "
        f"{len(static_devices)} devices"
    )
    require(snap.get("wave_mesh.waves", 0) > 0, "no wave ran on the mesh")
    require(
        snap.get("wave_mesh.fallbacks", 0) == 0,
        "sharded waves fell back to one device",
    )
    require(_CompiledShardedStep.heal_count == 0, "sharded dispatch healed")
    require(
        len(static_devices) > 1,
        f"static node columns sit on {static_devices}",
    )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    with phase("device"):
        device = check_device()
    with phase("native"):
        from minisched_tpu import native
        from minisched_tpu.utils.compilecache import enable_persistent_cache

        say(
            f"compile cache: {enable_persistent_cache()}; "
            f"native.HAVE_NATIVE={native.HAVE_NATIVE}"
        )
        require(native.HAVE_NATIVE, "native table builder not loaded")
    with phase("kernels"):
        check_kernels(args.seed)
    with phase("serve"), booted_stack() as (base, service):
        programs = drive_and_audit(
            base, service, FULL, args.seed, device["platform"]
        )
        check_programs(service, programs, device["count"])
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
