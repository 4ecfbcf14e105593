#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process holds the chip.  It boots the stack exactly as
``python -m minisched_tpu`` does (``minisched_tpu.__main__.start`` with the
device engine on: full default roster, engine defaults, in-memory store; no
``JAX_PLATFORMS`` and no ``MINISCHED_*`` set here) and fails, with no
fallback, unless JAX's default device is a TPU and the device count is the
cell's ``chips``.  The load comes from ``client.py``, a process of its own
started before JAX is imported here; every end-to-end number is taken on
that client's clock, over loopback HTTP.

Everything that belongs to one cell is found by name:
``workloads/<cell>.json`` names ``configs/<config>.json``,
``traffic/<mix>.json`` (which says what end-to-end metrics the mix reports)
and, under ``per_layer``, its ``metrics/<metric>.json``, each of which names
its reader under ``readers/``.  A configuration may name, under ``maker``,
the module under ``makers/`` that makes its nodes and pods (``cluster``
where it names none) and, under ``reference``, the module under
``references/`` whose numbers are compared after the common ones, and,
under ``rehearsal``, the sizes its rehearsal takes in place of
``REHEARSAL``'s.  A mix is parameters of the one generator
(``client.py``): closed or open, and a closed one may hold a live set by
deleting.  Adding a deployment, a cell, a mix, a metric or a reader is
adding files, as long as the mix is one the generator's two loops can offer
and the end-to-end metric is one ``end_to_end`` below works out: no file
that is there lists the cells.

Order of a run: set-up (boot, nodes, init pods bound over the served path,
a deleting mix's live set filled, the cell's own traffic until a whole
stretch passes with no trace, lowering or compile event) -> the window ->
drain-out grace -> memory peak -> read back through REST -> stop the stack
-> the comparison that decides ``correct`` (``audit.py``, ``reference.py``,
the configuration's own reference) -> one JSON line, last on standard
output.

The rehearsal (a tiny cluster on whatever device JAX has) is reached only
as a Python argument, ``main(argv, rehearsal=True)``: no flag and no
environment variable leads to it, so the driver's command cannot take it.
"""

from __future__ import annotations

T_START = __import__("time").monotonic()

import argparse
import contextlib
import gc
import importlib
import json
import os
import shutil
import subprocess
import sys
import time
from typing import Any, Callable, Dict, Iterator, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import audit  # noqa: E402  (the benchmark's own modules: none imports JAX)
import prom  # noqa: E402
import tracefile  # noqa: E402
from readers import trace_gaps  # noqa: E402

#: what the rehearsal overrides: sizes a CPU can hold in seconds
REHEARSAL = {
    "nodes": 64,
    "init_pods": 48,
    "outstanding": 96,
    "chunk": 32,
    "rate_per_s": 40,
    "warm_stretch_s": 1,
    "warm_max_stretches": 4,
    "grace_s": 6,
    "trace_s": 1,
    "deadline_s": 20,
    "warm_bursts": [40, 5],
    "live_pod_cap": 2400,
    "live_target": 192,
    "deleters": 2,
}
#: the sizes among them that follow from a deployment's capacity, which a
#: configuration may state for itself under ``rehearsal``
REHEARSAL_OWN = (
    "nodes", "init_pods", "live_target", "outstanding", "chunk", "deleters", "live_pod_cap", "warm_bursts",
)

COMPILE_EVENTS = "/jax/core/compile"
#: where a traced run keeps its trace until it has read it
TRACE_DIR = os.path.join(HERE, ".trace")
#: the longest wait for the init pods or a warm burst to bind (a cold compile)
DEADLINE_S = 900


def say(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T_START:7.1f}s] {msg}", file=sys.stderr, flush=True)


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(name: str) -> Dict[str, Any]:
    """The cell's file with its configuration and traffic beside it."""
    cell = load_json("workloads", name + ".json")
    cell["config_data"] = load_json("configs", cell["config"] + ".json")
    traffic = load_json("traffic", cell["traffic"] + ".json")
    traffic.update({k: v for k, v in cell.get("params", {}).items() if k in traffic})
    cell["traffic_data"] = traffic
    return cell


def cell_metrics(cell: Dict[str, Any]) -> List[Dict[str, Any]]:
    """The per-layer metrics the cell's own file names."""
    return [load_json("metrics", name + ".json") for name in cell["per_layer"]]


def rehearsal_keys_refused(config: Dict[str, Any]) -> List[str]:
    """The keys of a configuration's ``rehearsal`` that are not sizes it may
    state (``shrink`` raises on them, ``manifest.check`` lists them)."""
    return sorted(set(config.get("rehearsal") or {}) - set(REHEARSAL_OWN))


def shrink(cell: Dict[str, Any]) -> None:
    """Cut a cell to the rehearsal's size (never reached from the command
    line): ``REHEARSAL``, with the configuration's own ``rehearsal`` laid
    over it.  A deployment whose capacity is its node count cannot hold
    the pods that 64 nodes of 110 hold, so it states sizes that fit."""
    cfg, traffic = cell["config_data"], cell["traffic_data"]
    unknown = rehearsal_keys_refused(cfg)
    if unknown:
        raise ValueError(f"configs/{cfg['name']}.json: rehearsal {unknown}: a configuration may state {REHEARSAL_OWN}")
    sizes = {**REHEARSAL, **(cfg.get("rehearsal") or {})}
    cfg["nodes"]["count"] = sizes["nodes"]
    cfg["init_pods"]["count"] = sizes["init_pods"]
    cfg["live_pod_cap"] = sizes["live_pod_cap"]
    for key in (
        "outstanding", "chunk", "rate_per_s", "warm_stretch_s", "warm_max_stretches", "grace_s", "trace_s",
        "live_target", "deleters",
    ):
        if traffic.get(key) is not None:
            traffic[key] = sizes[key]
    if cell.get("params", {}).get("warm_bursts"):
        cell["params"]["warm_bursts"] = sizes["warm_bursts"]


class Client:
    """The pipe to ``client.py``."""

    def __init__(self) -> None:
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "client.py")],
            stdin=subprocess.PIPE,
            stdout=subprocess.PIPE,
            text=True,
            bufsize=1,
        )

    def send(self, op: str, **kw: Any) -> None:
        self.proc.stdin.write(json.dumps({"op": op, **kw}) + "\n")
        self.proc.stdin.flush()

    def call(self, op: str, **kw: Any) -> Dict[str, Any]:
        self.send(op, **kw)
        return self.recv(op)

    def recv(self, op: str) -> Dict[str, Any]:
        line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError(f"the load generator died during {op!r}")
        reply = json.loads(line)
        if not reply.pop("ok", False):
            raise RuntimeError(f"load generator, {op!r}: {reply.get('error')}")
        return reply

    def close(self) -> None:
        """Stop the process and wait until it has ended."""
        if self.proc.poll() is None:
            try:
                self.call("stop")
            except Exception:
                pass
        for pipe in (self.proc.stdin, self.proc.stdout):
            try:
                pipe.close()
            except Exception:
                pass
        try:
            self.proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()


class CompileCounter:
    """Counts JAX's trace, lowering and compile events (benchmark code: a
    ``jax.monitoring`` listener, no change to the program)."""

    def __init__(self) -> None:
        self.count = 0

    def __call__(self, event: str, _duration: float, **_kw: Any) -> None:
        if event.startswith(COMPILE_EVENTS):
            self.count += 1


class FullGcPauses:
    """The program's loop runs with the collector off and collects by hand;
    a full pass over a heap of bound pods stops every thread.  This times
    them (a ``gc.callbacks`` entry: reading, no change to the program), so
    that a run whose tail a stall made says whether this was it."""

    def __init__(self) -> None:
        self.count = 0
        self.longest_s = 0.0
        self._t0 = 0.0

    def __call__(self, phase: str, info: Dict[str, int]) -> None:
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._t0 = time.monotonic()
        else:
            self.count += 1
            self.longest_s = max(self.longest_s, time.monotonic() - self._t0)


def device_gate(chips: int, rehearsal: bool) -> Dict[str, Any]:
    """Says what JAX found; outside the rehearsal anything but a TPU with
    exactly the cell's chips ends the run, with no result."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    say(
        f"platform={dev.platform} device_kind={dev.device_kind!r} devices={len(devices)} "
        f"jax={jax.__version__} JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}"
    )
    if not rehearsal and (dev.platform != "tpu" or len(devices) != chips):
        raise SystemExit(
            f"benchmarks/run.py: the cell asks for {chips} TPU chip(s); JAX found "
            f"{len(devices)} x {dev.platform} ({dev.device_kind}). No fallback."
        )
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}


def memory_peak_bytes() -> int:
    """Peak on the fullest chip, where the backend reports it."""
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def wait_nodes_synced(service: Any, count: int, seconds: float = 120.0) -> None:
    """Readiness gate only: a first wave against a half-synced roster would
    compile a second node capacity."""
    lister = service.informer_factory.informer_for("Node").lister
    t_end = time.monotonic() + seconds
    while len(lister()) < count:
        if time.monotonic() > t_end:
            raise RuntimeError("the node informer never synced")
        time.sleep(0.05)


def end_to_end(names: List[str], window: Dict[str, Any], grace: Dict[str, Any], setup_s: float) -> Dict[str, Any]:
    """The cell's end-to-end metrics, each from the client's record: all
    the work over all the time of the window, the tail of all requests."""
    values = {
        "pods_bound_per_s": (window["bound_in_window"] / window["window_s"], "pods/s"),
        "bind_p50_ms": (grace["bind_ms"].get("p50"), "ms"),
        "bind_p99_ms": (grace["bind_ms"].get("p99"), "ms"),
        "setup_s": (setup_s, "s"),
    }
    return {
        n: {"value": values[n][0], "unit": values[n][1]}
        for n in names + ["setup_s"]
        if values[n][0] is not None
    }


def per_layer(metrics: List[Dict[str, Any]], ctx: Dict[str, Any]) -> Dict[str, Any]:
    out = {}
    for m in metrics:
        reader = importlib.import_module("readers." + m["reader"])
        value = reader.read(ctx, **m["args"])
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


class Stack:
    """What set-up hands to the window: the booted, warm stack and the
    generator that is feeding it."""

    def __init__(self, cell: Dict[str, Any], client: Client) -> None:
        self.cell = cell
        self.config: Dict[str, Any] = cell["config_data"]
        self.traffic: Dict[str, Any] = cell["traffic_data"]
        self.client = client
        self.device: Dict[str, Any] = {}
        self.base = ""
        self.sched: Any = None
        self.compiles = CompileCounter()
        self.full_gc = FullGcPauses()


@contextlib.contextmanager
def warm_stack(
    cell: Dict[str, Any],
    seed: int,
    rehearsal: bool = False,
    fault: Optional[Callable[[Any], None]] = None,
) -> Iterator[Stack]:
    """Set-up: the generator's process, the device gate, the stack booted as
    ``python -m minisched_tpu`` boots it, nodes and init pods bound over the
    served path, and the cell's own traffic until a whole stretch passes
    with no compile event.  On the way out the generator and the stack are
    stopped: the program's state is freed before anything else runs."""
    client = Client()  # before JAX: the generator must never hold the chip
    st = Stack(cell, client)
    stop = None
    jax = None
    try:
        st.device = device_gate(cell["chips"], rehearsal)

        import jax
        from minisched_tpu.__main__ import start
        from minisched_tpu.service.config import ProcessConfig

        jax.monitoring.register_event_duration_secs_listener(st.compiles)
        gc.callbacks.append(st.full_gc)

        _c, st.base, stop = start(
            ProcessConfig(port=0, frontend_url="http://localhost:3000"), device_mode=True
        )
        service = stop.service
        st.sched = service.scheduler
        if fault is not None:
            fault(service)
        say(f"stack up at {st.base}")

        client.call("hello", base=st.base, config=st.config, seed=seed)
        client.call("nodes")
        wait_nodes_synced(service, st.config["nodes"]["count"])
        deadline_s = REHEARSAL["deadline_s"] if rehearsal else DEADLINE_S
        init = client.call("init", deadline_s=deadline_s)
        say(f"init pods: {init['sent']} sent, {init['unbound']} unbound, {init['seconds']:.1f}s")

        # warm with the cell's own traffic and nothing else: bursts that
        # reach the program shapes its tail chunks can take, then the mix
        # itself, which keeps running into the window (a closed loop opens
        # its window at its steady outstanding count, not on an empty queue)
        for count in cell.get("params", {}).get("warm_bursts", []):
            b = client.call("burst", count=count, deadline_s=deadline_s)
            say(f"warm burst of {count}: {b['seconds']:.1f}s, {b['unbound']} unbound")
        if st.traffic.get("live_target"):
            # a mix that holds a live set opens its window in steady state:
            # the set is filled here, and the deleters run from the first stretch
            f = client.call(
                "burst", count=st.traffic["live_target"], chunk=st.traffic["chunk"], deadline_s=deadline_s
            )
            say(f"live set of {st.traffic['live_target']} filled: {f['seconds']:.1f}s, {f['unbound']} unbound")
        for i in range(st.traffic["warm_max_stretches"]):
            seen = st.compiles.count
            w = client.call("run", traffic=st.traffic, phase="warm", seconds=st.traffic["warm_stretch_s"])
            say(
                f"warm stretch {i}: {w['sent']} sent, {w['bound_in_window']} bound in it, "
                f"{w['outstanding_at_close']} outstanding, {st.compiles.count - seen} compile events"
            )
            if st.compiles.count == seen:
                break
        else:
            raise RuntimeError("the warm-up never passed a stretch without a compile event")
        yield st
    finally:
        client.close()
        if stop is not None:
            stop()
        if jax is not None:
            jax.monitoring.unregister_event_duration_listener(st.compiles)
        if st.full_gc in gc.callbacks:
            gc.callbacks.remove(st.full_gc)


def main(
    argv: Optional[List[str]] = None,
    rehearsal: bool = False,
    fault: Optional[Callable[[Any], None]] = None,
) -> int:
    """``rehearsal`` and ``fault`` are Python arguments only.  ``fault`` is
    handed the booted service before any pod is sent: the tests and the
    control break the timed path underneath with it."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_start = T_START if argv is None else time.monotonic()

    if not os.path.isdir(os.path.join(ROOT, "minisched_tpu")):
        raise SystemExit("benchmarks/run.py: no minisched_tpu/ beside benchmarks/: nothing to measure")
    cell = load_cell(args.workload)
    if rehearsal:
        shrink(cell)
    metrics = cell_metrics(cell)

    with warm_stack(cell, args.seed, rehearsal, fault) as st:
        import jax

        client, traffic, sched = st.client, st.traffic, st.sched
        before = prom.scrape(st.base)
        cycle_before = sched.metrics.snapshot()
        compiles_before = st.compiles.count
        st.full_gc.count, st.full_gc.longest_s = 0, 0.0
        cpu_before = time.process_time()
        if args.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # the Python tracer slows the host it measures
            options.host_tracer_level = 1
            options.enable_hlo_proto = False
            jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
            t_trace = time.monotonic()
        setup_s = time.monotonic() - t_start
        say(f"window opens: setup_s={setup_s:.2f}")
        client.send("run", traffic=traffic, phase="pod", seconds=args.seconds)
        trace_s = min(args.seconds, traffic.get("trace_s") or args.seconds)
        if args.trace and trace_s < args.seconds:
            # the trace covers the window's first trace_s seconds: a trace
            # of a whole long window is large, and a traced run reports no
            # end-to-end metric that the export's seconds could disturb
            time.sleep(trace_s)
            traced_s = time.monotonic() - t_trace
            jax.profiler.stop_trace()
        window = client.recv("run")
        after = prom.scrape(st.base)
        cycle_after = sched.metrics.snapshot()
        # this process's CPU seconds, all threads, a second of window: near 1
        # where the interpreter's one lock, and not the cores, is the limit
        window["process_cpu_per_s"] = (time.process_time() - cpu_before) / window["window_s"]
        window["compiles"] = st.compiles.count - compiles_before
        window["full_gc_passes"] = st.full_gc.count
        window["full_gc_longest_s"] = st.full_gc.longest_s
        if args.trace and trace_s >= args.seconds:
            traced_s = time.monotonic() - t_trace
            jax.profiler.stop_trace()  # exports: seconds, outside the window
        say(f"window closed: {json.dumps(window)}")

        grace = client.call("grace", seconds=traffic["grace_s"])
        say(f"drain-out: {json.dumps(grace)}")
        peak = memory_peak_bytes()
        nodes, pods = audit.read_back(st.base)
        acks = client.call("acks")

    trace = None
    if args.trace:
        t0 = time.monotonic()
        trace = tracefile.load(TRACE_DIR, traced_s)
        say(
            f"trace read in {time.monotonic() - t0:.1f}s: "
            + json.dumps({p: l for p, l in trace.layout.items() if l})[:1500]
        )
        say("programs in the trace [name, events, seconds]: " + json.dumps(tracefile.top_modules(trace)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    counters = {
        "wave_parked": prom.total(after, "wave_parked") - prom.total(before, "wave_parked"),
        "dispatch_healed": prom.total(after, "wave_dispatch_healed")
        - prom.total(before, "wave_dispatch_healed"),
        "compiles_in_window": window["compiles"],
    }
    compared = audit.checks(nodes, pods, acks, st.config["nodes"]["count"], counters, st.config)
    correct = audit.verdict(compared)
    failed = grace["unbound"]
    if not correct:
        failed = max(failed, 1)

    device = dict(st.device, memory_peak_bytes=peak)
    result: Dict[str, Any] = {
        "correct": correct,
        "attempted": window["sent"],
        "failed": failed,
    }
    if args.trace:
        ctx = {
            "before": before,
            "after": after,
            "window_s": window["window_s"],
            "trace": trace,
            "client": grace,
            "cycle_before": cycle_before,
            "cycle_after": cycle_after,
        }
        result["metrics"] = per_layer(metrics, ctx)
        device["busy_s"] = tracefile.busy_s(trace)
        device["window_s"] = trace.window_s
        result["breakdown"] = {"device_ops": tracefile.top_ops(trace), "idle_gaps": trace_gaps.table(trace)}
    else:
        result["metrics"] = end_to_end(traffic["end_to_end"], window, grace, setup_s)
    result["device"] = device
    result["window"] = {**window, **{k: grace[k] for k in ("grace_s", "bind_ms", "late_ms")}}
    result["compared"] = {k: {"number": v[0], "limit": v[1]} for k, v in compared.items()}

    sys.stderr.flush()
    for name, (number, limit) in compared.items():
        print(f"compared {name}: {number} (limit {limit})", file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
