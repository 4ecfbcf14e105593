"""Live latency histograms + Prometheus text exposition (ISSUE 11).

`observability/counters.py` records what the system DID as integers;
this module records how LONG it took, live, without a bench.  One
process-global registry (``GLOBAL``) of fixed-log-bucket histograms
keeps call sites one-liners — ``hist.observe("sched.time_to_bind_s",
dt)`` — and `/metrics` on the REST façade (or the supervisors' child
metrics listeners) renders the whole registry, counters and gauges
included, as Prometheus text exposition.

**Buckets are fixed, not configurable**: every histogram shares one
geometric ladder, ``100µs · 2^k`` for k in 0..25 (upper bound ≈ 56 min)
plus +Inf overflow.  Fixed buckets mean (a) zero per-histogram config to
drift, (b) any two histograms (or the same one before/after a restart)
are mergeable bucket-by-bucket, and (c) "agrees within bucket
resolution" is a well-defined cross-check the bench roles enforce
against their offline sampled percentiles.  Factor-2 resolution is
coarse for a single sample and plenty for an SLO percentile.

The histogram registry documented here (the lint test in
tests/test_telemetry.py greps call sites against THIS docstring,
same contract as counters.py):

    sched.time_to_bind_s
        — arrival→bind per pod: stamped once at queue admission (the
          stamp survives requeues; the queue owns it, not the
          QueuedPodInfo), observed at bind ack, labeled
          ``priority=<pod priority>`` — the per-priority-class latency
          breakdown of "Priority Matters"
    sched.queue_wait_s
        — admission→pop per pod: the same queue-owned stamp, read (not
          consumed) when ``pop_batch`` seals a wave — how long the pod
          sat in the queue before a wave took it, the part of
          ``sched.time_to_bind_s`` that is waiting and not service
    sched.pod_hold_s
        — popped→evaluate per pod, labeled ``lane=`` (``wave``, ``wide``,
          ``narrow``, ``exact``: the program that carries it, as
          ``sched.lane_pods.*``): from ``pop_batch``'s seal (where
          ``sched.queue_wait_s`` ends) to the entry of the evaluate that
          carries the pod (``sched.wave_device`` / ``sched.scan_evaluate``)
          — the handoff, the scan deferral, the grouping and the build;
          one ``observe_many`` a wave or call
    sched.pod_commit_s
        — that evaluate's entry→``bind_many`` returned with the pod's bind
          stored, per bound pod, labeled ``lane=``; one ``observe_many`` a
          bind batch.  For a pod bound at its first pop
          ``queue_wait + pod_hold + pod_commit = time_to_bind`` (one clock,
          shared stamps)
    informer.pod_add_lag_s
        — the Pod informer, per ``ADDED`` event: the store's fanout
          (``WatchEvent.born``) → the batch handed to its handlers, any
          wait at the dispatch gate included — the time before the queue
          stamps the pod's admission; one ``observe_many`` a batch
    watch.bind_lag_s
        — per bind event (the write that set ``spec.nodeName``: the store
          marks it, ``WatchEvent.bind``) and per HTTP watch stream: the
          store's fanout → the event written to the stream, in both
          delivery paths beside ``watch.delivery_lag_s``; never an
          ``ADDED``, a ``DELETED`` or a resume's replay
    http.request_s
        — REST façade request latency, labeled ``verb=``/``route=``
          (route is the low-cardinality shape of the path — kind +
          name/subresource markers — never raw names); long-lived watch
          streams are excluded
    http.list_s
        — LIST verb latency on the REST façade, labeled ``kind=`` (a
          handful of kinds, low cardinality), observed in BOTH read
          modes: the lock-free COW path serving the memoized shared
          payload and the ``MINISCHED_COW_READS=0`` locked re-encode
          path — the relist-storm p99 the ``relist`` bench gates
    watch.delivery_lag_s
        — store-fanout→socket-write lag per watch event, observed in
          BOTH delivery paths (selector stream loop and the legacy
          thread path) against the WatchEvent's birth stamp
    storage.wal_append_s / storage.wal_fsync_s
        — durable-store WAL frame append (write + inline fsync when
          armed) and deferred batch-barrier fsync times
    storage.group_wait_s
        — time a mutation spends parked on the group-commit barrier
          (stage → its group's fsync completing), observed by every
          waiter including the self-elected leader; the exemplar
          carries the object key of the waiter
    grpc.request_s
        — gRPC facade request latency, labeled ``method=`` (Health /
          Evaluate / List) — the wire-RPC mirror of ``http.request_s``
    storage.quorum_wait_s
        — time the leader's group-commit barrier spent awaiting a
          follower quorum's durability acks, between the group's fsync
          and its publish (DESIGN.md §27) — the replication tax every
          acked mutation pays; the bench ``repl`` role's headline
    storage.repl_ship_s
        — leader-side per-group ship time: framing one commit group and
          writing it down a follower's tail stream socket
    storage.repl_apply_s
        — follower-side per-group apply time: CRC verify + WAL append +
          fsync + replay through the real recovery path
    storage.repl.bootstrap_s
        — follower checkpoint-seeded reseed time: fetch the leader's
          checkpoint generation, verify its sha256, land + restore it
          locally (DESIGN.md §28) — the O(state) replica-bootstrap cost
          that replaced O(history) re-tails; the bench ``repl`` role's
          bootstrap-under-load gate
    shard.route_s
        — sharded-router topology refresh time: probing /shards/status
          across the known endpoints and adopting the highest epoch
          (DESIGN.md §30) — the stale-router recovery cost a WrongShard
          chase pays before its re-dispatch
    shard.crossbind_s
        — end-to-end latency of a bind batch that spanned >1 leader
          group: the two-shard commit (per-group dispatch in parallel,
          each side's group-commit barrier + registry insert) — the
          cross-shard tax the bench ``shard`` role reports separately
          from single-group binds
    shard.freeze_s
        — a split's whole write-freeze window, coordinator-side: the
          freeze fanout through handoff, seed, lease renewal, topology
          flip and unfreeze (DESIGN.md §31) — what the lease TTL must
          comfortably exceed for healthy splits
    shard.autosplit.window_p99_s
        — the autosplit watcher's WINDOWED storage.group_wait_s p99
          (bucket-count delta between consecutive ticks, nearest-rank
          over the shared ladder): the saturation signal the hot
          threshold is judged against, recoverable after a split where
          the cumulative histogram is not

**Spans** (``observability/profiling.span``).  Every span name below owns
TWO histograms, registered at import of the module that opens it, so a
scrape shows them with count 0 from boot: ``<span>_s``, its wall time,
and ``<span>_cpu_s``, the CPU seconds of the thread that ran it.  Wall
less CPU is time the thread did not run (the interpreter's one lock, the
device, a socket, a queue).  The same name is the event's name in a
``jax.profiler`` trace.  One span a batch, a wave or a call — never one
a pod.  The lint in tests/test_telemetry.py holds every ``span(<name>)`` and
``metrics.timed(<phase>)`` call site to this list:

    http.create
        — façade handler thread, one pod-create call (1 pod or 1,024;
          other kinds open none), id ``n``; children, in order:
          ``http.create_read`` (body off the socket + JSON parse),
          ``http.create_decode`` (dicts → objects),
          ``http.create_store`` (the store transaction and its fanout),
          ``http.create_respond`` (encode + socket write)
    http.delete
        — façade handler thread, one pod ``DELETE`` (one object a
          request; other kinds open none), id ``n``; children:
          ``http.delete_store`` (the wait for a leader's transaction
          or the lead of one: the store's lock, the deletes that queued
          together, one publish of the read plane — ``_DeleteCombiner``),
          ``http.delete_respond`` (the answer's socket write)
    watch.deliver
        — stream-loop thread, one drained batch of watch events for one
          stream: encode into the out-buffer + socket write; id ``n``
    informer.dispatch
        — informer thread, one batch handed to one handler (where pods
          enter the scheduling queue and bind events reach the cache);
          ids ``kind``, ``n``
    informer.gate_wait
        — informer thread, one event batch that found the dispatch gate
          closed (``_bind_batch`` closes it before a wave's binds land),
          held until the engine reopened it or 2 s passed; ids ``kind``,
          ``n``; none where the gate was open
    sched.queue_pop_wait
        — build worker blocked in ``pop_batch``: no work was offered
          (engine phase ``pipeline_pop``)
    sched.wave_build
        — build worker, one wave's host build (phase
          ``wave_pipeline_build``), ids ``wave``, ``n``; children
          ``sched.wave_snapshot``, ``sched.wave_assigned_list``,
          ``sched.wave_build_tables``, ``sched.wave_build_constraints``
          (the serial wave path opens the same children on the loop
          thread), and under the last ``sched.constraints_lock_wait``,
          ``sched.constraints_store_list``
    sched.loop_handoff_wait
        — loop thread waiting for its next item (phase ``loop_pop``: the
          pipeline's handoff queue; the scheduling queue itself on the
          serial path)
    sched.wave_stall
        — not a span, one histogram (``_s``): the handoff wait of a wave
          that followed a wave — the device idle because the next build
          was not ready (phase ``wave_pipeline_stall``)
    sched.wave
        — loop thread, one wave from handoff to committed, ids ``wave``,
          ``n``; children ``sched.wave_evaluate`` (which holds
          ``sched.wave_device`` and ``sched.wave_postfetch``),
          ``sched.wave_winners``, ``sched.wave_commit``,
          ``sched.losers_handle``
    sched.wave_device
        — dispatch + H2D + the device's run + D2H on the host's clock,
          ids ``wave``, ``n``; children ``sched.wave_dispatch`` (the
          evaluator call returns: enqueue + H2D) and ``sched.wave_fetch``
          (``device_get``: the run, D2H, and getting the lock back)
    sched.wave_commit
        — ``_commit_winners`` (phase ``commit``), ids ``wave``, ``n``;
          children ``sched.permit`` (one span over a wave's reserve and
          permit chains; the default roster has neither) and
          ``sched.bind`` (the batched bind transaction, id ``n``)
    events.write
        — ``event-writer`` thread (``controlplane/client.EventRecorder``),
          one batch of decisions landed as ``Event`` objects: the objects
          built, one ``delete_many`` of what passes the cap, one
          ``create_many``; id ``n`` (the batch's length).  On no layer's
          path: its CPU is what the writer takes from the interpreter's
          lock the engine's threads run under
    sched.scan_flush
        — loop thread, one flush of the deferred cross-pod lane, ids
          ``call``, ``n``; children ``sched.scan_grouping``,
          ``sched.scan_build`` (with ``sched.scan_build_nodes``,
          ``sched.scan_build_pods``, ``sched.scan_build_constraints``),
          ``sched.scan_evaluate`` (ids ``call``, ``n``; children
          ``sched.scan_dispatch`` / ``sched.scan_fetch`` as in the wave)
    constraints.combo_rows
        — the thread that builds the constraint tables, inside
          ``sched.scan_build_constraints`` and
          ``sched.wave_build_constraints``: the combo rows of one
          ``build_constraint_tables`` (a row a distinct namespaces,
          selector and topology key: its per-node counts, domain sums,
          symmetric weights and reverse anti-affinity bans); id ``n``
          (the rows filled)
    sched.loop_gc
        — loop thread, the explicit collection at a wave boundary
    sched.snapshot / sched.schedule / sched.wait_on_permit
        — the scalar engine's cycle (one pod is its whole wave) and the
          detached binding cycle of a pod a permit plugin parked
    sched.cycle / sched.cycle_failed
        — not spans, one histogram each (``_s``): the scalar engine's
          whole cycle, named by its outcome after the fact

**Exemplars**: ``observe(..., exemplar="default/pod-1")`` stamps the
bucket the sample lands in with that string (last writer wins, one per
bucket — bounded state, no sample log); ``observe_many(values,
exemplar=...)`` stamps the bucket of the batch's largest value, so the
string names the slowest pod of the batch.  The exposition renders them as
OpenMetrics exemplars — `` # {key="default/pod-1"} 0.043`` appended to
the owning ``_bucket`` line — so "what was the pod in the p99 bucket?"
is answerable straight off a scrape; exemplar-free histograms render
byte-identically to before.

Pretty-print a live process: ``python -m minisched_tpu metrics <url>``.
"""

from __future__ import annotations

import math
import threading
from typing import Dict, Iterable, List, Optional, Tuple

#: first bucket upper bound: 100µs (below the cheapest observed seam)
BUCKET_BASE_S = 1e-4
#: finite buckets: 1e-4 · 2^k, k ∈ [0, 26); last finite bound ≈ 3355s
NBUCKETS = 26

#: the shared ladder of finite upper bounds, low→high
BUCKET_BOUNDS: Tuple[float, ...] = tuple(
    BUCKET_BASE_S * (1 << k) for k in range(NBUCKETS)
)


def bucket_index(v: float) -> int:
    """Index of the finite bucket whose upper bound first covers ``v``,
    or ``NBUCKETS`` for overflow (+Inf only).  Exact at power-of-two
    boundaries (frexp, not float log2): a value equal to a bound lands
    IN that bucket, matching Prometheus ``le`` semantics."""
    if v <= BUCKET_BASE_S:
        return 0
    m, e = math.frexp(v / BUCKET_BASE_S)  # v/base = m·2^e, m ∈ [0.5, 1)
    idx = e - 1 if m == 0.5 else e
    return idx if idx < NBUCKETS else NBUCKETS


class Histogram:
    """One label-child: fixed log2 buckets + sum + count.

    Lock-cheap: one uncontended Lock per child, three integer bumps and
    a float add inside it — no allocation, no sorting, no sample list."""

    __slots__ = ("_mu", "counts", "overflow", "sum", "count", "exemplars")

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self.counts = [0] * NBUCKETS
        self.overflow = 0
        self.sum = 0.0
        self.count = 0
        #: bucket index (NBUCKETS = +Inf) → (exemplar string, value);
        #: last writer wins, so state stays O(buckets) forever
        self.exemplars: Dict[int, Tuple[str, float]] = {}

    def observe(self, v: float, exemplar: Optional[str] = None) -> None:
        i = bucket_index(v)
        with self._mu:
            if i < NBUCKETS:
                self.counts[i] += 1
            else:
                self.overflow += 1
            self.sum += v
            self.count += 1
            if exemplar is not None:
                self.exemplars[i] = (str(exemplar), v)

    def observe_many(
        self, values: List[float], exemplar: Optional[str] = None
    ) -> None:
        """A batch of observations under one lock hold — one a pod of a
        wave, say.  ``exemplar`` names the batch's largest value (the
        slowest pod): it stamps the bucket that value lands in."""
        if not values:
            return
        idx = [bucket_index(v) for v in values]
        top = max(range(len(values)), key=values.__getitem__)
        with self._mu:
            counts = self.counts
            for i in idx:
                if i < NBUCKETS:
                    counts[i] += 1
                else:
                    self.overflow += 1
            self.sum += sum(values)
            self.count += len(values)
            if exemplar is not None:
                self.exemplars[idx[top]] = (str(exemplar), values[top])

    def merge_into(self, counts: List[int]) -> Tuple[int, float, int]:
        """Add this child's buckets into ``counts`` (len NBUCKETS);
        returns (overflow, sum, count) deltas — the registry's
        cross-label aggregation primitive."""
        with self._mu:
            for i, c in enumerate(self.counts):
                counts[i] += c
            return self.overflow, self.sum, self.count

    def snapshot(self) -> Dict[str, object]:
        with self._mu:
            return {
                "counts": list(self.counts),
                "overflow": self.overflow,
                "sum": self.sum,
                "count": self.count,
                "exemplars": dict(self.exemplars),
            }


LabelsKey = Tuple[Tuple[str, str], ...]


class Histograms:
    """The registry: (name, sorted label items) → Histogram child."""

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self._hists: Dict[Tuple[str, LabelsKey], Histogram] = {}
        #: names that exist from registration on, with count 0 until the
        #: first observation (and again after ``reset``)
        self._registered: Dict[str, None] = {}
        #: bumped by ``reset``: whoever keeps a child (``child``) instead
        #: of going through ``observe`` drops it when this has moved
        self.generation = 0

    def register(self, *names: str) -> None:
        """Create the unlabeled child of each name now, so a scrape shows
        the series from boot: a reader that differences two scrapes then
        never meets a histogram that appeared in between."""
        with self._mu:
            for name in names:
                self._registered[name] = None
                self._hists.setdefault((name, ()), Histogram())

    def _child(self, name: str, labels: Dict[str, str]) -> Histogram:
        key = (name, tuple(sorted(labels.items())))
        with self._mu:
            h = self._hists.get(key)
            if h is None:
                h = self._hists[key] = Histogram()
        return h

    def child(self, name: str) -> Histogram:
        """The unlabeled child of ``name``, for a hot call site to keep
        and observe directly; valid while ``generation`` stands."""
        return self._child(name, {})

    def observe(
        self,
        name: str,
        v: float,
        exemplar: Optional[str] = None,
        **labels: str,
    ) -> None:
        self._child(name, labels).observe(v, exemplar=exemplar)

    def observe_many(
        self,
        name: str,
        values: List[float],
        exemplar: Optional[str] = None,
        **labels: str,
    ) -> None:
        self._child(name, labels).observe_many(values, exemplar=exemplar)

    def get(self, name: str, **labels: str) -> Optional[Histogram]:
        key = (name, tuple(sorted(labels.items())))
        with self._mu:
            return self._hists.get(key)

    def children(self, name: str) -> List[Tuple[LabelsKey, Histogram]]:
        with self._mu:
            return [
                (k[1], h) for k, h in self._hists.items() if k[0] == name
            ]

    def names(self) -> List[str]:
        with self._mu:
            return sorted({k[0] for k in self._hists})

    def merged(self, name: str) -> Tuple[List[int], int, float, int]:
        """(bucket counts, overflow, sum, count) aggregated across every
        label child of ``name`` — mergeable because buckets are fixed."""
        counts = [0] * NBUCKETS
        overflow, total, n = 0, 0.0, 0
        for _labels, h in self.children(name):
            o, s, c = h.merge_into(counts)
            overflow += o
            total += s
            n += c
        return counts, overflow, total, n

    def quantile_bounds(
        self, name: str, q: float
    ) -> Optional[Tuple[float, float]]:
        """[lower, upper) bounds of the bucket holding the q-quantile
        across all label children, or None when empty.  The upper bound
        is the conservative point estimate; "agrees within bucket
        resolution" means a sampled quantile falls inside (or within one
        bucket of) these bounds."""
        counts, overflow, _s, n = self.merged(name)
        if n == 0:
            return None
        rank = max(1, math.ceil(q * n))  # nearest-rank, 1-based
        cum = 0
        for i, c in enumerate(counts):
            cum += c
            if cum >= rank:
                lo = BUCKET_BOUNDS[i - 1] if i > 0 else 0.0
                return lo, BUCKET_BOUNDS[i]
        return BUCKET_BOUNDS[-1], math.inf

    def snapshot(self) -> Dict[str, Dict[str, object]]:
        """name → {count, sum, p50, p99} (bucket-upper estimates) —
        the compact block bench records embed as ``metrics_snapshot``."""
        out: Dict[str, Dict[str, object]] = {}
        for name in self.names():
            _counts, _ovf, total, n = self.merged(name)
            p50 = self.quantile_bounds(name, 0.50)
            p99 = self.quantile_bounds(name, 0.99)
            out[name] = {
                "count": n,
                "sum_s": total,
                "p50_le_s": p50[1] if p50 else None,
                "p99_le_s": p99[1] if p99 else None,
            }
        return out

    def reset(self) -> None:
        with self._mu:
            self._hists = {(n, ()): Histogram() for n in self._registered}
            self.generation += 1


GLOBAL = Histograms()


def observe(
    name: str, v: float, exemplar: Optional[str] = None, **labels: str
) -> None:
    GLOBAL.observe(name, v, exemplar=exemplar, **labels)


def observe_many(
    name: str,
    values: List[float],
    exemplar: Optional[str] = None,
    **labels: str,
) -> None:
    GLOBAL.observe_many(name, values, exemplar=exemplar, **labels)


def register(*names: str) -> None:
    GLOBAL.register(*names)


def quantile_bounds(name: str, q: float) -> Optional[Tuple[float, float]]:
    return GLOBAL.quantile_bounds(name, q)


def snapshot() -> Dict[str, Dict[str, object]]:
    return GLOBAL.snapshot()


def reset() -> None:
    GLOBAL.reset()


# -- Prometheus text exposition ---------------------------------------------

def _metric_name(name: str) -> str:
    """``sched.time_to_bind_s`` → ``sched_time_to_bind_seconds``: dots
    (and any other illegal rune) become underscores, a trailing ``_s``
    unit spells out per Prometheus naming convention."""
    out = "".join(
        ch if (ch.isascii() and (ch.isalnum() or ch in "_:")) else "_"
        for ch in name
    )
    if out.endswith("_s"):
        out = out[:-2] + "_seconds"
    if out and out[0].isdigit():
        out = "_" + out
    return out


def _escape_label(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _fmt_labels(items: Iterable[Tuple[str, str]], extra: str = "") -> str:
    parts = [f'{k}="{_escape_label(str(v))}"' for k, v in items]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


def _fmt_float(v: float) -> str:
    if v == math.inf:
        return "+Inf"
    return repr(float(v))


def _fmt_exemplar(ex: Optional[Tuple[str, float]]) -> str:
    """OpenMetrics exemplar suffix for a ``_bucket`` line, or "" when
    the bucket never saw an exemplar-carrying observation — keeping
    exemplar-free expositions byte-identical to the pre-exemplar
    format (the golden file relies on this)."""
    if ex is None:
        return ""
    key, v = ex
    return f' # {{key="{_escape_label(key)}"}} {_fmt_float(v)}'


def render_prometheus(
    counters_obj=None, hists: Optional[Histograms] = None
) -> str:
    """The whole registry — counters, gauges, histograms — as Prometheus
    text exposition (version 0.0.4).  Deterministic ordering so the
    golden-file test is byte-stable."""
    from minisched_tpu.observability import counters as counters_mod

    c = counters_obj if counters_obj is not None else counters_mod.GLOBAL
    h = hists if hists is not None else GLOBAL
    gauges = c.gauge_names()
    lines: List[str] = []
    for name, val in sorted(c.snapshot().items()):
        mname = _metric_name(name)
        kind = "gauge" if name in gauges else "counter"
        lines.append(f"# TYPE {mname} {kind}")
        lines.append(f"{mname} {val}")
    with h._mu:
        keys = sorted(h._hists.keys())
        children = [(k, h._hists[k]) for k in keys]
    seen_type = set()
    for (name, labels), child in children:
        mname = _metric_name(name)
        if mname not in seen_type:
            seen_type.add(mname)
            lines.append(f"# TYPE {mname} histogram")
        snap = child.snapshot()
        exemplars = snap["exemplars"]
        cum = 0
        for i, n in enumerate(snap["counts"]):
            cum += n
            le = 'le="%s"' % _fmt_float(BUCKET_BOUNDS[i])
            lines.append(
                f"{mname}_bucket{_fmt_labels(labels, extra=le)} {cum}"
                + _fmt_exemplar(exemplars.get(i))
            )
        cum += snap["overflow"]
        inf_le = 'le="+Inf"'
        lines.append(
            f"{mname}_bucket{_fmt_labels(labels, extra=inf_le)} {cum}"
            + _fmt_exemplar(exemplars.get(NBUCKETS))
        )
        lines.append(
            f"{mname}_sum{_fmt_labels(labels)} {_fmt_float(snap['sum'])}"
        )
        lines.append(f"{mname}_count{_fmt_labels(labels)} {snap['count']}")
    return "\n".join(lines) + "\n"


# -- minimal parser (the scrape consumer's half) ----------------------------

def _label_block_end(s: str) -> int:
    """Index of the ``}`` closing a label block that starts at ``s[0]``'s
    level — quote-aware, so escaped quotes and braces inside label
    values don't end the block early."""
    i, in_quote = 0, False
    while i < len(s):
        ch = s[i]
        if in_quote:
            if ch == "\\":
                i += 1
            elif ch == '"':
                in_quote = False
        elif ch == '"':
            in_quote = True
        elif ch == "}":
            return i
        i += 1
    return len(s)


def _parse_labels(s: str) -> Dict[str, str]:
    """Parse ``k="v",k2="v2"`` honoring \\\\, \\" and \\n escapes."""
    out: Dict[str, str] = {}
    i, n = 0, len(s)
    while i < n:
        j = s.index("=", i)
        key = s[i:j].strip().lstrip(",").strip()
        assert s[j + 1] == '"', f"unquoted label value at {s[j:]}"
        i = j + 2
        buf: List[str] = []
        while s[i] != '"':
            if s[i] == "\\":
                nxt = s[i + 1]
                buf.append({"\\": "\\", '"': '"', "n": "\n"}[nxt])
                i += 2
            else:
                buf.append(s[i])
                i += 1
        out[key] = "".join(buf)
        i += 1  # closing quote
        while i < n and s[i] in ", ":
            i += 1
    return out


def parse_prometheus(
    text: str,
) -> Tuple[Dict[str, str], List[Tuple[str, Dict[str, str], float]]]:
    """Minimal exposition parser: returns ``(types, samples)`` where
    types maps metric name → counter|gauge|histogram and samples is
    ``[(name, labels, value)]`` in document order.  Enough to validate
    a scrape, pretty-print a snapshot, and round-trip the golden file —
    deliberately not a full OpenMetrics implementation."""
    types: Dict[str, str] = {}
    samples: List[Tuple[str, Dict[str, str], float]] = []
    for raw in text.splitlines():
        line = raw.strip()
        if not line:
            continue
        if line.startswith("#"):
            parts = line.split(None, 3)
            if len(parts) >= 4 and parts[1] == "TYPE":
                types[parts[2]] = parts[3]
            continue
        if "{" in line:
            name = line[: line.index("{")]
            rest = line[line.index("{") + 1 :]
            i = _label_block_end(rest)
            labels = _parse_labels(rest[:i])
            val = rest[i + 1 :].strip()
        else:
            name, val = line.split(None, 1)
            labels = {}
        # an OpenMetrics exemplar (`` # {…} v``) may trail a _bucket
        # sample; it is annotation, not part of the sample value
        if " # " in val:
            val = val.split(" # ", 1)[0].strip()
        samples.append((name, labels, float(val)))
    return types, samples


def parse_exemplars(
    text: str,
) -> List[Tuple[str, Dict[str, str], Dict[str, str], float]]:
    """OpenMetrics exemplars from an exposition, in document order:
    ``[(sample name, sample labels, exemplar labels, exemplar value)]``.
    Kept separate from :func:`parse_prometheus` so its (types, samples)
    contract — and every existing consumer — stays untouched."""
    out: List[Tuple[str, Dict[str, str], Dict[str, str], float]] = []
    for raw in text.splitlines():
        line = raw.strip()
        if not line or line.startswith("#") or " # {" not in line:
            continue
        sample, ex = line.split(" # {", 1)
        j = _label_block_end(ex)
        ex_labels = _parse_labels(ex[:j])
        ex_val = float(ex[j + 1 :].strip().split()[0])
        if "{" in sample:
            name = sample[: sample.index("{")]
            rest = sample[sample.index("{") + 1 :]
            k = _label_block_end(rest)
            labels = _parse_labels(rest[:k])
        else:
            name = sample.split()[0]
            labels = {}
        out.append((name, labels, ex_labels, ex_val))
    return out


def parsed_histogram_quantile(
    samples: List[Tuple[str, Dict[str, str], float]],
    metric: str,
    q: float,
) -> Optional[Tuple[float, float]]:
    """Quantile bounds recomputed from PARSED ``_bucket`` samples —
    the scrape-side mirror of :meth:`Histograms.quantile_bounds`, used
    by the smoke tool and the CLI pretty-printer."""
    # merge cumulative buckets across label children: le → summed count
    by_le: Dict[float, float] = {}
    for name, labels, val in samples:
        if name != metric + "_bucket":
            continue
        le = labels.get("le", "")
        by_le[math.inf if le == "+Inf" else float(le)] = (
            by_le.get(math.inf if le == "+Inf" else float(le), 0.0) + val
        )
    if not by_le:
        return None
    bounds = sorted(by_le)
    total = by_le[bounds[-1]]
    if total <= 0:
        return None
    rank = max(1.0, math.ceil(q * total))
    lo = 0.0
    for b in bounds:
        if by_le[b] >= rank:
            return lo, b
        lo = b
    return lo, math.inf
