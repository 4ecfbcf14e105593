"""Cross-pod constraint tables: the pod↔pod×node coupling arrays.

InterPodAffinity and PodTopologySpread couple pending pods to *assigned*
pods through label selectors and topology domains — the scheduling analog
of attention's token↔token coupling (SURVEY.md §5.7, §7 stage 8).  The
TPU-native factoring separates the two halves:

* **Host side** (this module): every distinct (namespaces, label-selector,
  topology-key) triple appearing in the wave's constraints becomes a
  **combo**; assigned pods are matched against each combo ONCE, and the
  per-node domain sums land in a dense ``combo_dsum[C, N]`` matrix.  The
  reverse direction (assigned pods' required anti-affinity) is a combo
  too: the term's (namespaces, selector, topology key) is the row, and
  ``combo_excl[C, N]`` is set over the domains its owners occupy — the
  plane the sequential scan already carries for the pods it commits, so
  placed owners and owners committed a step ago ban through one matmul
  (``pod_matches_combo @ combo_excl``) and no axis follows how many
  nodes are occupied.

* **Device side** (plugins/interpodaffinity.py, podtopologyspread.py):
  kernels only gather combo rows and reduce — no string or object work.
  The reverse anti-affinity check is one bool matmul over the combo axis
  (MXU-shaped).

Semantics follow upstream v1.22 ``interpodaffinity`` / ``podtopologyspread``
(the reference's default roster enables both — scheduler_test.go:307-332),
including the affinity bootstrap special case (a pod matching its own
affinity term may land anywhere with the topology key when no pod matches
cluster-wide), spread's eligible-node gating, and SYMMETRIC preferred-term
scoring: assigned pods' preferred (and hard-weighted required) affinity
terms score toward incoming pods that match them, via the ``rev_weight``
plane (one ``pod_matches_combo @ rev_weight`` matmul on device).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from minisched_tpu.api.objects import LabelSelector, PodAffinityTerm
from minisched_tpu.models.tables import _register_table, pad_to
from minisched_tpu.observability import counters, profiling

MAX_VOLUMES = 4  # PVC references per pod
MAX_TSC = 4  # topology spread constraints per pod
MAX_PA = 4  # required pod-affinity terms per pod
MAX_PAN = 4  # required pod-anti-affinity terms per pod
MAX_PPA = 8  # preferred (anti-)affinity terms per pod, both signs pooled

#: topology keys used by DoNotSchedule spread constraints must either have
#: at most this many distinct values (zone-like) or be unique-per-node
#: (hostname-like) — the two real-world shapes.  The one-hot domain
#: encoding the eligibility-aware filter kernel needs is O(D × N) per key.
MAX_DOMAINS = 64

TS_DO_NOT_SCHEDULE = 0
TS_SCHEDULE_ANYWAY = 1

#: the combo-row fill of ``build_constraint_tables``, on ``/metrics`` from boot
profiling.register_spans("constraints.combo_rows")

#: packed-schema elision groups for the scan lane (pack_table
#: ``elide_groups``): columns whose zero-ness is a property of the
#: chunk's WORKLOAD, not of cluster state — each group elides as a unit
#: only when every member is all-zero, so e.g. a spread-only burst ships
#: no affinity/volume columns and XLA folds those whole per-step lanes
#: out of the blocked-scan program.  Gating counts (``*_n``) are members,
#: so zero-materialized values always read as "no constraints"
#: (TS_DO_NOT_SCHEDULE == 0 is safe: ``ts_n`` == 0 masks every slot).
SCAN_ELIDE_GROUPS = (
    (
        "pan_combo", "pan_n", "ppa_combo", "ppa_w", "ppa_n",
        "pa_combo", "pa_self", "pa_n",
    ),
    (
        "pod_claims", "pod_claim_valid", "pod_n_vols", "pod_vols_fam",
        "pod_missing", "claim_mask", "claim_zone_ok", "claim_cnt",
        "claim_family", "claim_ro",
    ),
    ("ts_combo", "ts_skew", "ts_mode", "ts_n"),
)

#: smallest capacity of the combo/claim/volume axes — every
#: distinct padded size is a separate compiled executable (see the combo
#: matrices comment in build_constraint_tables)
CAP_QUANTUM = 32
#: each further capacity of those axes is this many times the one below
CAP_TIER_FACTOR = 8


def cap_tier(n: int) -> int:
    """Capacity of a content-driven axis that holds ``n`` live rows: the
    smallest of 32, 256, 2048, ... that does.  The pod axis' discipline
    (_scan_cap: two capacities, _blocked_cap: three tiers) for the axes
    whose length follows what a build holds: padded to the next multiple
    of 32, 255 pending services met eight sizes of the combo axis between
    1 and 255, each a program of its own times the lanes' pod tiers and
    the later ones first met mid-run; a tier is left only by growing
    eightfold, so a run meets one or two, early.  Padded rows are all
    zero: they never match and never count."""
    cap = CAP_QUANTUM
    while cap < n:
        cap *= CAP_TIER_FACTOR
    return cap


@_register_table
@dataclass
class ConstraintTables:
    """Device-side cross-pod coupling state for one wave."""

    # per-combo (selector-group × topology-key), shape (C, N) / (C,)
    combo_dsum: Any  # i32[C, N] matching assigned pods in n's topo domain
    combo_haskey: Any  # bool[C, N] node carries the combo's topology key
    combo_global: Any  # i32[C] matching assigned pods cluster-wide
    combo_here: Any  # i32[C, N] matching assigned pods ON node n
    combo_key: Any  # i32[C] index into the topology-key axis below
    # per-topology-key domain encoding (spread's eligibility-aware filter:
    # upstream counts domains only over nodes passing the pod's
    # nodeSelector/required affinity, so domain sums are per-pod on device)
    topo_domain: Any  # i32[K, N] dense domain id; == D sentinel when keyless
    topo_onehot: Any  # bool[K, D, N] node ∈ domain d of key k (zone-like keys)
    topo_unique: Any  # bool[K] key is unique-per-node (hostname-like)
    # incoming pods' topology spread constraints
    ts_combo: Any  # i32[P, MAX_TSC]
    ts_skew: Any  # i32[P, MAX_TSC] max skew
    ts_mode: Any  # i32[P, MAX_TSC] 0=DoNotSchedule 1=ScheduleAnyway
    ts_n: Any  # i32[P]
    # incoming pods' required pod affinity
    pa_combo: Any  # i32[P, MAX_PA]
    pa_self: Any  # bool[P, MAX_PA] pod matches its own term selector
    pa_n: Any  # i32[P]
    # incoming pods' required pod anti-affinity
    pan_combo: Any  # i32[P, MAX_PAN]
    pan_n: Any  # i32[P]
    # incoming pods' preferred terms (weight < 0 encodes anti-affinity)
    ppa_combo: Any  # i32[P, MAX_PPA]
    ppa_w: Any  # i32[P, MAX_PPA]
    ppa_n: Any  # i32[P]
    # symmetric preferred scoring (upstream v1.22 interpodaffinity
    # PreScore): assigned pods' preferred affinity (+w) / anti-affinity
    # (−w) terms and required affinity terms (×HARD_POD_AFFINITY_WEIGHT),
    # accumulated as signed weight over the owner's topology domain per
    # combo.  Scored as pod_matches_combo @ rev_weight (one int matmul).
    rev_weight: Any  # i32[C, N] Σ signed term weights whose domain holds n
    # which pending pods match each combo's selector (the sequential
    # scan's commits update the combo aggregates with it; a wave matches
    # only the combos the two reverse planes use), and the reverse
    # direction of required anti-affinity: the domains that assigned
    # pods' terms own, to which the scan (ops/sequential.py) adds those
    # of the pods it commits
    pod_matches_combo: Any  # bool[P, C]
    combo_excl: Any  # bool[C, N] matching pods banned (an owner's
    #                  anti-affinity domain)
    # volume coupling (VolumeBinding / NodeVolumeLimits)
    claim_mask: Any  # bool[C2, N] nodes OK for referenced claim c (bound
    #                  PV's node labels, or ∃ bindable free PV)
    pod_claims: Any  # i32[P, MAX_VOLUMES] indices into claim_mask
    vol_ok: Any  # bool[P] every referenced PVC exists
    pod_n_vols: Any  # i32[P] volumes this pod mounts
    # volume roster planes (VolumeZone / VolumeRestrictions / limit family)
    claim_zone_ok: Any  # bool[C2, N] bound PV's zone labels match node
    pod_vols_fam: Any  # i32[P, F] pod's DISTINCT volumes per driver family
    #                    (+ unresolvable mounts, counted generic per-mount)
    node_vols_fam: Any  # i32[F, N] distinct assigned volumes per family
    # per-volume mount state, one row per counting key — a bound claim's
    # PersistentVolume, or an unbound claim itself (claims bound to one PV
    # share a row; upstream's attach limits count unique volumes, not
    # mounts).  The repair loop carries vol_any/vol_rw across rounds so
    # intra-wave conflicts are enforced, not just assigned-pod ones.
    # Row Vd-1 is a dummy scatter target.
    claim_vol: Any  # i32[C2] volume row of claim c; -1 when unbound
    #                 (VolumeRestrictions: conflicts need a PV identity)
    claim_cnt: Any  # i32[C2] counting row of claim c (always >= 0)
    claim_family: Any  # i32[C2] driver family of claim c
    claim_ro: Any  # bool[C2] the claim mounts its volume read-only
    pod_claim_valid: Any  # bool[P, MAX_VOLUMES] slot holds a real claim
    pod_missing: Any  # i32[P] mounts whose PVC doesn't exist (generic)
    vol_any: Any  # bool[Vd, N] some assigned pod on n mounts volume v
    vol_rw: Any  # bool[Vd, N] ... with a writable mount


def combo_rows(tables: Any) -> int:
    """Rows of the combo axis ``C`` of built tables, packed or on device."""
    if isinstance(tables, ConstraintTables):
        return tables.combo_global.shape[0]
    return next(
        shape[0]
        for name, _kind, shape in tables.metas + tables.zero_metas
        if name == "combo_global"
    )


#: field → (kind, axis-role) — the ONE authority on how each plane is laid
#: out: "first"/pods = leading pod dim, "last"/nodes = trailing node dim,
#: "rep" = small metadata.  parallel/sharding.py turns this into mesh
#: shardings; ops/sequential.py uses the pod-axis set to slice per-pod rows.
CONSTRAINT_AXES = {
    "combo_dsum": ("last", "nodes"),
    "combo_haskey": ("last", "nodes"),
    "combo_here": ("last", "nodes"),
    "combo_global": ("rep", None),
    "combo_key": ("rep", None),
    "topo_domain": ("last", "nodes"),
    "topo_onehot": ("last", "nodes"),
    "topo_unique": ("rep", None),
    "rev_weight": ("last", "nodes"),
    "pod_matches_combo": ("first", "pods"),
    "combo_excl": ("last", "nodes"),
    "claim_mask": ("last", "nodes"),
    "claim_zone_ok": ("last", "nodes"),
    "node_vols_fam": ("last", "nodes"),
    "pod_vols_fam": ("first", "pods"),
    "claim_vol": ("rep", None),
    "claim_cnt": ("rep", None),
    "claim_family": ("rep", None),
    "claim_ro": ("rep", None),
    "pod_claim_valid": ("first", "pods"),
    "pod_missing": ("first", "pods"),
    "vol_any": ("last", "nodes"),
    "vol_rw": ("last", "nodes"),
    # per-pod constraint rows (default shape: leading pod dim)
    "ts_combo": ("first", "pods"),
    "ts_skew": ("first", "pods"),
    "ts_mode": ("first", "pods"),
    "ts_n": ("first", "pods"),
    "pa_combo": ("first", "pods"),
    "pa_self": ("first", "pods"),
    "pa_n": ("first", "pods"),
    "pan_combo": ("first", "pods"),
    "pan_n": ("first", "pods"),
    "ppa_combo": ("first", "pods"),
    "ppa_w": ("first", "pods"),
    "ppa_n": ("first", "pods"),
    "pod_claims": ("first", "pods"),
    "vol_ok": ("first", "pods"),
    "pod_n_vols": ("first", "pods"),
}

#: fields with a leading pod dimension (sliced per step by the scan)
POD_AXIS_FIELDS = tuple(
    name for name, (kind, _) in CONSTRAINT_AXES.items() if kind == "first"
)

#: fields the sequential scan carries and updates as pods commit
SCAN_CARRIED_FIELDS = (
    "combo_dsum", "combo_here", "combo_global", "combo_excl", "rev_weight",
    "vol_any", "vol_rw", "node_vols_fam",
)

#: upstream HardPodAffinityWeight default (scheduler API defaulting): the
#: weight at which EXISTING pods' required affinity terms score toward an
#: incoming pod that matches them (symmetric hard-affinity scoring)
HARD_POD_AFFINITY_WEIGHT = 1


def rev_pref_terms_of(p: Any):
    """The (namespaces, selector, topology-key, signed weight) stream of an
    ASSIGNED pod's scoring-relevant terms toward future incoming pods —
    upstream v1.22 interpodaffinity's symmetric PreScore set: preferred
    affinity (+w), preferred anti-affinity (−w), required affinity
    (×HARD_POD_AFFINITY_WEIGHT).  ONE definition shared by the from-scratch
    walk, the incremental index, and the scalar plugin."""
    aff = p.spec.affinity
    if aff is None:
        return
    ns = p.metadata.namespace
    pa = aff.pod_affinity
    if pa is not None:
        for term in pa.required:
            yield (
                _term_namespaces(term, ns), term.label_selector,
                term.topology_key, HARD_POD_AFFINITY_WEIGHT,
            )
        for wt in pa.preferred:
            yield (
                _term_namespaces(wt.term, ns), wt.term.label_selector,
                wt.term.topology_key, wt.weight,
            )
    pan = aff.pod_anti_affinity
    if pan is not None:
        for wt in pan.preferred:
            yield (
                _term_namespaces(wt.term, ns), wt.term.label_selector,
                wt.term.topology_key, -wt.weight,
            )


def rev_excl_terms_of(p: Any):
    """The (namespaces, selector, topology-key) stream of an ASSIGNED
    pod's required anti-affinity terms: what it bans from its own
    topology domains (the reverse direction of the filter).  Shared by
    the from-scratch walk and the incremental index."""
    aff = p.spec.affinity
    if aff is None or aff.pod_anti_affinity is None:
        return
    ns = p.metadata.namespace
    for term in aff.pod_anti_affinity.required:
        yield _term_namespaces(term, ns), term.label_selector, term.topology_key


def _selector_sig(sel: LabelSelector) -> Tuple:
    return (
        tuple(sorted(sel.match_labels.items())),
        tuple(
            (r.key, r.operator, tuple(r.values)) for r in sel.match_expressions
        ),
    )


def _term_namespaces(term: PodAffinityTerm, pod_ns: str) -> Tuple[str, ...]:
    return tuple(sorted(term.namespaces)) if term.namespaces else (pod_ns,)


class _ComboRegistry:
    def __init__(self):
        self.ids: Dict[Tuple, int] = {}
        self.combos: List[Tuple[Tuple[str, ...], LabelSelector, str]] = []

    def get(self, namespaces: Tuple[str, ...], sel: LabelSelector, topo: str) -> int:
        key = (namespaces, _selector_sig(sel), topo)
        if key not in self.ids:
            self.ids[key] = len(self.combos)
            self.combos.append((namespaces, sel, topo))
        return self.ids[key]


@dataclass(frozen=True)
class KeyAxis:
    """One topology key's node side, its rows padded to the node capacity:
    all the constraint build reads of the nodes' labels under that key.
    The arrays are read-only (a cached axis is shared by later builds)."""

    values: Dict[str, int]  # label value → id, in the order nodes meet them
    val_id: Any  # i32[N] node i's value id, −1 where it lacks the key
    domain: Any  # i32[N] the key's topo_domain row (D where keyless)
    onehot: Any  # bool[D, N] for a key that is not unique, else None
    unique: bool  # every keyed node holds a value of its own (hostname)


def _key_axis(key: str, nodes: Sequence[Any], N: int) -> KeyAxis:
    """Dense domain encoding of one topology key.  A key whose
    cardinality exceeds MAX_DOMAINS must be unique-per-node
    (hostname-like) — its one-hot plane is unused (the kernel
    short-circuits to per-node counts); anything in between raises.
    ``val_id`` is the host-side gather axis that lets the combo planes
    fill without a per-combo × per-node Python loop."""
    values: Dict[str, int] = {}
    row = [
        -1 if v is None else values.setdefault(v, len(values))
        for v in (node.metadata.labels.get(key) for node in nodes)
    ]
    val_id = np.full(N, -1, np.int32)
    val_id[: len(row)] = row
    keyed = val_id >= 0
    n_domains = len(values)
    unique = n_domains == int(keyed.sum()) and n_domains > 0
    if n_domains > MAX_DOMAINS and not unique:
        raise ValueError(
            f"topology key {key!r}: {n_domains} domains exceed "
            f"MAX_DOMAINS={MAX_DOMAINS} and the key is not unique-per-node"
        )
    D = MAX_DOMAINS
    domain = np.full(N, D, np.int32)
    onehot = None
    if unique:
        domain[keyed] = 0  # unused by the unique path; != D marks haskey
    else:
        domain[keyed] = val_id[keyed]
        onehot = np.zeros((D, N), bool)
        onehot[val_id[keyed], np.flatnonzero(keyed)] = True
        onehot.flags.writeable = False
    val_id.flags.writeable = False
    domain.flags.writeable = False
    return KeyAxis(values, val_id, domain, onehot, unique)


def _axis_planes(axes: Sequence[KeyAxis], N: int) -> Tuple[Any, Any, Any]:
    """(topo_domain i32[K, N], topo_onehot bool[K, D, N], topo_unique
    bool[K]) of a build's keys, in the order of ``axes``."""
    # K is an executable shape too — quantize to 4 so adding a second
    # topology key doesn't recompile (the onehot plane costs K×D×N bools)
    K = pad_to(max(len(axes), 1), 4)
    topo_domain = np.full((K, N), MAX_DOMAINS, np.int32)
    topo_onehot = np.zeros((K, MAX_DOMAINS, N), bool)
    topo_unique = np.zeros(K, bool)
    for k, ax in enumerate(axes):
        topo_domain[k] = ax.domain
        topo_unique[k] = ax.unique
        if ax.onehot is not None:
            topo_onehot[k] = ax.onehot
    return topo_domain, topo_onehot, topo_unique


class TopoAxisCache:
    """The node side of the topology-key axis, derived once a node roster.

    What a constraint build reads of the nodes — their name → index map
    and each topology key's ``KeyAxis`` — depends on the nodes' names,
    order and labels only, so it is kept while the roster stands: a scan
    call over 5,000 nodes then reads no label in Python.  ``roster`` is
    the caller's version of the node list (``CachedNodeTableBuilder
    .roster_epoch``, which moves whenever a node's ``resource_version``
    or the membership changes); a new one, or a new node capacity, drops
    everything.  Axes are kept per KEY, so lanes that build with
    different keys (the wave with none, a scan with one) share them, and
    the assembled planes per key set.  An axis is kept only once derived:
    a key over MAX_DOMAINS raises on every build that meets it.  The wave
    build worker and the loop thread both build, hence the lock.

    Counters (registered at 0 with the device engine,
    ``counters.AXIS_COUNTERS``): ``constraints.axis_built`` /
    ``constraints.axis_reused``, once a key a build."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._roster: Any = None
        self._node_idx: Optional[Dict[str, int]] = None
        self._axes: Dict[str, KeyAxis] = {}
        self._planes: Dict[Tuple[str, ...], Tuple[Any, Any, Any]] = {}

    def _stand(self, roster: Any, N: int) -> None:
        """Drop what another roster or capacity left (lock held)."""
        if self._roster != (roster, N):
            self._roster = (roster, N)
            self._node_idx = None
            self._axes = {}
            self._planes = {}

    def node_index(self, roster: Any, nodes: Sequence[Any], N: int) -> Dict[str, int]:
        """Node name → index (shared: the caller must not mutate it)."""
        with self._lock:
            self._stand(roster, N)
            if self._node_idx is None:
                self._node_idx = {n.metadata.name: i for i, n in enumerate(nodes)}
            return self._node_idx

    def axes(
        self, roster: Any, nodes: Sequence[Any], keys: Tuple[str, ...], N: int
    ) -> Tuple[List[KeyAxis], Tuple[Any, Any, Any]]:
        """The keys' axes and their assembled, read-only planes."""
        with self._lock:
            self._stand(roster, N)
            axes = []
            for key in keys:
                ax = self._axes.get(key)
                if ax is None:
                    ax = self._axes[key] = _key_axis(key, nodes, N)
                    counters.inc("constraints.axis_built")
                else:
                    counters.inc("constraints.axis_reused")
                axes.append(ax)
            planes = self._planes.get(keys)
            if planes is None:
                planes = _axis_planes(axes, N)
                for a in planes:
                    a.flags.writeable = False
                self._planes[keys] = planes
            return axes, planes


def _matches(sel: LabelSelector, namespaces: Tuple[str, ...], pod: Any) -> bool:
    return pod.metadata.namespace in namespaces and sel.matches(pod.metadata.labels)


def _sig_groups(pods: Sequence[Any]):
    """Group pods by their (namespace, labels) signature.

    Selector matching is a pure function of that signature, and real
    populations are replica sets — thousands of pods collapse to a
    handful of signatures, so selector × pod matching can run selector ×
    GROUP (the per-combo fold over assumed/pending pods was ~0.5s per
    scan chunk at 32 combos × 16k pods).  Returns (representative pods,
    int32 group id per pod)."""
    group_of: Dict[Tuple, int] = {}
    reps: List[Any] = []
    ids = np.empty(len(pods), np.int32)
    for i, p in enumerate(pods):
        sig = (
            p.metadata.namespace,
            tuple(sorted(p.metadata.labels.items())),
        )
        g = group_of.get(sig)
        if g is None:
            g = group_of[sig] = len(reps)
            reps.append(p)
        ids[i] = g
    return reps, ids


def _claim_zone_row(pvc: Any, pv_by_name: Dict, nodes: Sequence[Any], zone_ok) -> List[bool]:
    """VolumeZone's per-node verdict for one claim: unbound claims pass
    everywhere (VolumeBinding owns them), a dangling volume_name passes
    nowhere, bound claims defer to the plugin's pv_zone_ok."""
    if not pvc.spec.volume_name:
        return [True] * len(nodes)
    pv = pv_by_name.get(pvc.spec.volume_name)
    if pv is None:
        return [False] * len(nodes)
    return [zone_ok(pv, n) for n in nodes]


def build_constraint_tables(
    pending_pods: Sequence[Any],
    nodes: Sequence[Any],
    assigned_pods: Sequence[Any],
    pod_capacity: Optional[int] = None,
    node_capacity: Optional[int] = None,
    pvcs: Sequence[Any] = (),
    pvs: Sequence[Any] = (),
    scan_planes: bool = True,
    index: Any = None,
    extra_assigned: Sequence[Any] = (),
    device: bool = True,
    elide_zeros: bool = True,
    elide_groups: Tuple[Tuple[str, ...], ...] = (),
    combo_capacity: int = 0,
    axis_cache: Optional[TopoAxisCache] = None,
    roster: Any = None,
):
    """Build the wave's coupling tables.

    ``nodes`` must be in the SAME order as the NodeTable build (name-sorted)
    so node indices line up.  ``assigned_pods`` are pods with
    ``spec.node_name`` set; others are ignored.  ``pvcs``/``pvs`` feed the
    volume coupling planes (VolumeBinding / NodeVolumeLimits).

    ``scan_planes``: build ``pod_matches_combo`` (the O(P × selector-groups)
    pending-pod matching the sequential scan's commit updates need).  On by
    default — all-False would silently break scan parity — wave-only
    callers (DeviceScheduler, bench wave paths) pass False to skip the
    host-side matching cost.

    ``combo_capacity``: the fewest rows the combo axis may have, for a
    caller that keeps the largest capacity its builds have reached (the
    engine's scan lanes: a build that holds few combos after one that held
    many runs the program that is there, see ``combo_rows``).

    ``index``: a ``constraint_index.ConstraintIndex`` — the assigned-pod
    planes then come from its event-maintained aggregates in
    O(nonzero + planes) instead of walking ``assigned_pods`` (pass ``()``).
    ``extra_assigned``: assigned pods the index hasn't seen yet (the
    engine's still-assumed binds), folded through the same per-pod logic
    the from-scratch walk uses.

    ``axis_cache`` / ``roster``: a ``TopoAxisCache`` and the version of
    ``nodes`` it is read under — the node index and the topology-key axis
    then come from the cache while the roster stands, instead of from a
    walk over every node's labels; the tables are the same.
    """
    P = pod_capacity or pad_to(len(pending_pods))
    N = node_capacity or pad_to(len(nodes))
    if axis_cache is not None:
        node_idx = axis_cache.node_index(roster, nodes, N)
    else:
        node_idx = {n.metadata.name: i for i, n in enumerate(nodes)}
    assigned = [p for p in assigned_pods if p.spec.node_name in node_idx]
    if index is not None:
        # the fold below re-applies the from-scratch per-pod logic to just
        # these; pods on nodes outside this wave's view are skipped the
        # same way the assigned filter above skips them
        extra_assigned = [
            p for p in extra_assigned if p.spec.node_name in node_idx
        ]

    reg = _ComboRegistry()
    # sparse rows: (pod index, row) only for pods that CARRY cross-pod
    # constraints — a plain 16k-pod wave walked three O(P) loops doing
    # nothing per pod (~150ms/wave of host time at config5 scale)
    pod_rows: List[Tuple[int, Dict[str, List]]] = []
    for pi, pod in enumerate(pending_pods):
        aff = pod.spec.affinity
        if not pod.spec.topology_spread_constraints and (
            aff is None
            or (aff.pod_affinity is None and aff.pod_anti_affinity is None)
        ):
            continue
        row: Dict[str, List] = {"ts": [], "pa": [], "pan": [], "ppa": []}
        ns = pod.metadata.namespace
        for c in pod.spec.topology_spread_constraints:
            cid = reg.get((ns,), c.label_selector, c.topology_key)
            mode = (
                TS_DO_NOT_SCHEDULE
                if c.when_unsatisfiable == "DoNotSchedule"
                else TS_SCHEDULE_ANYWAY
            )
            row["ts"].append((cid, c.max_skew, mode))
        aff = pod.spec.affinity
        if aff is not None and aff.pod_affinity is not None:
            for term in aff.pod_affinity.required:
                nss = _term_namespaces(term, ns)
                cid = reg.get(nss, term.label_selector, term.topology_key)
                row["pa"].append((cid, _matches(term.label_selector, nss, pod)))
            for wt in aff.pod_affinity.preferred:
                nss = _term_namespaces(wt.term, ns)
                cid = reg.get(nss, wt.term.label_selector, wt.term.topology_key)
                row["ppa"].append((cid, wt.weight))
        if aff is not None and aff.pod_anti_affinity is not None:
            for term in aff.pod_anti_affinity.required:
                nss = _term_namespaces(term, ns)
                cid = reg.get(nss, term.label_selector, term.topology_key)
                row["pan"].append(cid)
            for wt in aff.pod_anti_affinity.preferred:
                nss = _term_namespaces(wt.term, ns)
                cid = reg.get(nss, wt.term.label_selector, wt.term.topology_key)
                row["ppa"].append((cid, -wt.weight))
        for kind, cap in (("ts", MAX_TSC), ("pa", MAX_PA), ("pan", MAX_PAN),
                          ("ppa", MAX_PPA)):
            if len(row[kind]) > cap:
                raise ValueError(
                    f"pod {pod.metadata.name}: >{cap} {kind} constraints"
                )
        pod_rows.append((pi, row))

    # --- reverse contributions (assigned pods' terms) ----------------------
    # symmetric preferred scoring: term → topology value → Σ signed weight;
    # reverse required anti-affinity: term → topology value → owners.
    # Their combos register here too, in the order of their keys (the
    # from-scratch walk and the index meet them in different orders), so C
    # covers them before the matrices are allocated: one row a distinct
    # TERM, whatever the cluster holds
    rev_by_key: Dict[Tuple, Tuple[LabelSelector, Dict[str, int]]] = {}
    excl_by_key: Dict[Tuple, Tuple[LabelSelector, Dict[str, int]]] = {}

    def _note(by_key, key: Tuple, sel: LabelSelector, val: str, w: int):
        ent = by_key.get(key)
        if ent is None:
            ent = by_key[key] = (sel, {})
        ent[1][val] = ent[1].get(val, 0) + w

    def _collect_rev(p: Any) -> None:
        labels = nodes[node_idx[p.spec.node_name]].metadata.labels
        for nss, sel, topo, w in rev_pref_terms_of(p):
            val = labels.get(topo)
            if val is not None:  # else no domain to score
                _note(rev_by_key, (nss, _selector_sig(sel), topo), sel, val, w)
        for nss, sel, topo in rev_excl_terms_of(p):
            val = labels.get(topo)
            if val is not None:  # else the term can't be violated
                _note(excl_by_key, (nss, _selector_sig(sel), topo), sel, val, 1)

    if index is not None:
        for src, by_key in (
            (index.rev_pref_list(), rev_by_key),
            (index.rev_excl_list(), excl_by_key),
        ):
            for key, sel_obj, vals in src:
                by_key[key] = (sel_obj, vals)  # each key once, a copy
        for p in extra_assigned:
            _collect_rev(p)
    else:
        for p in assigned:
            _collect_rev(p)
    rev_vals: Dict[int, Dict[str, int]] = {}  # cid → value → Σ weight
    excl_vals: Dict[int, Dict[str, int]] = {}  # cid → value → owners
    for by_key, by_cid in ((rev_by_key, rev_vals), (excl_by_key, excl_vals)):
        for key in sorted(by_key, key=repr):
            sel, vals = by_key[key]
            by_cid[reg.get(key[0], sel, key[2])] = vals

    # --- combo matrices ----------------------------------------------------
    # C/C2/Vd are EXECUTABLE shapes — a build whose combo count steps
    # over a capacity recompiles the whole evaluator mid-run.  cap_tier
    # keeps one shape up to 32 combos and one more for every eightfold
    # growth, at the cost of spare (all-zero) planes.
    C = max(cap_tier(len(reg.combos)), combo_capacity)
    if scan_planes:
        # the scan lanes' builds say how full the combo axis is
        counters.inc("scan.combos_live", len(reg.combos))
        counters.inc("scan.combos_total", C)
    combo_dsum = np.zeros((C, N), np.int32)
    combo_haskey = np.zeros((C, N), bool)
    combo_global = np.zeros(C, np.int32)
    combo_here = np.zeros((C, N), np.int32)
    combo_key = np.zeros(C, np.int32)
    keys = tuple(sorted({topo for (_, _, topo) in reg.combos}))
    key_ids = {k: i for i, k in enumerate(keys)}
    if axis_cache is not None:
        axes, (topo_domain, topo_onehot, topo_unique) = axis_cache.axes(
            roster, nodes, keys, N
        )
    else:
        axes = [_key_axis(k, nodes, N) for k in keys]
        topo_domain, topo_onehot, topo_unique = _axis_planes(axes, N)
    pod_matches_combo = np.zeros((P, C), bool)
    combo_excl = np.zeros((C, N), bool)
    rev_weight = np.zeros((C, N), np.int32)
    # scan mode matches every combo (commits update aggregates with it);
    # wave mode matches only the rev-active combos — the symmetric score
    # and the reverse anti-affinity ban need "does this pending pod match
    # the assigned pod's term", and a wave over a cluster with no such
    # terms pays nothing
    match_combos = (
        range(len(reg.combos)) if scan_planes
        else sorted(rev_vals.keys() | excl_vals.keys())
    )
    if match_combos:
        # combos sharing (namespaces, selector) across topology keys match
        # identically — compute each distinct group once, against pod
        # SIGNATURES rather than pods (replicas share label sets)
        p_reps, p_gid = _sig_groups(pending_pods)
        match_cache: Dict[Tuple, Any] = {}
        for cid in match_combos:
            nss, sel, _topo = reg.combos[cid]
            mkey = (nss, _selector_sig(sel))
            row = match_cache.get(mkey)
            if row is None:
                grp = np.fromiter(
                    (_matches(sel, nss, r) for r in p_reps),
                    dtype=bool,
                    count=len(p_reps),
                )
                row = match_cache[mkey] = grp[p_gid]
            pod_matches_combo[: len(pending_pods), cid] = row
    n_real = len(nodes)
    # assumed/assigned-pod fold by signature group: sig → {node: count} —
    # each combo then matches the handful of signatures, not every pod.
    # With an index the planes already hold the indexed population, so
    # only the assume-cache extras fold here; without one, all assigned.
    _fold_src = extra_assigned if index is not None else assigned
    a_reps, a_nodes = [], []
    if _fold_src:
        a_reps, a_gid = _sig_groups(_fold_src)
        a_nodes = [dict() for _ in a_reps]
        for g, p in zip(a_gid, _fold_src):
            d = a_nodes[g]
            node = p.spec.node_name
            d[node] = d.get(node, 0) + 1
    # the combo rows, forward and reverse: one row a distinct
    # (namespaces, selector, topology key), its counts from the index and
    # its bans from the placed owners' domains
    with profiling.span("constraints.combo_rows", n=len(reg.combos)):
        for cid, (nss, sel, topo) in enumerate(reg.combos):
            k = key_ids[topo]
            combo_key[cid] = k
            # (node index, matching pods) pairs of the pods this combo counts
            here_i: List[int] = []
            here_n: List[int] = []
            if index is not None:
                # O(nonzero): per-node counts from the index, assumed pods
                # folded through the same matcher; domain sums derive from the
                # CURRENT node labels so label churn self-heals
                here = index.combo_aggregate(nss, sel, topo)
                for g, rep in enumerate(a_reps):
                    if _matches(sel, nss, rep):
                        for node, cnt in a_nodes[g].items():
                            here[node] = here.get(node, 0) + cnt
                for node_name, cnt in here.items():
                    i = node_idx.get(node_name)
                    if i is not None:  # else the node is outside this view
                        here_i.append(i)
                        here_n.append(cnt)
            else:
                for g, rep in enumerate(a_reps):
                    if not _matches(sel, nss, rep):
                        continue
                    for node, cnt in a_nodes[g].items():
                        here_i.append(node_idx[node])
                        here_n.append(cnt)
            hi = np.asarray(here_i, np.intp)
            hn = np.asarray(here_n, np.int32)
            np.add.at(combo_here[cid], hi, hn)
            combo_global[cid] = hn.sum()
            # haskey/dsum/rev/excl rows as gathers through the node→value-id
            # axis (a per-combo × per-node Python loop here cost ~1s per scan
            # chunk at 32 combos × 10k nodes)
            rv = rev_vals.get(cid)
            ev = excl_vals.get(cid)
            vid = axes[k].val_id[:n_real]  # (n_real,) value id, -1 absent
            has = vid >= 0
            combo_haskey[cid, :n_real] = has
            vals_k = axes[k].values
            safe_vid = np.where(has, vid, 0)
            h_vid = vid[hi]
            h_keyed = h_vid >= 0
            if h_keyed.any():
                cnt_by_vid = np.zeros(max(len(vals_k), 1), np.int32)
                np.add.at(cnt_by_vid, h_vid[h_keyed], hn[h_keyed])
                combo_dsum[cid, :n_real] = np.where(
                    has, cnt_by_vid[safe_vid], 0
                )
            if rv:
                rw_by_vid = np.zeros(max(len(vals_k), 1), np.int32)
                for val, w in rv.items():
                    vi = vals_k.get(val)
                    if vi is not None:
                        rw_by_vid[vi] = w
                rev_weight[cid, :n_real] = np.where(
                    has, rw_by_vid[safe_vid], 0
                )
            if ev:
                ban_by_vid = np.zeros(max(len(vals_k), 1), bool)
                for val in ev:
                    vi = vals_k.get(val)
                    if vi is not None:
                        ban_by_vid[vi] = True
                combo_excl[cid, :n_real] = has & ban_by_vid[safe_vid]
    if scan_planes:
        # how much of the cluster the reverse direction bans
        counters.inc("scan.excl_terms", len(excl_vals))
        counters.inc("scan.excl_nodes", int(combo_excl.sum()))
        counters.inc("scan.excl_capacity", len(excl_vals) * n_real)
        # how much of the cluster the pending pods' required affinity
        # terms admit
        aff_cids = sorted({cid for _pi, row in pod_rows for cid, _self in row["pa"]})
        counters.inc(
            "scan.aff_nodes",
            int((combo_haskey[aff_cids] & (combo_dsum[aff_cids] > 0)).sum()),
        )
        counters.inc("scan.aff_capacity", len(aff_cids) * n_real)

    # --- volume coupling ---------------------------------------------------
    # feasibility semantics come from ONE place each — the VolumeBinding /
    # VolumeZone / VolumeRestrictions / volume-limit plugins — so the
    # host-side tables can never drift from the scalar filters
    from minisched_tpu.plugins.volumebinding import claim_node_mask
    from minisched_tpu.plugins.volumelimits import FAMILIES, volume_family
    from minisched_tpu.plugins.volumezone import pv_zone_ok

    pvc_by_key = {pvc.metadata.key: pvc for pvc in pvcs}
    pv_by_name = {pv.metadata.name: pv for pv in pvs}
    # claims mounted by assigned pods, grouped per node (restriction and
    # family counting both walk these) — skipped on the index path, which
    # supplies the equivalent per-node aggregates below
    node_claims: List[List[Any]] = [[] for _ in range(len(nodes))]
    if index is None:
        for p in assigned:
            for vol in p.spec.volumes:
                opvc = pvc_by_key.get(f"{p.metadata.namespace}/{vol}")
                node_claims[node_idx[p.spec.node_name]].append(opvc)

    # counting key of a claim: its bound PV, else the claim itself —
    # upstream's attach limits count unique VOLUMES, so claims sharing a
    # PV share a row (tuple-keyed to keep the two namespaces apart)
    def count_key(pvc: Any) -> Tuple[str, str]:
        if pvc.spec.volume_name:
            return ("pv", pvc.spec.volume_name)
        return ("pvc", pvc.metadata.key)

    vol_ids: Dict[Tuple[str, str], int] = {}  # counting key → vol-plane row

    def vol_id(key: Tuple[str, str]) -> int:
        if key not in vol_ids:
            vol_ids[key] = len(vol_ids)
        return vol_ids[key]

    claim_ids: Dict[str, int] = {}
    claim_rows: List[List[bool]] = []
    zone_rows: List[List[bool]] = []
    claim_vol_l: List[int] = []
    claim_cnt_l: List[int] = []
    claim_fam_l: List[int] = []
    claim_ro_l: List[bool] = []
    vol_ok = np.zeros(P, bool)
    pod_claims = np.zeros((P, MAX_VOLUMES), np.int32)
    pod_claim_valid = np.zeros((P, MAX_VOLUMES), bool)
    pod_missing = np.zeros(P, np.int32)
    pod_n_vols = np.zeros(P, np.int32)
    F = len(FAMILIES)
    pod_vols_fam = np.zeros((P, F), np.int32)
    # a pod with no volumes trivially passes (ok=True, zero counts) — only
    # volume-carrying pods pay the per-claim walk
    vol_ok[: len(pending_pods)] = True
    for i, pod in enumerate(pending_pods):
        vols = pod.spec.volumes
        if not vols:
            continue
        if len(vols) > MAX_VOLUMES:
            raise ValueError(f"pod {pod.metadata.name}: >{MAX_VOLUMES} volumes")
        pod_n_vols[i] = len(vols)
        ok = True
        seen_keys: set = set()
        for j, vol in enumerate(vols):
            key = f"{pod.metadata.namespace}/{vol}"
            if key not in pvc_by_key:
                ok = False
                pod_missing[i] += 1
                pod_vols_fam[i, volume_family(None, pv_by_name)] += 1
                continue
            pvc = pvc_by_key[key]
            ck = count_key(pvc)
            if ck not in seen_keys:  # distinct volumes, not mounts
                seen_keys.add(ck)
                pod_vols_fam[i, volume_family(pvc, pv_by_name)] += 1
            if key not in claim_ids:
                claim_ids[key] = len(claim_rows)
                claim_rows.append(claim_node_mask(pvc, pvs, nodes))
                zone_rows.append(_claim_zone_row(pvc, pv_by_name, nodes, pv_zone_ok))
                row = vol_id(ck)
                claim_cnt_l.append(row)
                claim_vol_l.append(row if pvc.spec.volume_name else -1)
                claim_fam_l.append(volume_family(pvc, pv_by_name))
                claim_ro_l.append(pvc.spec.read_only)
            pod_claims[i, j] = claim_ids[key]
            pod_claim_valid[i, j] = True
        vol_ok[i] = ok
    C2 = cap_tier(len(claim_rows))
    claim_mask = np.zeros((C2, N), bool)
    claim_zone_ok = np.zeros((C2, N), bool)
    claim_vol = np.full(C2, -1, np.int32)
    claim_cnt = np.zeros(C2, np.int32)
    claim_family = np.zeros(C2, np.int32)
    claim_ro = np.zeros(C2, bool)
    for cid, row in enumerate(claim_rows):
        claim_mask[cid, : len(row)] = row
        claim_zone_ok[cid, : len(row)] = zone_rows[cid]
        claim_vol[cid] = claim_vol_l[cid]
        claim_cnt[cid] = claim_cnt_l[cid]
        claim_family[cid] = claim_fam_l[cid]
        claim_ro[cid] = claim_ro_l[cid]
    # per-volume mount state from assigned pods: one pre-pass over node
    # claims (O(assigned mounts)), rows only for volumes the wave's claims
    # reference; last row stays a dummy scatter target
    Vd = cap_tier(len(vol_ids) + 1)
    vol_any = np.zeros((Vd, N), bool)
    vol_rw = np.zeros((Vd, N), bool)
    node_vols_fam = np.zeros((F, N), np.int32)
    if index is not None:
        # O(nonzero): the index's per-node volume state, assumed pods
        # folded through the wave's own PVC/PV view
        nvs = index.node_vol_state()
        for p in extra_assigned:
            nv = nvs.setdefault(p.spec.node_name, {})
            for j, vol in enumerate(p.spec.volumes):
                opvc = pvc_by_key.get(f"{p.metadata.namespace}/{vol}")
                if opvc is None:
                    ent = nv.setdefault(
                        ("miss", p.metadata.uid, j),
                        [0, 0, volume_family(None, pv_by_name)],
                    )
                    ent[0] += 1
                    continue
                ck = count_key(opvc)
                fam = volume_family(opvc, pv_by_name)
                ent = nv.setdefault(ck, [0, 0, fam])
                ent[0] += 1
                ent[2] = fam
                if opvc.spec.volume_name and not opvc.spec.read_only:
                    ent[1] += 1
        for node_name, entries in nvs.items():
            n = node_idx.get(node_name)
            if n is None:
                continue
            for vk, (mounts, rw_mounts, fam) in entries.items():
                if mounts <= 0:
                    continue
                node_vols_fam[fam, n] += 1  # distinct volumes per node
                v = vol_ids.get(vk)
                if v is not None:
                    vol_any[v, n] = True
                    if rw_mounts > 0:
                        vol_rw[v, n] = True
    else:
        for n, claims in enumerate(node_claims):
            seen_node: set = set()
            for opvc in claims:
                if opvc is None:
                    # no identity: each unresolvable mount counts by itself
                    node_vols_fam[0, n] += 1
                    continue
                ck = count_key(opvc)
                if ck not in seen_node:  # distinct volumes per node
                    seen_node.add(ck)
                    node_vols_fam[volume_family(opvc, pv_by_name), n] += 1
                v = vol_ids.get(ck)
                if v is not None:
                    vol_any[v, n] = True
                    if opvc.spec.volume_name and not opvc.spec.read_only:
                        vol_rw[v, n] = True

    # --- per-pod constraint arrays ----------------------------------------
    ts_combo = np.zeros((P, MAX_TSC), np.int32)
    ts_skew = np.zeros((P, MAX_TSC), np.int32)
    ts_mode = np.zeros((P, MAX_TSC), np.int32)
    ts_n = np.zeros(P, np.int32)
    pa_combo = np.zeros((P, MAX_PA), np.int32)
    pa_self = np.zeros((P, MAX_PA), bool)
    pa_n = np.zeros(P, np.int32)
    pan_combo = np.zeros((P, MAX_PAN), np.int32)
    pan_n = np.zeros(P, np.int32)
    ppa_combo = np.zeros((P, MAX_PPA), np.int32)
    ppa_w = np.zeros((P, MAX_PPA), np.int32)
    ppa_n = np.zeros(P, np.int32)
    for i, row in pod_rows:
        for j, (cid, skew, mode) in enumerate(row["ts"]):
            ts_combo[i, j], ts_skew[i, j], ts_mode[i, j] = cid, skew, mode
        ts_n[i] = len(row["ts"])
        for j, (cid, self_match) in enumerate(row["pa"]):
            pa_combo[i, j], pa_self[i, j] = cid, self_match
        pa_n[i] = len(row["pa"])
        for j, cid in enumerate(row["pan"]):
            pan_combo[i, j] = cid
        pan_n[i] = len(row["pan"])
        for j, (cid, w) in enumerate(row["ppa"]):
            ppa_combo[i, j], ppa_w[i, j] = cid, w
        ppa_n[i] = len(row["ppa"])

    # one batched transfer (per-array device_put dispatches a transfer
    # per column); device=False instead returns the still-on-host
    # PackedTable for consumers that unpack inside their own program
    # (ops/repair packed mode: one dispatch per wave, no separate
    # splitter program alternating with the evaluator)
    from minisched_tpu.models.tables import batched_device_put, pack_table

    host_cols = dict(
            combo_dsum=combo_dsum, combo_haskey=combo_haskey,
            combo_global=combo_global, combo_here=combo_here,
            combo_key=combo_key, topo_domain=topo_domain,
            topo_onehot=topo_onehot, topo_unique=topo_unique,
            ts_combo=ts_combo, ts_skew=ts_skew, ts_mode=ts_mode, ts_n=ts_n,
            pa_combo=pa_combo, pa_self=pa_self, pa_n=pa_n,
            pan_combo=pan_combo, pan_n=pan_n,
            ppa_combo=ppa_combo, ppa_w=ppa_w, ppa_n=ppa_n,
            pod_matches_combo=pod_matches_combo, combo_excl=combo_excl,
            rev_weight=rev_weight,
            claim_mask=claim_mask, pod_claims=pod_claims, vol_ok=vol_ok,
            pod_n_vols=pod_n_vols,
            claim_zone_ok=claim_zone_ok,
            pod_vols_fam=pod_vols_fam, node_vols_fam=node_vols_fam,
            claim_vol=claim_vol, claim_cnt=claim_cnt,
            claim_family=claim_family, claim_ro=claim_ro,
            pod_claim_valid=pod_claim_valid, pod_missing=pod_missing,
            vol_any=vol_any, vol_rw=vol_rw,
        )
    if not device:
        # elide_zeros=False callers (the scan lane) trade wire bytes for
        # ONE packed schema per capacity: with elision, every distinct
        # zero-set is a fresh consumer executable, and the scan's planes
        # flip zero/nonzero mid-run (combo counts appear after the first
        # commits) — each flip is a full scan-program compile or cache
        # load.  Waves keep elision: plain waves elide everything and
        # their schema is stable.  elide_groups (SCAN_ELIDE_GROUPS) is
        # the scan lane's bounded middle ground: per-WORKLOAD zero
        # groups (affinity terms, pod volumes) elide as units, folding
        # their whole per-step compute lanes for e.g. spread-only bursts.
        return pack_table(
            host_cols, (), P,
            elide_zeros=elide_zeros, elide_groups=elide_groups,
        )
    return ConstraintTables(**batched_device_put(host_cols))
