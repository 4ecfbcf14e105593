"""InterPodAffinity: required/preferred pod (anti-)affinity, both directions.

Re-creates the in-tree ``interpodaffinity`` plugin from the reference's
default roster (scheduler/scheduler_test.go:307-332; default score weight
1) — the pod↔pod×node coupling plugin (BASELINE config 4).  Semantics
follow upstream v1.22:

* Filter rejects a node when (1) one of the pod's required anti-affinity
  terms has a matching assigned pod in the node's topology domain, (2) an
  *assigned* pod's required anti-affinity term matches the incoming pod
  and the node shares that pod's topology domain (the reverse direction),
  or (3) a required affinity term is unsatisfied — no matching pod in the
  domain, except the bootstrap case: the pod matches its own term selector
  and NO pod matches cluster-wide, in which case any node carrying the
  topology key qualifies.
* Score sums weight × (matching pods in the node's domain) over the pod's
  preferred terms (anti-affinity terms contribute negative weight), PLUS
  the symmetric direction: every *assigned* pod's preferred affinity
  (+w) / anti-affinity (−w) terms and required affinity terms (at
  ``HARD_POD_AFFINITY_WEIGHT``) score toward an incoming pod matching
  them, over the assigned pod's topology domain.  The total then min-max
  normalizes to [0, 100].

Batch form (models/constraints.py): gathers of ``combo_dsum`` rows, one
bool matmul for the reverse required-anti direction
(``pod_matches_combo @ combo_excl``), and one int matmul
(``pod_matches_combo @ rev_weight``) for the symmetric scoring — all
MXU-shaped at scale, both over the combo axis.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax.numpy as jnp

from minisched_tpu.framework.events import ActionType, ClusterEvent, GVK
from minisched_tpu.framework.nodeinfo import NodeInfo
from minisched_tpu.framework.plugin import BatchEvaluable, Plugin
from minisched_tpu.framework.types import (
    CycleState,
    MAX_NODE_SCORE,
    NodeScoreList,
    Status,
)
from minisched_tpu.models.constraints import _matches, _term_namespaces
from minisched_tpu.plugins.normalize import (
    minmax_normalize_batch,
    minmax_normalize_scalar,
)

NAME = "InterPodAffinity"
PRE_FILTER_KEY = "PreFilter" + NAME
PRE_SCORE_KEY = "PreScore" + NAME

REASON_AFFINITY = "node(s) didn't match pod affinity rules"
REASON_ANTI = "node(s) didn't satisfy existing pods anti-affinity rules"


def _assigned_pods(node_infos: List[NodeInfo]) -> List[Any]:
    out = []
    for ni in node_infos:
        out.extend(ni.pods)
    return out


def _domain_counts(term, pod_ns: str, node_infos: List[NodeInfo]):
    """(counts per topo value, global count) of assigned pods matching the
    term's selector in the term's namespaces."""
    nss = _term_namespaces(term, pod_ns)
    counts: Dict[str, int] = {}
    total = 0
    for ni in node_infos:
        val = ni.node.metadata.labels.get(term.topology_key)
        for p in ni.pods:
            if _matches(term.label_selector, nss, p):
                total += 1
                if val is not None:
                    counts[val] = counts.get(val, 0) + 1
    return counts, total


class _Normalize:
    """Upstream interpodaffinity NormalizeScore: min-max to [0, 100]; all
    equal → 0."""

    def normalize_score(self, state: CycleState, pod: Any, scores: NodeScoreList) -> Status:
        minmax_normalize_scalar(scores, reverse=False, fill=0)
        return Status.success()


class InterPodAffinity(Plugin, BatchEvaluable):
    needs_extra = True
    #: which coupling planes the sequential scan must carry for this
    #: plugin (ops/sequential.py): the combo aggregates
    scan_carried_planes = ("combos",)

    def name(self) -> str:
        return NAME

    # -- scalar ------------------------------------------------------------
    def pre_filter(
        self, state: CycleState, pod: Any, node_infos: List[NodeInfo]
    ) -> Status:
        ns = pod.metadata.namespace
        aff = pod.spec.affinity
        pa = aff.pod_affinity if aff is not None else None
        pan = aff.pod_anti_affinity if aff is not None else None

        aff_terms = []  # (term, counts, global, self_match)
        for term in pa.required if pa is not None else ():
            counts, total = _domain_counts(term, ns, node_infos)
            nss = _term_namespaces(term, ns)
            aff_terms.append(
                (term, counts, total, _matches(term.label_selector, nss, pod))
            )
        anti_terms = []  # (term, counts)
        for term in pan.required if pan is not None else ():
            counts, _ = _domain_counts(term, ns, node_infos)
            anti_terms.append((term, counts))

        # reverse direction: assigned pods' required anti-affinity terms
        # that match the incoming pod → forbidden (topo_key, value) pairs
        forbidden: set = set()
        for ni in node_infos:
            for q in ni.pods:
                qaff = q.spec.affinity
                qpan = qaff.pod_anti_affinity if qaff is not None else None
                for term in qpan.required if qpan is not None else ():
                    nss = _term_namespaces(term, q.metadata.namespace)
                    if not _matches(term.label_selector, nss, pod):
                        continue
                    val = ni.node.metadata.labels.get(term.topology_key)
                    if val is not None:
                        forbidden.add((term.topology_key, val))

        state.write(PRE_FILTER_KEY, (aff_terms, anti_terms, forbidden))
        return Status.success()

    def filter(self, state: CycleState, pod: Any, node_info: NodeInfo) -> Status:
        aff_terms, anti_terms, forbidden = state.read(PRE_FILTER_KEY)
        labels = node_info.node.metadata.labels
        for key, val in forbidden:
            if labels.get(key) == val:
                return Status.unresolvable(REASON_ANTI).with_plugin(NAME)
        for term, counts in anti_terms:
            val = labels.get(term.topology_key)
            if val is not None and counts.get(val, 0) > 0:
                return Status.unresolvable(REASON_ANTI).with_plugin(NAME)
        for term, counts, total, self_match in aff_terms:
            val = labels.get(term.topology_key)
            satisfied = val is not None and (
                counts.get(val, 0) > 0 or (total == 0 and self_match)
            )
            if not satisfied:
                return Status.unschedulable(REASON_AFFINITY).with_plugin(NAME)
        return Status.success()

    def pre_score(self, state: CycleState, pod: Any, nodes: List[Any]) -> Status:
        from minisched_tpu.models.constraints import rev_pref_terms_of

        ns = pod.metadata.namespace
        node_infos = state.read("nodeinfos")
        aff = pod.spec.affinity
        weighted = []  # (topo_key, counts, signed weight)
        if aff is not None and aff.pod_affinity is not None:
            for wt in aff.pod_affinity.preferred:
                counts, _ = _domain_counts(wt.term, ns, node_infos)
                weighted.append((wt.term.topology_key, counts, wt.weight))
        if aff is not None and aff.pod_anti_affinity is not None:
            for wt in aff.pod_anti_affinity.preferred:
                counts, _ = _domain_counts(wt.term, ns, node_infos)
                weighted.append((wt.term.topology_key, counts, -wt.weight))
        # symmetric direction: assigned pods' preferred/hard-affinity terms
        # that match THIS pod score over the assigned pod's topology domain
        sym: Dict[Tuple[str, str], int] = {}  # (topo_key, value) → Σ w
        for ni in node_infos:
            labels = ni.node.metadata.labels
            for q in ni.pods:
                for nss, sel, topo, w in rev_pref_terms_of(q):
                    if not _matches(sel, nss, pod):
                        continue
                    val = labels.get(topo)
                    if val is not None:
                        sym[(topo, val)] = sym.get((topo, val), 0) + w
        state.write(PRE_SCORE_KEY, (weighted, sym))
        return Status.success()

    def score(self, state: CycleState, pod: Any, node_name: str) -> Tuple[int, Status]:
        weighted, sym = state.read(PRE_SCORE_KEY)
        ni: NodeInfo = state.read("nodeinfo/" + node_name)
        labels = ni.node.metadata.labels
        total = 0
        for topo_key, counts, w in weighted:
            val = labels.get(topo_key)
            if val is not None:
                total += w * counts.get(val, 0)
        for (topo_key, val), w in sym.items():
            if labels.get(topo_key) == val:
                total += w
        return total, Status.success()

    def score_extensions(self):
        return _Normalize()

    def events_to_register(self) -> List[ClusterEvent]:
        return [
            ClusterEvent(GVK.POD, ActionType.ALL),
            ClusterEvent(GVK.NODE, ActionType.ADD | ActionType.UPDATE_NODE_LABEL),
        ]

    # -- batch -------------------------------------------------------------
    def batch_filter(self, ctx: Any, pods: Any, nodes: Any, extra: Any):
        if extra is None:
            raise ValueError(
                "InterPodAffinity batch kernels need the wave's "
                "ConstraintTables (models/constraints.py) — pass `extra`"
            )
        # reverse direction: one bool matmul over the combo axis.  A row
        # of combo_excl is the domains owned by the required anti-affinity
        # terms of the pods that are placed, and of those committed
        # EARLIER IN THIS SCAN (the sequential engine adds them as it
        # goes); all-zero, and folded, where no such term is about
        rev = (
            jnp.einsum(
                "pc,cn->pn",
                extra.pod_matches_combo.astype(jnp.int32),
                extra.combo_excl.astype(jnp.int32),
            )
            > 0
        )  # (P, N)

        # incoming required anti-affinity
        pan_in = (
            jnp.arange(extra.pan_combo.shape[1])[None, :] < extra.pan_n[:, None]
        )  # (P, A)
        pan_dsum = extra.combo_dsum[extra.pan_combo]  # (P, A, N)
        anti_viol = jnp.any((pan_dsum > 0) & pan_in[:, :, None], axis=1)

        # incoming required affinity (+ bootstrap special case)
        pa_in = (
            jnp.arange(extra.pa_combo.shape[1])[None, :] < extra.pa_n[:, None]
        )
        pa_dsum = extra.combo_dsum[extra.pa_combo]  # (P, A, N)
        pa_haskey = extra.combo_haskey[extra.pa_combo]
        pa_glob = extra.combo_global[extra.pa_combo]  # (P, A)
        bootstrap = (pa_glob == 0) & extra.pa_self  # (P, A)
        sat = (pa_dsum > 0) | (bootstrap[:, :, None] & pa_haskey)
        aff_ok = jnp.all(sat | ~pa_in[:, :, None], axis=1)

        return ~rev & ~anti_viol & aff_ok

    def batch_score(self, ctx: Any, pods: Any, nodes: Any, aux: Dict[str, Any],
                    extra: Any):
        if extra is None:
            raise ValueError(
                "InterPodAffinity batch kernels need the wave's "
                "ConstraintTables (models/constraints.py) — pass `extra`"
            )
        in_range = (
            jnp.arange(extra.ppa_combo.shape[1])[None, :] < extra.ppa_n[:, None]
        )  # (P, W)
        dsum = extra.combo_dsum[extra.ppa_combo]  # (P, W, N)
        haskey = extra.combo_haskey[extra.ppa_combo]
        contrib = extra.ppa_w[:, :, None] * jnp.where(haskey, dsum, 0)
        incoming = jnp.sum(
            jnp.where(in_range[:, :, None], contrib, 0), axis=1
        )
        # symmetric direction: assigned (and scan-committed) pods' terms
        # scoring toward matching incoming pods — one int matmul over the
        # combo axis (rev_weight rows are zero for combos with no such
        # terms, so plain clusters add nothing)
        sym = jnp.einsum(
            "pc,cn->pn",
            extra.pod_matches_combo.astype(jnp.int32),
            extra.rev_weight,
        )
        return (incoming + sym).astype(jnp.int32)

    def batch_normalize(self, ctx: Any, scores, mask):
        return minmax_normalize_batch(scores, mask, reverse=False, fill=0)
