"""Incremental assigned-pod aggregates for the cross-pod constraint planes.

``build_constraint_tables`` derives every assigned-pod plane (combo
``here``/``global``/domain sums, the reverse anti-affinity bans, the
volume mount/family state) by walking the FULL assigned-pod population —
O(cluster) host Python per wave.  That is the reference's own per-cycle
re-list pattern one layer up (``minisched/minisched.go:40`` — SURVEY.md
§7's "#1 pattern not to copy"), and at 10k×100k it charged every wave
~200ms regardless of what changed.

``ConstraintIndex`` maintains the same aggregates from informer events —
O(changes), exactly like the NodeInfo cache (engine/cache.py) — and
``build_constraint_tables(..., index=...)`` assembles the dense planes
from it in O(nonzero + planes).  The engine folds still-assumed pods
(binds whose events haven't landed) in at assemble time, under one hold
of the index lock so no event can land between the membership check and
the aggregate reads; the fold re-applies the from-scratch per-pod logic
and the randomized equivalence suite (tests/test_constraint_index.py)
is the drift tripwire between the two paths.

Growth bound: the combo registry keeps every distinct (namespaces,
selector, topology-key) group ever seen by a wave, and each assigned-pod
event matches against every GROUP (selector-deduped).  Real rosters
reuse a handful of selectors, so groups plateau; per-claim volume maps
are pruned when their last pod leaves, and so are the reverse
anti-affinity owner values (one entry a (term, occupied domain): under a
hostname key one for every occupied node, dropped when its last owner is
deleted).

Consistency model (same as the NodeInfo cache): the index is updated on
the informer dispatch thread; reads see event-stream state plus the
fold-in of assumed pods.  Self-healing derivations keep label churn
correct without rescans:

* combo domain sums are derived at assemble time from the ``here`` dicts
  plus the CURRENT node labels (a node changing its zone moves its counts
  automatically);
* reverse anti-affinity owner domains are re-resolved when the owner
  node's labels change (the node-update handler re-adds affected pods);
* PVC bind / PV create events re-resolve the volume records of the pods
  referencing them (a claim's counting identity switches from the claim
  to its bound PV — upstream counts unique volumes).

Registry ids are index-private; ``build_constraint_tables`` keeps its
wave-local combo ids and queries by structural key.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Set, Tuple

from minisched_tpu.api.objects import LabelSelector
from minisched_tpu.observability import counters

# the ONE definition of combo/term identity — shared with the from-scratch
# walk so the two paths cannot drift on key shape
from minisched_tpu.models.constraints import (
    _matches,
    _selector_sig,
    rev_excl_terms_of,
    rev_pref_terms_of,
)

#: combo key: (namespaces, selector signature, topology key)
ComboKey = Tuple[Tuple[str, ...], Tuple, str]
#: volume counting key: ("pv", volume_name) | ("pvc", claim_key) |
#: ("miss", pod_uid, slot)
VolKey = Tuple


class _SigMeta:
    __slots__ = ("namespace", "labels")

    def __init__(self, namespace: str, labels: Dict[str, str]):
        self.namespace = namespace
        self.labels = labels


class _SigRep:
    """namespace/labels shim standing in for every pod sharing a label
    signature in selector matching — ``_matches`` reads only
    ``pod.metadata.namespace`` and ``.labels``, and retaining a real pod
    object here would pin its whole spec/status past removal."""

    __slots__ = ("metadata",)

    def __init__(self, namespace: str, labels: Dict[str, str]):
        self.metadata = _SigMeta(namespace, labels)


class _PodRecord:
    """What one assigned pod contributed — enough to subtract it again
    without re-matching (labels may have changed since)."""

    __slots__ = (
        "node", "sig", "excl", "vols", "claims", "has_anti", "rev",
    )

    def __init__(self, node: str):
        self.node = node
        #: the pod's label-signature id — combo membership lives at the
        #: SIGNATURE level (``_sig_combos``), not per record: replica
        #: populations collapse to a handful of signatures, so selector
        #: matching (per add and per new-combo backfill) runs against
        #: signatures instead of pods
        self.sig: int = -1
        #: reverse required anti-affinity: (ComboKey, owner topo value) per
        #: term of this assigned pod whose node carries the topology key
        self.excl: List[Tuple[ComboKey, str]] = []
        #: (VolKey, family, rw) per mount — one entry per spec.volumes slot
        self.vols: List[Tuple[VolKey, int, bool]] = []
        #: referenced claim keys (for PVC/PV re-resolution)
        self.claims: List[str] = []
        #: pod carries node-label-SENSITIVE terms (required anti-affinity
        #: owner domains, symmetric preferred/hard-affinity contributions)
        #: — node label changes (or the node's ADD arriving after the
        #: pod's, informers being separate dispatch threads) change them
        self.has_anti = False
        #: symmetric preferred contributions: (ComboKey, owner topo value,
        #: signed weight) per scoring-relevant term of this assigned pod
        self.rev: List[Tuple[ComboKey, str, int]] = []


class ConstraintIndex:
    def __init__(self) -> None:
        # REENTRANT: the engine holds it across a whole table assembly
        # (lock() below) while the read methods re-acquire it — a plain
        # lock would deadlock, and not holding it across the assembly
        # lets a bind land between the assumed-fold membership check and
        # the aggregate reads, counting the pod twice for that wave
        self._mu = threading.RLock()
        # persistent combo registry: key → id; per id the match group and
        # the per-node assigned-match counts
        self._combo_ids: Dict[ComboKey, int] = {}
        self._combo_sel: List[Tuple[Tuple[str, ...], LabelSelector]] = []
        self._combo_here: List[Dict[str, int]] = []
        # distinct (namespaces, selector-sig) match groups shared across
        # topology keys: group key → combo ids in the group (one match
        # test per GROUP per SIGNATURE, as the from-scratch builder does)
        self._group_ids: Dict[Tuple, List[int]] = {}
        # label-signature tables: selector matching is a pure function of
        # (namespace, labels), and real populations are replica sets —
        # deferring combo registration to a late wave used to backfill
        # each new combo over EVERY assigned pod (~1M matcher calls at
        # 100k pods × 32 combos); against signatures it's 32 × #sigs.
        # Signatures are REFCOUNTED and their ids recycled: populations
        # with per-pod-unique labels (StatefulSets' pod-name label) would
        # otherwise grow these tables one entry per pod ever assigned —
        # and the rep is a namespace/labels shim, never the pod object
        self._sig_ids: Dict[Tuple, int] = {}  # (ns, labels items) → sig id
        self._sig_rep: List[Optional[Any]] = []  # sig id → _SigRep | None
        self._sig_combos: List[List[int]] = []  # sig id → matching combo ids
        self._sig_nodes: List[Dict[str, int]] = []  # sig id → node → count
        self._sig_count: List[int] = []  # sig id → live records
        self._sig_key: List[Optional[Tuple]] = []  # sig id → _sig_ids key
        self._sig_free: List[int] = []  # recycled sig ids
        # reverse required anti-affinity: combo key → owner topo value →
        # assigned pods whose term owns that domain (the shape of
        # _rev_pref below: one entry a distinct TERM, its values dropped
        # at zero, so nothing here outlives the pods that put it there)
        self._rev_excl: Dict[ComboKey, Dict[str, int]] = {}
        self._excl_sel: Dict[ComboKey, LabelSelector] = {}
        # symmetric preferred scoring: combo key → owner topo value →
        # Σ signed weight of assigned pods' terms owning that domain
        self._rev_pref: Dict[ComboKey, Dict[str, int]] = {}
        self._rev_sel: Dict[ComboKey, LabelSelector] = {}
        # volume state: node → VolKey → [mounts, rw_mounts, family]
        self._node_vols: Dict[str, Dict[VolKey, List[int]]] = {}
        # claim key → uids of assigned pods mounting it (PVC/PV re-resolve)
        self._claim_pods: Dict[str, Set[str]] = {}
        # bound volume name → claim keys referencing it (PV events)
        self._vol_claims: Dict[str, Set[str]] = {}
        self._pods: Dict[str, Any] = {}  # uid → pod object
        self._records: Dict[str, _PodRecord] = {}
        # node → uids of pods with required anti-affinity ON that node —
        # the re-resolution set for node add/label events (O(affected),
        # never O(all records))
        self._node_anti: Dict[str, Set[str]] = {}
        # claim resolution source — the live PVC/PV listers, injected by
        # wire(); event handlers resolve through the informer cache so the
        # index sees the same objects the wave build does
        self._pvc_lister = None
        self._pv_lister = None

    # -- wiring ------------------------------------------------------------
    def wire(self, informer_factory: Any) -> None:
        """Register handlers.  MUST run BEFORE the NodeInfo cache's
        (engine/cache.py) so the index is never behind it: the engine
        prunes its assume-cache against the NodeInfo cache's view, and a
        pruned pod missing from the index would drop out of the planes
        for a wave.  Index-ahead is safe (the assumed fold checks index
        membership first)."""
        from minisched_tpu.controlplane.informer import ResourceEventHandlers

        def assigned(pod: Any) -> bool:
            return bool(pod.spec.node_name)

        pvc_inf = informer_factory.informer_for("PersistentVolumeClaim")
        pv_inf = informer_factory.informer_for("PersistentVolume")
        node_inf = informer_factory.informer_for("Node")
        # informer cache keys are "namespace/name"; cluster-scoped kinds
        # (Node, PV) key as "/<name>"
        self._pvc_lister = pvc_inf.get
        self._pv_lister = lambda name: pv_inf.get(f"/{name}")
        self._node_get = lambda name: node_inf.get(f"/{name}")
        informer_factory.informer_for("Pod").add_event_handlers(
            ResourceEventHandlers(on_batch=self._pod_batch)
        )
        informer_factory.informer_for("Node").add_event_handlers(
            ResourceEventHandlers(
                # ADD matters too: informers are separate dispatch threads,
                # so a pod's event can beat its node's — the owner labels
                # read empty and its ex-terms would be silently dropped
                on_add=lambda node: self.update_node(None, node),
                on_update=self.update_node,
            )
        )
        pvc_inf.add_event_handlers(
            ResourceEventHandlers(
                on_add=lambda pvc: self.claim_changed(pvc.metadata.key),
                on_update=lambda old, new: self.claim_changed(new.metadata.key),
                on_delete=lambda pvc: self.claim_changed(pvc.metadata.key),
            )
        )
        pv_inf.add_event_handlers(
            ResourceEventHandlers(
                on_add=lambda pv: self.volume_changed(pv.metadata.name),
                on_update=lambda old, new: self.volume_changed(new.metadata.name),
                on_delete=lambda pv: self.volume_changed(pv.metadata.name),
            )
        )

    # -- event handlers ----------------------------------------------------
    def _pod_batch(self, events: List[Any]) -> None:
        """Informer batch fast path: one lock hold for a whole wave's bind
        events.  Gates on assignment itself (batch handlers receive the
        raw batch; pending pods never touch the planes); errors are
        contained per event so one malformed object cannot drop the rest
        of the batch from the index."""
        from minisched_tpu.controlplane.store import EventType

        with self._mu:
            for ev in events:
                try:
                    if not ev.obj.spec.node_name:
                        continue
                    if ev.type == EventType.DELETED:
                        self._delete(ev.obj.metadata.uid)
                    elif ev.type == EventType.ADDED:
                        self._add(ev.obj)
                    else:
                        self._remove(ev.obj.metadata.uid)
                        self._add(ev.obj)
                except Exception:
                    import traceback

                    traceback.print_exc()

    def add_pod(self, pod: Any) -> None:
        with self._mu:
            self._add(pod)

    def update_pod(self, old: Any, new: Any) -> None:
        with self._mu:
            self._remove(new.metadata.uid)
            self._add(new)

    def delete_pod(self, pod: Any) -> None:
        with self._mu:
            self._delete(pod.metadata.uid)

    def _delete(self, uid: str) -> None:
        """An assigned pod went away (not a re-resolution, which removes
        and adds again): ``constraint_index.pods_removed`` counts those
        the index held."""
        if uid in self._records:
            counters.inc("constraint_index.pods_removed")
        self._remove(uid)

    def update_node(self, old: Any, new: Any) -> None:
        """A node's labels feed the reverse anti-affinity owner domains —
        re-resolve the anti-affinity pods on it.  (Combo domain sums
        self-heal: they are derived from CURRENT labels at assemble
        time.)"""
        if old is not None and old.metadata.labels == new.metadata.labels:
            return
        with self._mu:
            for uid in list(self._node_anti.get(new.metadata.name, ())):
                pod = self._pods.get(uid)
                if pod is not None:
                    self._remove(uid)
                    self._add(pod)

    def claim_changed(self, claim_key: str) -> None:
        """A PVC appeared / bound / changed — the counting identity and
        family of every mount referencing it may have moved."""
        with self._mu:
            self._reresolve_claims({claim_key})

    def volume_changed(self, pv_name: str) -> None:
        with self._mu:
            refs = self._vol_claims.get(pv_name)
            if refs is None:
                return
            # opportunistic sweep of claims no pod mounts anymore
            dead = {ck for ck in refs if not self._claim_pods.get(ck)}
            refs -= dead
            if not refs:
                del self._vol_claims[pv_name]
                return
            self._reresolve_claims(set(refs))

    def _reresolve_claims(self, claim_keys: Set[str]) -> None:
        uids: Set[str] = set()
        for ck in claim_keys:
            uids |= self._claim_pods.get(ck, set())
        for uid in uids:
            pod = self._pods.get(uid)
            if pod is not None:
                self._remove(uid)
                self._add(pod)

    # -- contribution maintenance (shared by events and the assumed fold) --
    def _lookup_pvc(self, key: str) -> Any:
        return self._pvc_lister(key) if self._pvc_lister is not None else None

    def _lookup_pv(self, name: str) -> Any:
        return self._pv_lister(name) if self._pv_lister is not None else None

    def _contribution(self, pod: Any) -> _PodRecord:
        """Compute the pod's record against the CURRENT registry and the
        live PVC/PV caches — the one place contribution math lives."""
        from minisched_tpu.plugins.volumelimits import volume_family

        rec = _PodRecord(pod.spec.node_name)
        # reverse required anti-affinity and symmetric preferred/hard-
        # affinity contributions (what this ASSIGNED pod bans and scores
        # toward future incoming pods) — term streams shared with the
        # from-scratch walk
        owner_labels = None
        for nss, sel, topo in rev_excl_terms_of(pod):
            rec.has_anti = True
            if owner_labels is None:
                # the owner's CURRENT node labels give the term's domain
                owner_labels = self._node_labels(pod.spec.node_name)
            owner_val = owner_labels.get(topo)
            if owner_val is None:
                continue  # owner's node lacks the key: can't be violated
            ck = (nss, _selector_sig(sel), topo)
            self._excl_sel.setdefault(ck, sel)
            rec.excl.append((ck, owner_val))
        for nss, sel, topo, w in rev_pref_terms_of(pod):
            # node-label-sensitive either way: a label change can grant or
            # revoke the owner's topology key — re-resolve on node events
            rec.has_anti = True
            if owner_labels is None:
                owner_labels = self._node_labels(pod.spec.node_name)
            owner_val = owner_labels.get(topo)
            if owner_val is None:
                continue  # owner's node lacks the key: no domain to score
            ck: ComboKey = (nss, _selector_sig(sel), topo)
            self._rev_sel.setdefault(ck, sel)
            rec.rev.append((ck, owner_val, w))
        uid = pod.metadata.uid
        for j, vol in enumerate(pod.spec.volumes):
            claim_key = f"{pod.metadata.namespace}/{vol}"
            rec.claims.append(claim_key)
            pvc = self._lookup_pvc(claim_key)
            if pvc is None:
                # no identity: each unresolvable mount counts by itself
                rec.vols.append((("miss", uid, j), 0, False))
                continue
            pv_by_name = _LazyPVMap(self._lookup_pv)
            fam = volume_family(pvc, pv_by_name)
            if pvc.spec.volume_name:
                vk: VolKey = ("pv", pvc.spec.volume_name)
                rw = not pvc.spec.read_only
            else:
                vk = ("pvc", claim_key)
                rw = False  # unbound: no PV identity to conflict on
            rec.vols.append((vk, fam, rw))
        # signature LAST (advisor r4): _sig_of creates a refcount-0
        # registry entry on first sight, and apply_events swallows
        # per-event exceptions — a raise in any step above would strand
        # the entry in _sig_ids/_sig_rep forever (only _remove releases).
        # Nothing above reads rec.sig, so creating it after every
        # fallible step means a failed _contribution mutates no
        # signature state.
        rec.sig = self._sig_of(pod)
        return rec

    def _sig_of(self, pod: Any) -> int:
        """The pod's label-signature id, creating (and combo-matching)
        the signature on first sight — every later pod with the same
        (namespace, labels) costs one dict lookup instead of a matcher
        call per selector group.  The caller (_add) owns the refcount."""
        key = (
            pod.metadata.namespace,
            tuple(sorted(pod.metadata.labels.items())),
        )
        sid = self._sig_ids.get(key)
        if sid is None:
            rep = _SigRep(pod.metadata.namespace, dict(pod.metadata.labels))
            cids: List[int] = []
            for gkey, ids in self._group_ids.items():
                nss, _sig = gkey
                sel = self._combo_sel[ids[0]][1]
                if _matches(sel, nss, rep):
                    cids.extend(ids)
            if self._sig_free:
                sid = self._sig_free.pop()
                self._sig_rep[sid] = rep
                self._sig_combos[sid] = cids
                self._sig_nodes[sid] = {}
                self._sig_count[sid] = 0
                self._sig_key[sid] = key
            else:
                sid = len(self._sig_rep)
                self._sig_rep.append(rep)
                self._sig_combos.append(cids)
                self._sig_nodes.append({})
                self._sig_count.append(0)
                self._sig_key.append(key)
            self._sig_ids[key] = sid
        return sid

    def _sig_release(self, sid: int) -> None:
        """Drop one reference; free and recycle the id at zero."""
        self._sig_count[sid] -= 1
        if self._sig_count[sid] <= 0:
            key = self._sig_key[sid]
            if key is not None:
                self._sig_ids.pop(key, None)
            self._sig_rep[sid] = None
            self._sig_combos[sid] = []
            self._sig_nodes[sid] = {}
            self._sig_key[sid] = None
            self._sig_free.append(sid)

    def _node_labels(self, node_name: str) -> Dict[str, str]:
        # set by wire(): the Node informer's get; absent in unit tests
        # that drive the index directly — they pass nodes via _node_get
        node = self._node_get(node_name) if self._node_get else None
        return node.metadata.labels if node is not None else {}

    _node_get = None  # injected by wire() below

    def _add(self, pod: Any) -> None:
        uid = pod.metadata.uid
        if uid in self._records:
            return  # duplicate event
        rec = self._contribution(pod)
        self._pods[uid] = pod
        self._records[uid] = rec
        node = rec.node
        for cid in self._sig_combos[rec.sig]:
            here = self._combo_here[cid]
            here[node] = here.get(node, 0) + 1
        sn = self._sig_nodes[rec.sig]
        sn[node] = sn.get(node, 0) + 1
        self._sig_count[rec.sig] += 1
        for ck, owner_val in rec.excl:
            vals = self._rev_excl.setdefault(ck, {})
            vals[owner_val] = vals.get(owner_val, 0) + 1
        for ck, owner_val, w in rec.rev:
            vals = self._rev_pref.setdefault(ck, {})
            vals[owner_val] = vals.get(owner_val, 0) + w
        if rec.vols:
            nv = self._node_vols.setdefault(node, {})
            for vk, fam, rw in rec.vols:
                ent = nv.get(vk)
                if ent is None:
                    ent = nv[vk] = [0, 0, fam]
                ent[0] += 1
                ent[1] += 1 if rw else 0
                ent[2] = fam
        for ck in rec.claims:
            self._claim_pods.setdefault(ck, set()).add(uid)
            pvc = self._lookup_pvc(ck)
            if pvc is not None and pvc.spec.volume_name:
                self._vol_claims.setdefault(pvc.spec.volume_name, set()).add(ck)
        if rec.has_anti:
            self._node_anti.setdefault(node, set()).add(uid)

    def _remove(self, uid: str) -> None:
        rec = self._records.pop(uid, None)
        if rec is None:
            return
        self._pods.pop(uid, None)
        node = rec.node
        for cid in self._sig_combos[rec.sig]:
            here = self._combo_here[cid]
            n = here.get(node, 0) - 1
            if n <= 0:
                here.pop(node, None)
            else:
                here[node] = n
        sn = self._sig_nodes[rec.sig]
        left = sn.get(node, 0) - 1
        if left <= 0:
            sn.pop(node, None)
        else:
            sn[node] = left
        self._sig_release(rec.sig)
        for ck, owner_val in rec.excl:
            vals = self._rev_excl.get(ck)
            if vals is not None:
                left = vals.get(owner_val, 0) - 1
                if left <= 0:
                    vals.pop(owner_val, None)
                    if not vals:
                        self._rev_excl.pop(ck, None)
                        self._excl_sel.pop(ck, None)
                else:
                    vals[owner_val] = left
        for ck, owner_val, w in rec.rev:
            vals = self._rev_pref.get(ck)
            if vals is not None:
                left = vals.get(owner_val, 0) - w
                if left == 0:
                    vals.pop(owner_val, None)
                    if not vals:
                        self._rev_pref.pop(ck, None)
                else:
                    vals[owner_val] = left
        nv = self._node_vols.get(node)
        if nv is not None:
            for vk, _fam, rw in rec.vols:
                ent = nv.get(vk)
                if ent is None:
                    continue
                ent[0] -= 1
                ent[1] -= 1 if rw else 0
                if ent[0] <= 0:
                    del nv[vk]
        for ck in rec.claims:
            pods = self._claim_pods.get(ck)
            if pods is not None:
                pods.discard(uid)
                if not pods:
                    # prune the claim's reverse maps when its last pod
                    # leaves (a long-running service would otherwise
                    # accrete one entry per claim ever mounted).  Stale
                    # old-volname entries (claim re-bound between adds)
                    # are swept by volume_changed below.
                    del self._claim_pods[ck]
                    pvc = self._lookup_pvc(ck)
                    if pvc is not None and pvc.spec.volume_name:
                        refs = self._vol_claims.get(pvc.spec.volume_name)
                        if refs is not None:
                            refs.discard(ck)
                            if not refs:
                                del self._vol_claims[pvc.spec.volume_name]
        if rec.has_anti:
            anti = self._node_anti.get(node)
            if anti is not None:
                anti.discard(uid)
                if not anti:
                    del self._node_anti[node]

    # -- reads (wave assembly) ---------------------------------------------
    def combo_aggregate(
        self, nss: Tuple[str, ...], sel: LabelSelector, topo: str
    ) -> Dict[str, int]:
        """Per-node assigned-match counts for one combo, registering (and
        backfilling over the current population) if unseen.  Caller holds
        nothing; returns a COPY."""
        key = (nss, _selector_sig(sel), topo)
        with self._mu:
            cid = self._combo_ids.get(key)
            if cid is None:
                cid = self._register_combo(key, nss, sel)
            return dict(self._combo_here[cid])

    def _register_combo(
        self, key: ComboKey, nss: Tuple[str, ...], sel: LabelSelector
    ) -> int:
        cid = len(self._combo_sel)
        self._combo_ids[key] = cid
        self._combo_sel.append((nss, sel))
        here: Dict[str, int] = {}
        gkey = (nss, key[1])
        group = self._group_ids.get(gkey)
        if group:
            # same (namespaces, selector) under another topology key:
            # matches are identical — share the backfill and the
            # signature membership
            here.update(self._combo_here[group[0]])
            for cids in self._sig_combos:
                if group[0] in cids:
                    cids.append(cid)
            group.append(cid)
        else:
            # one-time backfill against SIGNATURES (a handful), not the
            # assigned population — a combo registered late (the deferred
            # scan lane queries at drain end, 100k pods assigned) used to
            # pay one matcher call per pod here
            for sid, rep in enumerate(self._sig_rep):
                if rep is not None and _matches(sel, nss, rep):
                    self._sig_combos[sid].append(cid)
                    for node, cnt in self._sig_nodes[sid].items():
                        here[node] = here.get(node, 0) + cnt
            self._group_ids[gkey] = [cid]
        self._combo_here.append(here)
        return cid

    def lock(self):
        """The index's RLock as a context manager.  The engine wraps the
        assumed-pod membership check AND the whole constraint-table
        assembly in one hold, so no event can slip a pod into the
        aggregates after it was selected for the assumed fold (the
        TOCTOU double-count).  Events block for the duration (~tens of
        ms per wave) — the same trade the store's ``locked()`` makes for
        checkpoint snapshots."""
        return self._mu

    def assigned_uids(self) -> Set[str]:
        with self._mu:
            return set(self._records)

    def rev_excl_list(self) -> List[Tuple[ComboKey, LabelSelector, Dict[str, int]]]:
        """Live reverse required anti-affinity: (combo key, selector,
        owner-topo-value → owners) — one entry a distinct term, whatever
        the cluster holds."""
        with self._mu:
            return [
                (ck, self._excl_sel[ck], dict(vals))
                for ck, vals in self._rev_excl.items()
            ]

    def rev_pref_list(self) -> List[Tuple[ComboKey, LabelSelector, Dict[str, int]]]:
        """Live symmetric preferred contributions: (combo key, selector,
        owner-topo-value → Σ signed weight)."""
        with self._mu:
            return [
                (ck, self._rev_sel[ck], dict(vals))
                for ck, vals in self._rev_pref.items()
                if vals
            ]

    def node_vol_state(self) -> Dict[str, Dict[VolKey, List[int]]]:
        """node → VolKey → [mounts, rw_mounts, family] (copied)."""
        with self._mu:
            return {
                node: {vk: list(ent) for vk, ent in nv.items()}
                for node, nv in self._node_vols.items()
                if nv
            }


class _LazyPVMap:
    """dict-shaped adapter over the PV informer get — volume_family only
    calls .get(name)."""

    def __init__(self, lookup):
        self._lookup = lookup

    def get(self, name: str, default: Any = None) -> Any:
        out = self._lookup(name)
        return out if out is not None else default
