"""Interaction grouping for the blocked scan lane (ops/sequential.
blocked_scan_schedule).

Two cross-pod-constrained pods INTERACT when one's commit can change what
the other observes: they share a selector group (one's labels match a
selector another's constraint carries — in either direction), or they
reference a shared volume identity.  Pods that don't interact can be
evaluated in one block: their carried-plane updates commute, so the block
result equals a sequential order — capacity races are separately caught
by repair acceptance and retried.

``order_into_blocks`` assigns pods first-fit into fixed-size blocks whose
member interaction sets stay pairwise disjoint.  First-fit preserves
per-group FIFO order: a block rejected for an earlier same-group pod
keeps rejecting later ones (blocks only grow), so a group's members land
in strictly increasing blocks — the within-group sequential semantics the
blocked kernel's exactness claim rests on.

The blocks' FILL is the grouping's other finding, and the engine lays its
rows out by it (DeviceScheduler._plan_blocked_calls): many small groups
fill their blocks and run 32 rows a kernel step; one large group (every
pod under one selector) gets a block a pod, and such one-pod blocks at the
end of the order run a pod a row through the same kernel at a narrow
block size, after the blocks before them — block order is kept, so a
group's members still run in FIFO order.

The same fact makes first fit near-linear: a block REFUSES an identity
when it is full or its union holds the identity, and a refusal is for
good — a union only gains identities and a full block stays full.  So
the search keeps, per identity, the lowest block not yet known to refuse
it, and the lowest block that is not full; every block below the largest
of those over a pod's identities refuses the pod, and its walk starts
there instead of at block 0.  The pointers only move forward, so the
blocks chosen are exactly those of the walk from 0.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

from minisched_tpu.models.constraints import (
    _matches,
    _selector_sig,
    _term_namespaces,
    rev_pref_terms_of,
)


def _own_terms(pod: Any):
    """Every (namespaces, selector) group a pod's constraints carry —
    spread constraints, required/preferred (anti-)affinity both signs."""
    ns = pod.metadata.namespace
    for c in pod.spec.topology_spread_constraints:
        yield ((ns,), c.label_selector)
    aff = pod.spec.affinity
    if aff is None:
        return
    pa, pan = aff.pod_affinity, aff.pod_anti_affinity
    if pa is not None:
        for term in pa.required:
            yield (_term_namespaces(term, ns), term.label_selector)
        for wt in pa.preferred:
            yield (_term_namespaces(wt.term, ns), wt.term.label_selector)
    if pan is not None:
        for term in pan.required:
            yield (_term_namespaces(term, ns), term.label_selector)
        for wt in pan.preferred:
            yield (_term_namespaces(wt.term, ns), wt.term.label_selector)


def interaction_sets(pods: Sequence[Any]) -> List[Set]:
    """Per-pod interaction-identity sets over the given pods.

    Identities: selector-group ids (a pod holds a group if its constraints
    carry it OR its labels match it — matching covers both directions of
    every coupling, incl. the symmetric rev_weight scoring, whose term
    stream is a subset of ``_own_terms``) and volume claim keys."""
    group_ids: Dict[Tuple, int] = {}
    group_sel: List[Tuple[Tuple[str, ...], Any]] = []

    def gid(nss: Tuple[str, ...], sel: Any) -> int:
        key = (nss, _selector_sig(sel))
        g = group_ids.get(key)
        if g is None:
            g = group_ids[key] = len(group_sel)
            group_sel.append((nss, sel))
        return g

    own: List[Set] = []
    for pod in pods:
        s: Set = {gid(nss, sel) for nss, sel in _own_terms(pod)}
        for _nss, _sel, _topo, _w in rev_pref_terms_of(pod):
            s.add(gid(_nss, _sel))
        for vol in pod.spec.volumes:
            s.add(("vol", f"{pod.metadata.namespace}/{vol}"))
        own.append(s)
    # matching direction: pod's labels hit a group's selector
    for i, pod in enumerate(pods):
        for g, (nss, sel) in enumerate(group_sel):
            if g not in own[i] and _matches(sel, nss, pod):
                own[i].add(g)
    return own


def order_into_blocks(
    items: Sequence[Any], sets: Sequence[Set], block_size: int
) -> List[List[Optional[Any]]]:
    """First-fit the items into blocks of ``block_size`` with pairwise-
    disjoint sets; short blocks are padded with None.  Items appear in
    non-decreasing block order per interaction group (see module doc)."""
    members: List[List[Any]] = []
    unions: List[Set] = []
    # identity -> lowest block not yet known to refuse it
    lowest: Dict[Any, int] = {}
    open_from = 0  # lowest block that is not full
    for item, s in zip(items, sets):
        n = len(members)
        while open_from < n and len(members[open_from]) >= block_size:
            open_from += 1
        b = open_from
        for g in s:
            k = max(lowest.get(g, 0), open_from)
            while k < n and (
                len(members[k]) >= block_size or g in unions[k]
            ):
                k += 1
            lowest[g] = k
            if k > b:
                b = k
        while b < n and (
            len(members[b]) >= block_size or (unions[b] & s)
        ):
            b += 1
        if b == n:
            members.append([item])
            unions.append(set(s))
        else:
            members[b].append(item)
            unions[b] |= s
    return [m + [None] * (block_size - len(m)) for m in members]
