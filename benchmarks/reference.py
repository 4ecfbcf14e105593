"""The plain reference: what a placement has to satisfy, said in plain Python.

The scheduler's repair wave is not bind-exact by design (two sound runs
place the same pods on different nodes), so the reference is not a second
scheduler whose choices are compared one by one.  It is the semantics the
deployment's guarantees state, applied to every answer: given the nodes
and the pods as the REST API returns them (raw JSON, parsed here, with
nothing of the program imported), count every placement that the
guarantees forbid.  Every count has the limit 0, except the spread skew,
whose limit is the constraint's own ``max_skew``.

* a bound pod names a node that exists;
* no pod on an unschedulable node;
* per node, summed cpu, memory and pod count within allocatable;
* every ``node_selector`` entry matches the node's labels;
* for every ``DoNotSchedule`` spread constraint: over the topology domains
  of the nodes the constraint's pods may use, max - min of the matching
  pods' counts is at most ``max_skew``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple


def _requests(pod: Dict[str, Any]) -> Tuple[int, int]:
    cpu = mem = 0
    for c in pod["spec"]["containers"]:
        cpu += c["requests"]["milli_cpu"]
        mem += c["requests"]["memory"]
    return cpu, mem


def _matches(selector: Dict[str, Any], labels: Dict[str, str]) -> bool:
    if selector.get("match_expressions"):
        raise ValueError("the reference reads match_labels selectors only")
    return all(labels.get(k) == v for k, v in selector["match_labels"].items())


def violations(nodes: List[Dict[str, Any]], pods: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Counts of forbidden placements, and the worst skew over its limit."""
    by_name = {n["metadata"]["name"]: n for n in nodes}
    used: Dict[str, List[int]] = {}
    out = {
        "unbound": 0,
        "on_unknown_node": 0,
        "on_unschedulable": 0,
        "selector_broken": 0,
        "nodes_over_allocatable": 0,
        "skew_over_max": 0,
    }
    #: (namespace, topology key, max_skew, selector as sorted items) -> domain -> count
    groups: Dict[Tuple, Dict[str, int]] = {}
    placed: List[Tuple[Dict[str, Any], Dict[str, Any]]] = []
    for pod in pods:
        node_name = pod["spec"]["node_name"]
        if not node_name:
            out["unbound"] += 1
            continue
        node = by_name.get(node_name)
        if node is None:
            out["on_unknown_node"] += 1
            continue
        placed.append((pod, node))
        if node["spec"]["unschedulable"]:
            out["on_unschedulable"] += 1
        labels = node["metadata"]["labels"]
        for key, want in (pod["spec"].get("node_selector") or {}).items():
            if labels.get(key) != want:
                out["selector_broken"] += 1
        cpu, mem = _requests(pod)
        u = used.setdefault(node_name, [0, 0, 0])
        u[0] += cpu
        u[1] += mem
        u[2] += 1
        for c in pod["spec"].get("topology_spread_constraints") or []:
            if c["when_unsatisfiable"] != "DoNotSchedule":
                continue
            sel = c["label_selector"]
            key = (
                pod["metadata"]["namespace"],
                c["topology_key"],
                c["max_skew"],
                tuple(sorted(sel["match_labels"].items())),
            )
            groups.setdefault(key, {})
    for name, (cpu, mem, count) in used.items():
        alloc = by_name[name]["status"]["allocatable"]
        if cpu > alloc["milli_cpu"] or mem > alloc["memory"] or count > alloc["pods"]:
            out["nodes_over_allocatable"] += 1
    # spread: count every placed pod that a group's selector matches, by
    # the domain of its node; domains are those of the schedulable nodes
    for (ns, topo, max_skew, sel_items), counts in groups.items():
        selector = {"match_labels": dict(sel_items)}
        for n in nodes:
            dom = n["metadata"]["labels"].get(topo)
            if dom is not None and not n["spec"]["unschedulable"]:
                counts.setdefault(dom, 0)
        for pod, node in placed:
            if pod["metadata"]["namespace"] != ns:
                continue
            if not _matches(selector, pod["metadata"]["labels"]):
                continue
            dom = node["metadata"]["labels"].get(topo)
            if dom is not None:
                counts[dom] = counts.get(dom, 0) + 1
        if counts:
            skew = max(counts.values()) - min(counts.values())
            out["skew_over_max"] = max(out["skew_over_max"], skew - max_skew)
    out["spread_groups"] = len(groups)
    return out
