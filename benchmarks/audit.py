"""What decides ``correct``: every number compared, beside its limit.

Adapted from ``chip_smoke.py``'s ``_audit``.  After the window has closed
and what was outstanding has had its grace, the nodes and pods are read
back through REST as raw JSON and held against

* the plain reference (``reference.violations``): every placement the
  window made, with the init and warm-up pods it was made on top of;
* the client's own record: every pod it sent is there, every bind its
  watch carried is there on read-back on that node, and none was carried
  twice with two nodes;
* the program's counters over the window: no wave parked, no dispatch
  healed; and the benchmark's own count of compilations in the window.

Every limit is 0: these are exact comparisons (a guarantee holds or it
does not), not tolerances between two readings.
"""

from __future__ import annotations

import json
import urllib.request
from typing import Any, Dict, List, Tuple

import reference


def read_back(base: str) -> Tuple[List[Dict[str, Any]], List[Dict[str, Any]]]:
    def items(path: str) -> List[Dict[str, Any]]:
        with urllib.request.urlopen(base + path, timeout=300) as r:
            return json.load(r)["items"]

    return items("/api/v1/nodes"), items("/api/v1/pods")


def checks(
    nodes: List[Dict[str, Any]],
    pods: List[Dict[str, Any]],
    acks: Dict[str, Any],
    expected_nodes: int,
    counters: Dict[str, float],
) -> Dict[str, List[float]]:
    """name -> [number, limit], in the order they are printed."""
    ref = reference.violations(nodes, pods)
    on_node = {p["metadata"]["name"]: p["spec"]["node_name"] for p in pods}
    names = [p["metadata"]["name"] for p in pods]
    sent = acks["sent"]
    out: Dict[str, List[float]] = {
        "nodes_missing": [abs(expected_nodes - len(nodes)), 0],
        "pods_missing": [sum(1 for n in sent if n not in on_node), 0],
        "pods_unsent": [len(on_node) - len(set(sent) & set(on_node)), 0],
        "pods_twice": [len(names) - len(on_node), 0],
        "unbound_after_grace": [ref["unbound"], 0],
        "on_unknown_node": [ref["on_unknown_node"], 0],
        "on_unschedulable": [ref["on_unschedulable"], 0],
        "selector_broken": [ref["selector_broken"], 0],
        "nodes_over_allocatable": [ref["nodes_over_allocatable"], 0],
        "skew_over_max": [max(0, ref["skew_over_max"]), 0],
        "ack_not_on_readback": [
            sum(1 for n, node in acks["acks"].items() if on_node.get(n) != node), 0
        ],
        "bound_never_acked": [
            sum(1 for n, node in on_node.items() if node and n not in acks["acks"]), 0
        ],
        "acked_twice": [len(acks["rebinds"]), 0],
    }
    for name, value in counters.items():
        out[name] = [value, 0]
    return out


def verdict(compared: Dict[str, List[float]]) -> bool:
    return all(number <= limit for number, limit in compared.values())
