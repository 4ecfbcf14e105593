"""What decides ``correct``: every number compared, beside its limit.

Adapted from ``chip_smoke.py``'s ``_audit``.  After the window has closed
and what was outstanding has had its grace, the nodes and pods are read
back through REST as raw JSON and held against

* the plain reference (``reference.violations``): every placement the
  window made, with the init and warm-up pods it was made on top of;
* the client's own record: every pod it sent and did not delete is there,
  every bind its watch carried is there on read-back on that node, and
  none was carried twice with two nodes; every pod whose delete was
  answered 200 is gone, no delete was refused, and a deleted pod had been
  bound to a node that exists;
* the program's counters over the window: no wave parked, no dispatch
  healed; and the benchmark's own count of compilations in the window.

Those are the common numbers, which every deployment is held to.  A
configuration that names a ``reference`` (a module under ``references/``
with ``violations(nodes, pods, config, record) -> {name: number}``, which
like ``reference.py`` imports nothing of the program) has its own numbers
appended after them; ``record`` is the client's record (``acks``), which
still holds the placement of a pod that has since been deleted, and the
instants, on the client's one monotonic clock, at which each bind was read
off the watch (``bound_at``) and each ``DELETE`` was sent and answered
(``deleted_at``): a guarantee about pods that shared a node is held to
those, not to the pods that survive to the read-back.  A name
that is already a common number's is an error, so a configuration can add
to what it is held to and never replace it.

Every limit is 0: these are exact comparisons (a guarantee holds or it
does not), not tolerances between two readings.
"""

from __future__ import annotations

import importlib
import json
import urllib.request
from typing import Any, Dict, List, Optional, Tuple

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
    config: Optional[Dict[str, Any]] = None,
) -> Dict[str, List[float]]:
    """name -> [number, limit], in the order they are printed."""
    ref = reference.violations(nodes, pods)
    on_node = {p["metadata"]["name"]: p["spec"]["node_name"] for p in pods}
    names = [p["metadata"]["name"] for p in pods]
    node_names = {n["metadata"]["name"] for n in nodes}
    sent = acks["sent"]
    deleted = set(acks.get("deleted", ()))
    out: Dict[str, List[float]] = {
        "nodes_missing": [abs(expected_nodes - len(nodes)), 0],
        "pods_missing": [sum(1 for n in sent if n not in on_node and n not in deleted), 0],
        "pods_unsent": [len(on_node) - len(set(sent) & set(on_node)), 0],
        "pods_twice": [len(names) - len(on_node), 0],
        "unbound_after_grace": [ref["unbound"], 0],
        "on_unknown_node": [ref["on_unknown_node"], 0],
        "on_unschedulable": [ref["on_unschedulable"], 0],
        "selector_broken": [ref["selector_broken"], 0],
        "nodes_over_allocatable": [ref["nodes_over_allocatable"], 0],
        "skew_over_max": [max(0, ref["skew_over_max"]), 0],
        "ack_not_on_readback": [
            sum(1 for n, node in acks["acks"].items() if n not in deleted and on_node.get(n) != node), 0
        ],
        "bound_never_acked": [
            sum(1 for n, node in on_node.items() if node and n not in acks["acks"]), 0
        ],
        "acked_twice": [len(acks["rebinds"]), 0],
    }
    for name, value in counters.items():
        out[name] = [value, 0]
    out["deleted_still_there"] = [sum(1 for n in deleted if n in on_node), 0]
    out["delete_errors"] = [acks.get("delete_errors", 0), 0]
    out["deleted_on_unknown_node"] = [sum(1 for n in deleted if acks["acks"].get(n) not in node_names), 0]
    if config and config.get("reference"):
        own = importlib.import_module("references." + config["reference"]).violations(nodes, pods, config, acks)
        for name, value in own.items():
            if name in out:
                raise ValueError(f"references/{config['reference']}.py: {name!r} is a common number's name")
            out[name] = [value, 0]
    return out


def verdict(compared: Dict[str, List[float]]) -> bool:
    return all(number <= limit for number, limit in compared.values())
