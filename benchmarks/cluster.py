"""Objects of one deployment, made from its configuration file and the seed.

Adapted from ``chip_smoke.py``'s ``_make_cluster``.  Imports the program's
API objects (the wire format is the program's) and nothing that touches
JAX: the load generator runs this in a process that must never hold a chip.

The seed changes the order of things, never the amount: every seed gives
the same number of nodes of the same shape in the same zones, under names
and in an order of its own.
"""

from __future__ import annotations

import random
from typing import Any, Dict, List

from minisched_tpu.api.objects import (
    LabelSelector,
    TopologySpreadConstraint,
    make_node,
    make_pod,
)


def make_nodes(config: Dict[str, Any], seed: int) -> List[Any]:
    spec = config["nodes"]
    zones = spec.get("zones") or []
    order = list(range(spec["count"]))
    random.Random(seed).shuffle(order)
    nodes = []
    for i in order:
        labels = {}
        if zones:
            labels[spec["zone_label"]] = zones[i % len(zones)]
        nodes.append(
            make_node(f"node-{i:05d}", capacity=spec["capacity"], labels=labels)
        )
    return nodes


def make_pods(kind: Dict[str, Any], prefix: str, start: int, count: int) -> List[Any]:
    """``count`` pods of one kind of the configuration (``init_pods`` or
    ``measured_pods``), named ``<prefix>-<n>`` from ``start``."""
    labels = kind.get("labels") or {}
    spread = kind.get("spread")
    pods = []
    for i in range(start, start + count):
        pod = make_pod(f"{prefix}-{i:07d}", requests=kind["requests"], labels=labels)
        if spread:
            pod.spec.topology_spread_constraints = [
                TopologySpreadConstraint(
                    max_skew=spread["max_skew"],
                    topology_key=spread["topology_key"],
                    when_unsatisfiable=spread["when_unsatisfiable"],
                    label_selector=LabelSelector(
                        match_labels=dict(spread["match_labels"])
                    ),
                )
            ]
        pods.append(pod)
    return pods
