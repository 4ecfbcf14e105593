"""The maker of ``mixed-5000n``: ``cluster``'s nodes and pods, dealt into
the kinds of a multi-tenant service cluster by their index alone.

Node ``n`` (the number in its name) is in zone ``n % len(zones)`` as
``cluster`` deals it; ``n % pool.every == pool.at`` puts it in the dedicated
pool (a label, a ``NoSchedule`` taint, the pool's own capacity) and
``n % cordoned.every == cordoned.at`` cordons it.  Measured pod ``i`` (the
running index ``make_pods`` is handed) takes its requests from ``i`` and is

* ``i % 4 != 0``: a service pod of service ``service_of(i)``, labelled
  ``app=svc-<k>`` with one zone spread constraint selecting that label;
  a service with ``k % dedicated_every == 0`` is a tenant of the pool: it
  also selects the pool's label and tolerates its taint;
* ``i % 16 == 0``: pinned to a zone by ``node_selector``;
* ``i % 16 == 8``: tolerates the pool's taint and selects nothing;
* else plain.

The seed changes names and the order of the nodes, never the amounts: the
same index is the same kind in every seed.  ``references/mixed.py`` says
the same dealing again in plain Python and holds the read-back to it.

The deployment needs a program whose scan lanes keep the combo axis of
their constraint tables to a few capacities (``constraints.cap_tier``,
PERF.md section 6, PR 33).  One that does not meets a new scan program for
every 32 more services a call holds and spends hours of set-up compiling
them, so this maker refuses it at once: such a program lacks the lane
counters this cell's metrics read, too, and their registry is the one
thing of that change that can be looked up without importing JAX into the
load generator's process.
"""

import math

import cluster
from minisched_tpu.observability.counters import LANE_COUNTERS  # noqa: F401  (the refusal: see above)
from minisched_tpu.api.objects import (
    LabelSelector,
    ResourceList,
    Taint,
    Toleration,
    TopologySpreadConstraint,
    make_pod,
)


def service_of(i, mix):
    """1 <= k <= services: service k holds ln((k+1)/k) / ln(services+1) of
    the service pods, dealt by the golden-ratio sequence so that every
    stretch of indices holds every service in its share."""
    return int((mix["services"] + 1) ** math.modf(i * mix["golden"])[0])


def make_nodes(config, seed):
    spec = config["nodes"]
    pool, cordoned = spec["pool"], spec["cordoned"]
    nodes = cluster.make_nodes(config, seed)
    for node in nodes:
        n = int(node.metadata.name.rsplit("-", 1)[1])
        if n % pool["every"] == pool["at"]:
            node.metadata.labels[pool["label"]] = pool["value"]
            node.spec.taints = [Taint(pool["taint_key"], pool["taint_value"], pool["taint_effect"])]
            node.status.capacity = ResourceList.parse(pool["capacity"])
            node.status.allocatable = node.status.capacity.clone()
        if n % cordoned["every"] == cordoned["at"]:
            node.spec.unschedulable = True
    return nodes


def make_pods(kind, prefix, start, count):
    mix = kind.get("mix")
    if not mix:  # the init pods: pod-default.yaml
        return cluster.make_pods(kind, prefix, start, count)
    pool, zones = mix["pool"], mix["zones"]
    toleration = Toleration(
        key=pool["taint_key"], operator="Equal", value=pool["taint_value"], effect=pool["taint_effect"]
    )
    pods = []
    for i in range(start, start + count):
        requests = {"cpu": mix["cpu"][i % 3], "memory": mix["memory"][(i // 3) % 3]}
        pod = make_pod(f"{prefix}-{i:07d}", requests=requests)
        if i % 4 != 0:
            k = service_of(i, mix)
            app = {mix["service_label"]: f"{mix['service_prefix']}{k}"}
            pod.metadata.labels.update(app)
            pod.spec.topology_spread_constraints = [
                TopologySpreadConstraint(
                    max_skew=mix["spread"]["max_skew"],
                    topology_key=mix["spread"]["topology_key"],
                    when_unsatisfiable=mix["spread"]["when_unsatisfiable"],
                    label_selector=LabelSelector(match_labels=dict(app)),
                )
            ]
            if k % mix["dedicated_every"] == 0:
                pod.spec.node_selector = {pool["label"]: pool["value"]}
                pod.spec.tolerations = [toleration]
        elif i % 16 == 0:
            pod.spec.node_selector = {mix["spread"]["topology_key"]: zones[(i // 16) % len(zones)]}
        elif i % 16 == 8:
            pod.spec.tolerations = [toleration]
        pods.append(pod)
    return pods
