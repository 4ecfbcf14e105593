"""The maker of ``antiaffinity-host-5000n``: ``cluster``'s objects, every
node under a hostname label of its own, the pods of a kind that names an
``anti_affinity`` carrying it as one required term.

The deployment needs a program that keeps the reverse direction of
required anti-affinity (what the placed pods ban) on the combo axis of its
constraint tables: one row a distinct term (PERF.md section 6, PR 35).  One
that keeps a row for every occupied hostname instead builds an axis of
2,048 and then 16,384 rows in a Python loop over terms x nodes, compiles a
scan program for each size and does not reach a window within the check's
time (PERF.md section 7, row 18), so this maker refuses it at once: such a
program lacks the counters this cell's metrics read, too, and their
registry is the one thing of that change that can be looked up without
importing JAX into the load generator's process.
"""

import cluster
from minisched_tpu.api.objects import Affinity, LabelSelector, PodAffinityTerm, PodAntiAffinity
from minisched_tpu.observability.counters import LANE_COUNTERS

if "scan.excl_terms" not in LANE_COUNTERS:  # the refusal: see above
    raise ImportError("antiaffinity-host-5000n: this program keeps a row of its scan tables for every occupied node")


def make_nodes(config, seed):
    nodes = cluster.make_nodes(config, seed)
    for node in nodes:
        node.metadata.labels[config["nodes"]["hostname_label"]] = node.metadata.name
    return nodes


def make_pods(kind, prefix, start, count):
    pods = cluster.make_pods(kind, prefix, start, count)
    term = kind.get("anti_affinity")
    for pod in pods if term else ():
        pod.spec.affinity = Affinity(
            pod_anti_affinity=PodAntiAffinity(
                required=[
                    PodAffinityTerm(
                        label_selector=LabelSelector(match_labels=dict(term["match_labels"])),
                        topology_key=term["topology_key"],
                        namespaces=list(term["namespaces"]),
                    )
                ]
            )
        )
    return pods
