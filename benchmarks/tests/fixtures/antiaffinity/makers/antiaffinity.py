"""The anti-affinity deployment's maker: ``cluster``'s objects, every node
under a hostname label of its own, the pods of a kind that names an
``anti_affinity`` carrying it as one required term."""

import cluster
from minisched_tpu.api.objects import Affinity, LabelSelector, PodAffinityTerm, PodAntiAffinity


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
