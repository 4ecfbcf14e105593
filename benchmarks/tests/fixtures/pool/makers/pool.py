"""The fixture deployment's maker: ``cluster``'s objects, the nodes dealt
into the configuration's pools by a label, the pods of a kind that names a
``node_selector`` carrying it."""

import cluster


def make_nodes(config, seed):
    spec = config["nodes"]
    nodes = cluster.make_nodes(config, seed)
    for node in nodes:
        index = int(node.metadata.name.rsplit("-", 1)[1])
        node.metadata.labels[spec["pool_label"]] = spec["pools"][index % len(spec["pools"])]
    return nodes


def make_pods(kind, prefix, start, count):
    pods = cluster.make_pods(kind, prefix, start, count)
    for pod in pods:
        pod.spec.node_selector = dict(kind.get("node_selector") or {})
    return pods
