"""The fixture deployment's own guarantee, in plain Python: no pod that
selects a pool was ever bound outside it.  A pod that is still there is
read from the read-back; one that has been deleted since from the client's
record, which keeps the node it was bound to.  Imports nothing of the
program."""


def violations(nodes, pods, config, record):
    label = config["nodes"]["pool_label"]
    want = config["measured_pods"]["node_selector"][label]
    pool_of = {n["metadata"]["name"]: n["metadata"]["labels"].get(label) for n in nodes}
    selecting = {
        p["metadata"]["name"] for p in pods if (p["spec"].get("node_selector") or {}).get(label) == want
    } | set(record["deleted"])  # only measured pods are ever deleted, and every one of them selects
    return {"outside_pool": sum(1 for name in selecting if pool_of.get(record["acks"].get(name)) != want)}
