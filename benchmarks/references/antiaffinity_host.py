"""The deployment ``antiaffinity-host-5000n``'s own guarantees, in plain
Python: at no instant two green pods on one node, and every green pod still
carries its required term.  Imports nothing of the program.

A mix that deletes leaves on read-back only the pods that survived, so the
first guarantee is held to the client's record: a green pod was on its node
for certain from the instant its bind was read off the watch
(``bound_at``) to the instant its ``DELETE`` was sent (``deleted_at[0]``),
or to the read-back where it is still there.  The bind happened no later
than it was seen and the delete no sooner than it was sent, so two such
lifetimes that overlap on one node are a fault and never an artefact of
the clocks; both instants are the client's one monotonic clock.
"""

import bisect
import sys

#: a pod still there on read-back was on its node until then, which is
#: after every instant of the record
READ_BACK = float("inf")
#: a pod on read-back whose bind the watch never carried (the common
#: ``bound_never_acked``) was there for certain at the read-back only
NEVER_SEEN = sys.float_info.max


def _term(config):
    return config["measured_pods"]["anti_affinity"]


def _green(labels, term):
    return all((labels or {}).get(k) == v for k, v in term["match_labels"].items())


def _carries(pod, term):
    affinity = pod["spec"].get("affinity") or {}
    for have in (affinity.get("pod_anti_affinity") or {}).get("required") or []:
        if (
            have["topology_key"] == term["topology_key"]
            and have["label_selector"]["match_labels"] == term["match_labels"]
            and set(term["namespaces"]) <= set(have["namespaces"])
        ):
            return True
    return False


def _overlapping_pairs(lifetimes):
    """Pairs of (start, end) that share an instant.  In order of start, a
    lifetime overlaps every earlier one that has not ended by its start;
    one that has ended by then started earlier still, so it is among them."""
    lifetimes = sorted(t for t in lifetimes if t[0] < t[1])
    ends = sorted(end for _start, end in lifetimes)
    return sum(i - bisect.bisect_right(ends, start) for i, (start, _end) in enumerate(lifetimes))


def violations(nodes, pods, config, record):
    term = _term(config)
    bound_at, deleted_at = record["bound_at"], record["deleted_at"]
    on_node = {}  # node -> [(first instant certainly there, last)]
    dropped = 0
    for pod in pods:
        meta = pod["metadata"]
        if meta["namespace"] not in term["namespaces"] or not _green(meta["labels"], term):
            continue
        if not _carries(pod, term):
            dropped += 1
        if pod["spec"]["node_name"]:
            start = bound_at.get(meta["name"], NEVER_SEEN)
            on_node.setdefault(pod["spec"]["node_name"], []).append((start, READ_BACK))
    if _green(config["measured_pods"]["labels"], term):  # only measured pods are ever deleted
        for name in record["deleted"]:
            sent = deleted_at[name][0]
            on_node.setdefault(record["acks"].get(name), []).append((bound_at.get(name, sent), sent))
    return {
        "anti_affinity_broken": sum(_overlapping_pairs(lifetimes) for lifetimes in on_node.values()),
        "term_dropped": dropped,
    }
