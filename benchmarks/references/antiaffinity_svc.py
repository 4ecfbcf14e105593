"""The deployment ``antiaffinity-svc-5000n``'s own guarantees, in plain
Python: at no instant two pods of one service on one host, and every pod
still carries its own service's label and term.  Imports nothing of the
program, and says the dealing of ``makers/antiaffinity_svc.py`` again from
the configuration's numbers: a pod's name ends in its running index, and
the index alone decides its service (``service_of``, ``mixed-5000n``'s
dealing), the init pods and the measured pods alike.

``anti_affinity_broken``  pairs of pods of one service whose certain
                          lifetimes on one host overlap: from the instant
                          the bind was read (``bound_at``) to the instant
                          the ``DELETE`` was sent (``deleted_at[0]``), or to
                          the read-back (``references/antiaffinity_host.py``'s
                          rule: the bind happened no later than it was seen
                          and the delete no sooner than it was sent).
``term_dropped``          pods on read-back without the label or the
                          required anti-affinity term their index deals, or
                          with those of another service.
"""

import math

from references.antiaffinity_host import NEVER_SEEN, READ_BACK, _overlapping_pairs


def service_of(i, mix):
    """Service k of running index i: floor((services + 1) ** frac(i * golden))."""
    return int((mix["services"] + 1) ** math.modf(i * mix["golden"])[0])


def dealt(name, config):
    """(labels, the required anti-affinity term as the façade writes it) the
    pod's name deals, or None for a name not of the form
    ``<seed>-<phase>-<index>``."""
    parts = name.rsplit("-", 2)
    if len(parts) != 3 or not parts[2].isdigit():
        return None
    mix = config["init_pods" if parts[1] == "init" else "measured_pods"]["mix"]
    app = mix["service_prefix"] + str(service_of(int(parts[2]), mix))
    term = {
        "topology_key": mix["anti_affinity"]["topology_key"],
        "namespaces": [],
        "label_selector": {
            "match_labels": {},
            "match_expressions": [
                {"key": mix["service_label"], "operator": mix["anti_affinity"]["operator"], "values": [app]}
            ],
        },
    }
    return {mix["service_label"]: app}, term


def _as_sent(pod, labels, term):
    if (pod["metadata"].get("labels") or {}) != labels:
        return False
    affinity = pod["spec"].get("affinity") or {}
    return ((affinity.get("pod_anti_affinity") or {}).get("required") or []) == [term]


def violations(nodes, pods, config, record):
    bound_at, deleted_at = record["bound_at"], record["deleted_at"]
    on_node = {}  # (service, node) -> [(first instant certainly there, last)]
    dropped = 0
    for pod in pods:
        name = pod["metadata"]["name"]
        want = dealt(name, config)
        if want is None:
            continue
        labels, term = want
        if not _as_sent(pod, labels, term):
            dropped += 1
        if pod["spec"]["node_name"]:
            key = (tuple(labels.items()), pod["spec"]["node_name"])
            on_node.setdefault(key, []).append((bound_at.get(name, NEVER_SEEN), READ_BACK))
    for name in record["deleted"]:
        want = dealt(name, config)
        if want is not None:
            sent = deleted_at[name][0]
            key = (tuple(want[0].items()), record["acks"].get(name))
            on_node.setdefault(key, []).append((bound_at.get(name, sent), sent))
    return {
        "anti_affinity_broken": sum(_overlapping_pairs(lifetimes) for lifetimes in on_node.values()),
        "term_dropped": dropped,
    }
