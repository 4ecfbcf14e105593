"""``mixed-5000n``'s own guarantees, in plain Python: what the common
reference (``reference.py``) does not hold a placement to.  Imports nothing
of the program, and says the dealing of ``makers/mixed.py`` again from the
configuration's numbers: a pod's name ends in its running index, and the
index alone decides what the pod has to be.

``taint_not_tolerated``          bound pods on a node one of whose
                                 ``NoSchedule`` / ``NoExecute`` taints no
                                 toleration of the pod matches;
``service_constraint_dropped``   measured pods that read back without what
                                 their index says they were sent with: a
                                 service pod's label, its one constraint
                                 selecting that label, a dedicated tenant's
                                 selector and toleration, a pinned pod's
                                 zone, a tolerating pod's toleration;
``kinds_missing``                kinds of the mix (service, dedicated
                                 tenant, pinned, tolerating, plain) of
                                 which the client's watch saw no pod bound,
                                 once pods of every kind were sent.
"""

import math

KINDS = ("service", "tenant", "pinned", "tolerating", "plain")


def _tolerates(toleration, taint):
    if toleration.get("effect") and toleration["effect"] != taint["effect"]:
        return False
    if not toleration.get("key"):
        return toleration.get("operator") == "Exists"
    if toleration["key"] != taint["key"]:
        return False
    return toleration.get("operator") == "Exists" or toleration.get("value", "") == taint.get("value", "")


def expected(i, mix):
    """(kind, labels, node_selector, selects, tolerates the pool) of
    measured pod ``i``."""
    pool = mix["pool"]
    if i % 4 != 0:
        k = int((mix["services"] + 1) ** math.modf(i * mix["golden"])[0])
        app = {mix["service_label"]: mix["service_prefix"] + str(k)}
        if k % mix["dedicated_every"] == 0:
            return "tenant", app, {pool["label"]: pool["value"]}, app, True
        return "service", app, {}, app, False
    if i % 16 == 0:
        return "pinned", {}, {mix["spread"]["topology_key"]: mix["zones"][(i // 16) % len(mix["zones"])]}, None, False
    if i % 16 == 8:
        return "tolerating", {}, {}, None, True
    return "plain", {}, {}, None, False


def _as_sent(pod, want, mix):
    _kind, labels, node_selector, selects, tolerates = want
    spec = pod["spec"]
    if (pod["metadata"].get("labels") or {}) != labels or (spec.get("node_selector") or {}) != node_selector:
        return False
    constraints = spec.get("topology_spread_constraints") or []
    if selects is None:
        if constraints:
            return False
    else:
        s = mix["spread"]
        if len(constraints) != 1:
            return False
        c = constraints[0]
        if (c["max_skew"], c["topology_key"], c["when_unsatisfiable"]) != (
            s["max_skew"], s["topology_key"], s["when_unsatisfiable"]
        ):
            return False
        sel = c.get("label_selector") or {}
        if (sel.get("match_labels") or {}) != selects or sel.get("match_expressions"):
            return False
    pool = mix["pool"]
    taint = {"key": pool["taint_key"], "value": pool["taint_value"], "effect": pool["taint_effect"]}
    return any(_tolerates(t, taint) for t in spec.get("tolerations") or []) == tolerates


def _index(name):
    """The running index a measured pod's name ends in; None for an init pod."""
    phase, _, index = name.rpartition("-")
    return None if phase.endswith("-init") else int(index)


def violations(nodes, pods, config, record):
    mix = config["measured_pods"]["mix"]
    taints = {
        n["metadata"]["name"]: [t for t in n["spec"].get("taints") or [] if t["effect"] in ("NoSchedule", "NoExecute")]
        for n in nodes
    }
    out = {"taint_not_tolerated": 0, "service_constraint_dropped": 0, "kinds_missing": 0}
    for pod in pods:
        tolerations = pod["spec"].get("tolerations") or []
        for taint in taints.get(pod["spec"]["node_name"], ()):
            if not any(_tolerates(t, taint) for t in tolerations):
                out["taint_not_tolerated"] += 1
                break
        i = _index(pod["metadata"]["name"])
        if i is not None and not _as_sent(pod, expected(i, mix), mix):
            out["service_constraint_dropped"] += 1
    sent = {k: 0 for k in KINDS}
    bound = dict(sent)
    for name in record["sent"]:
        i = _index(name)
        if i is None:
            continue
        kind = expected(i, mix)[0]
        sent[kind] += 1
        bound[kind] += name in record["acks"]
    if all(sent.values()):
        out["kinds_missing"] = sum(1 for k in KINDS if not bound[k])
    return out
