"""The deployment ``colocate-host-5000n``'s own guarantees, in plain Python:
a web only on a host that holds a cache, at no instant two caches or two
webs on one host, and every pod still carries what its tier was sent with.
Imports nothing of the program, and says the dealing of
``makers/colocate_host.py`` again from the configuration: a pod's name ends
in its running index, its phase (``init`` or another) names the kind, and
``tiers[i % len(tiers)]`` of that kind is what the pod has to be.

``affinity_broken``       webs whose host (on read-back, or the acknowledged
                          node of a web deleted since) held no cache that
                          was there when the web was placed, as far as the
                          client's record can show it, in two parts:

                          *bound first*: the cache's bind was read no later
                          than the web's.  The scheduler decides a web only
                          after a cache's bind on that host: one it read off
                          the store, or one it committed earlier in the same
                          call and binds in the same transaction, ahead of
                          the web.  The client reads every bind off one
                          watch stream (``client.py`` ``_consume``), which
                          carries the store's writes in the order they were
                          made and stamps each on one monotonic clock as it
                          is read, so the cache's instant is never later
                          than the web's.

                          *not yet gone*: for a web of the timed window (the
                          phase ``pod``), the cache's ``DELETE`` was not
                          answered before the web was created.  The window's
                          closed loop (``client.py`` ``_closed_loop``) takes
                          the names of a create only while the pods created
                          and not yet seen bound, those names among them,
                          number no more than the mix's ``outstanding``, and
                          it counts every pod from the same running index
                          that ends a pod's name.  So when pod ``i`` was
                          created at least ``i + 1 - outstanding`` binds had
                          been read: the web was created after the instant
                          the ``(i + 1 - outstanding)``-th bind of the record
                          was read.  A cache whose ``DELETE`` was answered
                          before that was gone from the store before the web
                          was in it, and the scheduler reads the store's pod
                          events in the order they were made, so it had
                          dropped the cache before it saw the web.  A cache
                          deleted later, still in the scheduler's view when
                          it placed the web, is never flagged.  The
                          configuration states the mix's ``outstanding``
                          under ``outstanding_at_most``; a larger number
                          only weakens the bound.  The check sees a cache
                          that stays in the scheduler's view longer than a
                          web waits from its create to its bind, and up to
                          one create's pods more.
``anti_affinity_broken``  pairs of caches, or pairs of webs, whose certain
                          lifetimes on one host overlap: from the instant
                          the bind was read (``bound_at``) to the instant
                          the ``DELETE`` was sent (``deleted_at[0]``), or to
                          the read-back (``references/antiaffinity_host.py``'s
                          rule: the bind happened no later than it was seen
                          and the delete no sooner than it was sent).
``term_dropped``          pods on read-back without the labels, the
                          required anti-affinity term or the required
                          affinity term their tier was sent with.
"""

from references.antiaffinity_host import NEVER_SEEN, READ_BACK, _overlapping_pairs

#: the phase in the names of the pods the timed window's closed loop created
TIMED_PHASE = "pod"


def tier_of(name, config):
    """The tier a pod's name deals it, or None for a name not of the form
    ``<seed>-<phase>-<index>``."""
    parts = name.rsplit("-", 2)
    if len(parts) != 3 or not parts[2].isdigit():
        return None
    tiers = config["init_pods" if parts[1] == "init" else "measured_pods"]["tiers"]
    return tiers[int(parts[2]) % len(tiers)]


def created_after(name, binds, outstanding):
    """An instant before which the pod was certainly not created: for a pod
    of the timed window, the instant the ``(i + 1 - outstanding)``-th bind
    was read (``binds`` in order); for any other, minus infinity."""
    _seed, phase, index = name.rsplit("-", 2)
    k = int(index) + 1 - outstanding
    if phase != TIMED_PHASE or not 1 <= k <= len(binds):
        return float("-inf")
    return binds[k - 1]


def _wire_term(spec):
    """A tier's term as the façade writes it."""
    return {
        "topology_key": spec["topology_key"],
        "namespaces": [],
        "label_selector": {"match_labels": {}, "match_expressions": [dict(e) for e in spec["match_expressions"]]},
    }


def _required(pod, direction):
    affinity = pod["spec"].get("affinity") or {}
    return (affinity.get(direction) or {}).get("required") or []


def _as_sent(pod, tier):
    if (pod["metadata"].get("labels") or {}) != tier["labels"]:
        return False
    if _required(pod, "pod_anti_affinity") != [_wire_term(tier["anti_affinity"])]:
        return False
    want = [_wire_term(tier["affinity"])] if tier["affinity"] else []
    return _required(pod, "pod_affinity") == want


def violations(nodes, pods, config, record):
    bound_at, deleted_at = record["bound_at"], record["deleted_at"]
    # (tier name, node) -> [(pod, first instant certainly there, DELETE sent, DELETE answered)]
    on_node = {}
    dropped = 0
    for pod in pods:
        name = pod["metadata"]["name"]
        tier = tier_of(name, config)
        if tier is None:
            continue
        if not _as_sent(pod, tier):
            dropped += 1
        if pod["spec"]["node_name"]:
            start = bound_at.get(name, NEVER_SEEN)
            on_node.setdefault((tier["name"], pod["spec"]["node_name"]), []).append((name, start, READ_BACK, READ_BACK))
    for name in record["deleted"]:
        tier = tier_of(name, config)
        if tier is not None:
            sent, answered = deleted_at[name]
            on_node.setdefault((tier["name"], record["acks"].get(name)), []).append(
                (name, bound_at.get(name, sent), sent, answered)
            )
    binds = sorted(bound_at.values())
    broken = 0
    for (tier, node), webs in on_node.items():
        if tier != "web":
            continue
        caches = on_node.get(("cache", node), ())
        for name, start, _sent, _answered in webs:
            born = created_after(name, binds, config["outstanding_at_most"])
            if not any(c_start <= start and born <= c_answered for _c, c_start, _s, c_answered in caches):
                broken += 1
    return {
        "affinity_broken": broken,
        "anti_affinity_broken": sum(
            _overlapping_pairs([(start, sent) for _name, start, sent, _answered in lifetimes])
            for lifetimes in on_node.values()
        ),
        "term_dropped": dropped,
    }
