"""The maker of ``colocate-host-5000n``: ``antiaffinity_host``'s nodes, every
one under a hostname label of its own, and pods dealt into the tiers of the
configuration by their running index alone.

A kind (``init_pods``, ``measured_pods``) lists its ``tiers``; pod ``i`` of
the kind (the running index ``make_pods`` is handed) is of tier
``tiers[i % len(tiers)]``: every init pod is a cache, and of the measured
pods the even are caches and the odd are webs.  A tier states its labels,
one required anti-affinity term and, for the web, one required affinity
term, each a ``match_expressions`` selector under a topology key, with no
namespaces named (the term selects in its pod's own).  The seed changes
names and the order of the nodes, never the amounts: the same index is the
same tier in every seed.  ``references/colocate_host.py`` says the same
dealing again in plain Python and holds the read-back to it.
"""

from makers.antiaffinity_host import make_nodes  # noqa: F401  (the nodes of this deployment)
from minisched_tpu.api.objects import (
    Affinity,
    LabelSelector,
    LabelSelectorRequirement,
    PodAffinity,
    PodAffinityTerm,
    PodAntiAffinity,
    make_pod,
)


def tier_of(kind, i):
    tiers = kind["tiers"]
    return tiers[i % len(tiers)]


def _term(spec):
    return PodAffinityTerm(
        label_selector=LabelSelector(
            match_expressions=[
                LabelSelectorRequirement(e["key"], e["operator"], list(e["values"]))
                for e in spec["match_expressions"]
            ]
        ),
        topology_key=spec["topology_key"],
    )


def make_pods(kind, prefix, start, count):
    pods = []
    for i in range(start, start + count):
        tier = tier_of(kind, i)
        pod = make_pod(f"{prefix}-{i:07d}", requests=kind["requests"], labels=tier["labels"])
        pod.spec.affinity = Affinity(
            pod_affinity=PodAffinity(required=[_term(tier["affinity"])]) if tier["affinity"] else None,
            pod_anti_affinity=PodAntiAffinity(required=[_term(tier["anti_affinity"])]),
        )
        pods.append(pod)
    return pods
