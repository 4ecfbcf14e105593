"""The maker of ``antiaffinity-svc-5000n``: ``antiaffinity_host``'s nodes,
every one under a hostname label of its own, and pods dealt into services
by their running index alone.

Pod ``i`` of either kind (the running index ``make_pods`` is handed: the
init pods take the first, the measured pods the ones after) is of service
``k = service_of(i)``, ``mixed``'s dealing: service k holds
ln((k+1)/k) / ln(services+1) of the pods.  It carries the label
``app=svc-<k>`` and one required anti-affinity term selecting that label
(``match_expressions`` ``app In (svc-<k>)``) under the hostname key, with
no namespaces named: one replica of a service a host.  The seed changes
names and the order of the nodes, never the amounts: the same index is the
same service in every seed.  ``references/antiaffinity_svc.py`` says the
same dealing again in plain Python and holds the read-back to it.
"""

from makers.antiaffinity_host import make_nodes  # noqa: F401  (the nodes of this deployment)
from makers.mixed import service_of
from minisched_tpu.api.objects import (
    Affinity,
    LabelSelector,
    LabelSelectorRequirement,
    PodAffinityTerm,
    PodAntiAffinity,
    make_pod,
)


def make_pods(kind, prefix, start, count):
    mix = kind["mix"]
    term = mix["anti_affinity"]
    pods = []
    for i in range(start, start + count):
        app = f"{mix['service_prefix']}{service_of(i, mix)}"
        pod = make_pod(f"{prefix}-{i:07d}", requests=kind["requests"], labels={mix["service_label"]: app})
        selector = LabelSelector(match_expressions=[LabelSelectorRequirement(mix["service_label"], term["operator"], [app])])
        pod.spec.affinity = Affinity(
            pod_anti_affinity=PodAntiAffinity(
                required=[PodAffinityTerm(label_selector=selector, topology_key=term["topology_key"])]
            )
        )
        pods.append(pod)
    return pods
