"""The controls of ``mixed-5000n``'s own three numbers: ways to break the
timed path underneath a run, as ``faults.py``'s (each returns a
``fault(service)`` for ``run.main(..., fault=)``; ``patch`` is ``setattr``
or pytest's ``monkeypatch.setattr``).

``untolerated_into_pool``  the control of ``taint_not_tolerated``: every
                bind of a measured pod that tolerates nothing, selects
                nothing and spreads with nothing (the plain kind) is
                altered, where it is produced, to a node of the tainted
                pool;
``constraint_stripped``    the control of ``service_constraint_dropped``:
                the façade stores every eighth pod it is sent without its
                spread constraints, so the program schedules a service pod
                as a plain one;
``pinned_never_bound``     the control of ``kinds_missing``: the binds of
                the pods pinned to a zone are acknowledged to the engine
                and never written.
"""

from __future__ import annotations

import zlib

import faults


def untolerated_into_pool(patch):
    def fault(service):
        state = {"i": 0}

        def rewrite(api, bindings):
            nodes = service.informer_factory.informer_for("Node").lister()
            pool = sorted(n.metadata.name for n in nodes if n.spec.taints and not n.spec.unschedulable)
            for b in bindings:
                if "-init-" in b.pod_name:
                    continue
                spec = api._store.get("Pod", b.pod_namespace, b.pod_name).spec
                if not (spec.tolerations or spec.node_selector or spec.topology_spread_constraints):
                    b.node_name = pool[state["i"] % len(pool)]
                    state["i"] += 1
            return bindings

        faults._wrap_bind(patch, rewrite)

    return fault


def constraint_stripped(patch):
    def fault(_service):
        from minisched_tpu.controlplane import httpserver

        real = httpserver._fixup_namespace

        def fixup(kind, ns, obj):
            real(kind, ns, obj)
            if type(obj).__name__ == "Pod" and zlib.crc32(obj.metadata.name.encode()) % 8 == 0:
                obj.spec.topology_spread_constraints = []

        patch(httpserver, "_fixup_namespace", fixup)

    return fault


def pinned_never_bound(patch):
    return faults._drop(patch, lambda b: "-init-" in b.pod_name or int(b.pod_name.rsplit("-", 1)[1]) % 16 != 0)
