"""Ways to break the timed path underneath a run, for the tests and the
control: each returns a ``fault(service)`` for ``run.main(..., fault=)``.

All but the last two patch the program's bind path
(``controlplane.client._PodAPI``), which every placement the engine makes
goes through on its way to the store:

``overcommit``  the control of the basic cells: the store's capacity gate
                is off and every bind is altered, where it is produced, to
                name one node, which ends far over its allocatable;
``one_zone``    the control of the spread cell: every bind of a pod that
                carries a spread constraint is altered to a node of the
                first zone, so the zones end skewed;
``drop_half``   half of each batch is left out: acknowledged to the engine,
                never written;
``drop_all``    a step that returns its state unchanged: nothing is written;
``delete_swallowed``  the control of the numbers a mix that deletes adds:
                the façade answers a ``DELETE`` 200 and deletes nothing;
``green_as_plain``  the control of the anti-affinity deployment: the engine
                routes every pod as a plain one, so the green pods ride the
                packed wave, whose pods are blind to each other.

``patch`` is ``setattr`` or pytest's ``monkeypatch.setattr``.
"""

from __future__ import annotations

import zlib


def _wrap_bind(patch, rewrite):
    from minisched_tpu.controlplane import client as cp

    real = cp._PodAPI.bind_many

    def bind_many(self, bindings, return_objects=True):
        kept = rewrite(self, list(bindings))
        results = iter(real(self, kept, return_objects=return_objects))
        kept_ids = {id(b) for b in kept}
        return [next(results) if id(b) in kept_ids else None for b in bindings]

    patch(cp._PodAPI, "bind_many", bind_many)


def overcommit(patch):
    def fault(service):
        from minisched_tpu.controlplane import client as cp

        patch(cp._PodAPI, "_node_budgets", staticmethod(lambda store, targets: {}))

        def rewrite(_api, bindings):
            target = min(n.metadata.name for n in service.informer_factory.informer_for("Node").lister())
            for b in bindings:
                b.node_name = target
            return bindings

        _wrap_bind(patch, rewrite)

    return fault


def one_zone(patch):
    def fault(service):
        state = {"i": 0}

        def rewrite(api, bindings):
            nodes = service.informer_factory.informer_for("Node").lister()
            zone_key = "topology.kubernetes.io/zone"
            zones = sorted({n.metadata.labels[zone_key] for n in nodes if zone_key in n.metadata.labels})
            first = sorted(n.metadata.name for n in nodes if n.metadata.labels.get(zone_key) == zones[0])
            for b in bindings:
                pod = api._store.get("Pod", b.pod_namespace, b.pod_name)
                if pod.spec.topology_spread_constraints:
                    b.node_name = first[state["i"] % len(first)]
                    state["i"] += 1
            return bindings

        _wrap_bind(patch, rewrite)

    return fault


def _drop(patch, keep):
    def fault(_service):
        _wrap_bind(patch, lambda _api, bindings: [b for b in bindings if keep(b)])

    return fault


def drop_half(patch):
    return _drop(patch, lambda b: zlib.crc32(b.pod_name.encode()) % 2 == 0 or "-init-" in b.pod_name)


def drop_all(patch):
    return _drop(patch, lambda b: "-init-" in b.pod_name)


def delete_swallowed(patch):
    def fault(_service):
        from minisched_tpu.controlplane import httpserver

        patch(httpserver._Handler, "_handle_delete", lambda self: self._send(200, {}))

    return fault


def green_as_plain(patch):
    def fault(_service):
        from minisched_tpu.engine import device_scheduler

        # both the loop and the build worker look the function up at each wave
        patch(device_scheduler, "_is_cross_pod", lambda _pod: False)

    return fault
