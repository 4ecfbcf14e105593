"""The controls of ``antiaffinity-svc-5000n``'s own number
``anti_affinity_broken``: ways to break the timed path underneath a run,
as ``faults.py``'s (each returns a ``fault(service)`` for
``run.main(..., fault=)``; ``patch`` is ``setattr`` or pytest's
``monkeypatch.setattr``).

``svc_as_plain``  every service pod reaches the scheduling queue as a plain
                pod: a copy without its term, so the engine routes it to
                the packed wave and places it by the resource scores alone,
                blind to where its service's replicas are.  The store keeps
                the pod as it was sent, so the read-back still carries its
                term.
``block_carry_dropped``  the blocked lane's calls are built as if no pending
                pod matched any term's selector: what a committed pod writes
                to its service's row (the count on its host and the ban of
                its host) reaches no later block of its call.  The placed
                pods' rows, built on the host from the constraint index,
                still ban; so does each pod's own term against them.  The
                half of the number the first control cannot reach: pods of
                one service committed in one call.

``term_dropped`` has its control on pods written by hand
(``test_antiaffinity_svc.py``).
"""

from __future__ import annotations


def svc_as_plain(patch):
    def fault(_service):
        from minisched_tpu.queue.queue import SchedulingQueue

        real = SchedulingQueue._add_locked

        def add_locked(self, pod, requeue=False, born=0.0):
            if pod.metadata.labels.get("app", "").startswith("svc-") and pod.spec.affinity is not None:
                pod = pod.clone()
                pod.spec.affinity = None
            real(self, pod, requeue=requeue, born=born)

        patch(SchedulingQueue, "_add_locked", add_locked)

    return fault


def block_carry_dropped(patch):
    def fault(_service):
        import threading

        import numpy as np

        from minisched_tpu.engine.device_scheduler import DeviceScheduler
        from minisched_tpu.models import tables

        in_blocked_call = threading.local()
        real_chunk, real_pack = DeviceScheduler._run_blocked_chunk, tables.pack_table

        def run_blocked_chunk(self, *args, **kwargs):
            in_blocked_call.on = True
            try:
                return real_chunk(self, *args, **kwargs)
            finally:
                in_blocked_call.on = False

        def pack_table(cols, *args, **kwargs):
            if getattr(in_blocked_call, "on", False) and "pod_matches_combo" in cols:
                cols = dict(cols, pod_matches_combo=np.zeros_like(cols["pod_matches_combo"]))
            return real_pack(cols, *args, **kwargs)

        patch(DeviceScheduler, "_run_blocked_chunk", run_blocked_chunk)
        patch(tables, "pack_table", pack_table)

    return fault
