"""The controls of ``colocate-host-5000n``'s own number ``affinity_broken``:
ways to break the timed path underneath a run, as ``faults.py``'s (each
returns a ``fault(service)`` for ``run.main(..., fault=)``; ``patch`` is
``setattr`` or pytest's ``monkeypatch.setattr``).

``web_as_plain``  every web reaches the scheduling queue as a plain pod: a
                copy without its terms, so the engine routes it to the
                packed wave and places it by the resource scores alone,
                mostly on the empty hosts.  The store keeps the web as it
                was sent, so the read-back still carries its terms.
``cache_delete_late``  the constraint index counts a cache down
                ``CACHE_DELETE_LATE_S`` after its ``DELETE`` reached the
                engine: for that long the forward row still admits webs to
                the host the cache left, and the resource scores send them
                there, since the host is emptier than one that holds a
                cache.  The half of the number the first control cannot
                reach: a cache that was there once and is gone.

``anti_affinity_broken`` and ``term_dropped`` have their controls in
``faults_antiaffinity_host.py`` (the same interval rule) and on pods written
by hand (``test_colocate_host.py``).
"""

from __future__ import annotations

#: how long after its DELETE a cache still counts in ``cache_delete_late``
CACHE_DELETE_LATE_S = 2.0


def web_as_plain(patch):
    def fault(_service):
        from minisched_tpu.queue.queue import SchedulingQueue

        real = SchedulingQueue._add_locked

        def add_locked(self, pod, requeue=False, born=0.0):
            if pod.metadata.labels.get("app") == "web-store" and pod.spec.affinity is not None:
                pod = pod.clone()
                pod.spec.affinity = None
            real(self, pod, requeue=requeue, born=born)

        patch(SchedulingQueue, "_add_locked", add_locked)

    return fault


def cache_delete_late(patch, late_s=CACHE_DELETE_LATE_S):
    def fault(_service):
        import collections
        import threading
        import time

        from minisched_tpu.models.constraint_index import ConstraintIndex

        real = ConstraintIndex._delete
        due = collections.deque()  # (instant, index, uid), in order of instant
        cv = threading.Condition()

        def delete(self, uid):
            pod = self._pods.get(uid)
            if pod is None or pod.metadata.labels.get("app") != "store":
                real(self, uid)
                return
            with cv:
                due.append((time.monotonic() + late_s, self, uid))
                cv.notify()

        def count_down():
            while True:
                with cv:
                    while not due or due[0][0] > time.monotonic():
                        cv.wait(due[0][0] - time.monotonic() if due else None)
                    _t, index, uid = due.popleft()
                with index.lock():
                    real(index, uid)

        patch(ConstraintIndex, "_delete", delete)
        threading.Thread(target=count_down, name="cache-delete-late", daemon=True).start()

    return fault
