"""The control of ``antiaffinity-host-5000n``'s own number
``anti_affinity_broken``: a way to break the timed path underneath a run,
as ``faults.py``'s (it returns a ``fault(service)`` for
``run.main(..., fault=)``; ``patch`` is ``setattr`` or pytest's
``monkeypatch.setattr``).

``green_as_plain``  the engine routes every pod as a plain one, so the
                green pods ride the packed wave: each still sees the hosts
                that placed pods ban (the reverse direction is in the
                wave's filter too), but the pods of one wave are blind to
                each other, and two of them take one free host.

``term_dropped`` has no control of its own on the timed path: the
reference counts it on pods written by hand (``test_antiaffinity_host.py``).
"""

from __future__ import annotations


def green_as_plain(patch):
    def fault(_service):
        from minisched_tpu.engine import device_scheduler

        # both the loop and the build worker look the function up at each wave
        patch(device_scheduler, "_is_cross_pod", lambda _pod: False)

    return fault
