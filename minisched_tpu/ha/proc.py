"""Engines as killable child processes: real SIGKILL failover.

faults/proc.py kills the CONTROL PLANE; this module kills a SCHEDULER —
the other half of the HA story.  An :class:`EngineSupervisor` runs one HA
engine (ha/plane.start_ha_engine over a RemoteClient) in a fresh
``python -c`` child, SIGKILLs it on demand (no lease release, no queue
drain — the member just stops renewing), and the survivors must observe
the expiry through the watch path, bump their epochs, and adopt the
orphaned shard within the lease TTL.

Same process hygiene as the server supervisor: a fresh interpreter (the
parent's JAX runtime and threads never leak in), a parent-death watchdog
so an aborted soak strands no children, and readiness gated on
OBSERVABLE state — the child's member lease appearing live in the store,
the engine-side analog of polling /healthz.

Children are pinned to ``JAX_PLATFORMS=cpu`` by default, device-mode ones
included: a chip belongs to ONE process, so N engine children cannot
share it (the second fails or hangs at backend start).  One engine per
chip is the only device-mode layout; a supervisor that wants it passes
``jax_platforms=""`` (inherit) for exactly one child per chip and stays
off JAX itself.  The child's stderr is inherited, not discarded — it is
where the engine says which device it got and why a wave parked.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time
from typing import Any, Optional


def _engine_child_main(
    base_url: str,
    engine_id: str,
    ttl_s: float = 2.0,
    device_mode: bool = False,
    max_wave: int = 64,
    parent_pid: Optional[int] = None,
    metrics_port: Optional[int] = None,
) -> None:
    """The child's whole life: join the plane over the wire, schedule,
    park until SIGKILL.  Runs in a fresh interpreter — import inside.

    ``metrics_port`` arms the sidecar telemetry listener
    (observability.metricsd): THIS engine process's histograms, counters
    and trace ring become scrapeable at ``/metrics`` / ``/debug/trace``
    — the engine has no façade of its own, so without the sidecar its
    telemetry dies with it."""
    from hashlib import blake2s

    from minisched_tpu.controlplane.remote import RemoteClient
    from minisched_tpu.ha.plane import start_ha_engine
    from minisched_tpu.service.config import default_full_roster_config

    if metrics_port is not None:
        from minisched_tpu.observability.metricsd import start_metrics_server

        start_metrics_server(port=metrics_port)

    # per-engine deterministic retry jitter (hash() is salted per process)
    seed = int.from_bytes(
        blake2s(engine_id.encode(), digest_size=4).digest(), "big"
    )
    client = RemoteClient(
        base_url, retries=10, backoff_initial_s=0.05, retry_seed=seed
    )
    start_ha_engine(
        client,
        engine_id,
        cfg=default_full_roster_config(),
        ttl_s=ttl_s,
        device_mode=device_mode,
        max_wave=max_wave,
    )
    if parent_pid:
        # orphan watchdog (see faults/proc.py: polling beats
        # PR_SET_PDEATHSIG-via-preexec_fn, which forces unsafe fork)
        def watchdog() -> None:
            while os.getppid() == parent_pid:
                time.sleep(0.5)
            os.kill(os.getpid(), signal.SIGKILL)

        threading.Thread(target=watchdog, daemon=True).start()
    threading.Event().wait()  # until SIGKILL — crashes don't say goodbye


_CHILD_CMD = (
    "import json, sys; "
    "from minisched_tpu.ha.proc import _engine_child_main; "
    "_engine_child_main(**json.loads(sys.argv[1]))"
)


class EngineSupervisor:
    """Run one HA scheduler engine as a killable child process."""

    def __init__(
        self,
        base_url: str,
        engine_id: str,
        ttl_s: float = 2.0,
        device_mode: bool = False,
        max_wave: int = 64,
        boot_timeout_s: float = 90.0,
        jax_platforms: str = "cpu",
        metrics_port: Optional[int] = None,
    ):
        self._base = base_url
        self.engine_id = engine_id
        self._ttl_s = ttl_s
        self._device_mode = device_mode
        self._max_wave = max_wave
        self._boot_timeout_s = boot_timeout_s
        self._jax_platforms = jax_platforms
        # metrics_port=0 asks for an ephemeral one picked NOW (the
        # parent must know the port to build metrics_url; the same port
        # is reused across restarts, like the server supervisor's)
        if metrics_port == 0:
            from minisched_tpu.faults.proc import _free_port

            metrics_port = _free_port()
        self._metrics_port = metrics_port
        self._proc: Any = None
        self.kills = 0

    @property
    def pid(self) -> Optional[int]:
        return self._proc.pid if self._proc is not None else None

    @property
    def metrics_url(self) -> Optional[str]:
        """Scrape URL of the child's telemetry sidecar, or None when the
        supervisor was built without ``metrics_port``."""
        if self._metrics_port is None:
            return None
        return f"http://127.0.0.1:{self._metrics_port}/metrics"

    def alive(self) -> bool:
        return self._proc is not None and self._proc.poll() is None

    def _lease_live(self) -> bool:
        """Is the child's member lease present and unexpired? — the
        readiness (and liveness) probe, read straight off the plane."""
        from minisched_tpu.controlplane.remote import RemoteStore
        from minisched_tpu.ha.lease import HA_NAMESPACE
        from minisched_tpu.ha.membership import MEMBER_PREFIX

        store = RemoteStore(self._base, retries=1, timeout_s=5.0)
        try:
            lease = store.get(
                "Lease", HA_NAMESPACE, MEMBER_PREFIX + self.engine_id
            )
        except Exception:
            return False
        return not lease.expired(time.time())

    def start(self) -> None:
        """Spawn the child and block until its member lease is live —
        the engine is then joined, synced, and scheduling its shard."""
        if self.alive():
            raise RuntimeError(f"engine {self.engine_id!r} already running")
        cfg = {
            "base_url": self._base,
            "engine_id": self.engine_id,
            "ttl_s": self._ttl_s,
            "device_mode": self._device_mode,
            "max_wave": self._max_wave,
            "parent_pid": os.getpid(),
            "metrics_port": self._metrics_port,
        }
        env = dict(os.environ)
        repo_root = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
        if self._jax_platforms:
            env["JAX_PLATFORMS"] = self._jax_platforms
        self._proc = subprocess.Popen(
            [sys.executable, "-c", _CHILD_CMD, json.dumps(cfg)],
            env=env,
            stdout=subprocess.DEVNULL,
        )
        deadline = time.monotonic() + self._boot_timeout_s
        while time.monotonic() < deadline:
            if self._proc.poll() is not None:
                raise RuntimeError(
                    f"engine child {self.engine_id!r} died at boot "
                    f"(exitcode {self._proc.returncode})"
                )
            if self._lease_live():
                return
            time.sleep(0.1)
        raise RuntimeError(
            f"engine child {self.engine_id!r} never joined the plane "
            f"within {self._boot_timeout_s}s"
        )

    def kill(self) -> None:
        """SIGKILL — the lease stays behind, un-renewed; survivors must
        time it out and adopt the shard."""
        if self._proc is None:
            return
        if self._proc.poll() is None:
            self._proc.kill()
            self.kills += 1
        try:
            self._proc.wait(timeout=10.0)
        except subprocess.TimeoutExpired:
            pass
        self._proc = None

    def stop(self) -> None:
        self.kill()
