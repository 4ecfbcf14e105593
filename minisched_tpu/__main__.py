"""Process entry point: boot the whole stack from environment config.

Re-creates ``sched.go``'s ``main``/``start()`` boot order (sched.go:21-68):
read the env config (PORT / FRONTEND_URL / optional external store URL),
bring up the control plane (the REST façade on PORT — the reference boots
a real apiserver), start the PV controller, start the scheduler service,
then serve until interrupted.

    PORT=10251 FRONTEND_URL=http://localhost:3000 python -m minisched_tpu

Subcommands:

    python -m minisched_tpu fsck <wal> [--checkpoint PATH]
                                       [--digests] [--compare OTHER]

        offline storage-integrity check (controlplane/fsck): WAL frame
        CRCs, checkpoint sha256 sidecars (both generations), replay
        through the real recovery path, rv/uid monotonicity, the
        per-node aggregate index, and the exactly-once bind audit.
        Prints a JSON report; exit 1 on any integrity error.
        ``--digests`` emits per-frame CRC32C digests (the offline half
        of the replicated plane's digest gossip); ``--compare OTHER``
        diffs two replica WALs — exit 1 iff the histories diverged
        (one being a prefix of the other is a follower catching up).

    python -m minisched_tpu metrics <url>

        scrape ``<url>/metrics`` (the REST façade or an engine's
        metricsd sidecar) and pretty-print the snapshot: counters,
        gauges, and per-histogram count/p50/p99/max bucket bounds.

Optional env:

    MINISCHED_TPU_STORE_URL=file:///tmp/cluster.wal   durable WAL store
                                                      (reference: etcd URL)
    MINISCHED_DEVICE_MODE=1                           TPU wave engine; logs
                                                      the platform/device it
                                                      got (a CPU-only JAX runs
                                                      it on XLA:CPU) and keeps
                                                      its compile cache under
                                                      JAX_COMPILATION_CACHE_DIR
                                                      (utils/compilecache)
    MINISCHED_MESH_DEVICES=8                          pin an N-device mesh
                                                      (overrides the policy)
    MINISCHED_MESH=0|1                                mesh policy when no pin
                                                      is set: 0 = never,
                                                      1 = always (all visible
                                                      devices), unset = auto
                                                      when >1 device
                                                      (parallel/sharding.
                                                      resolve_mesh)
"""

from __future__ import annotations

import os
import signal
import sys
import threading

from minisched_tpu.controlplane.client import DEFAULT_BURST, DEFAULT_QPS, Client
from minisched_tpu.controlplane.durable import store_from_url
from minisched_tpu.controlplane.httpserver import start_api_server
from minisched_tpu.controlplane.pvcontroller import start_pv_controller
from minisched_tpu.service.config import (
    ProcessConfig,
    default_full_roster_config,
    default_scheduler_config,
)
from minisched_tpu.service.service import SchedulerService


def _announce_device(sched, cache_dir) -> None:
    """Say which device the wave engine runs on — one stderr line plus
    ``engine.*`` gauges on /metrics.  JAX picks the backend, so without
    this a "TPU wave engine" on XLA:CPU looks exactly like one on a chip."""
    import jax

    from minisched_tpu.observability import counters

    dev = jax.devices()[0]
    mesh = sched.mesh
    layout = (
        "single-device"
        if mesh is None
        else "mesh " + "x".join(map(str, mesh.devices.shape))
    )
    counters.set_gauge(
        f"engine.device.{dev.platform}.{dev.device_kind.replace(' ', '_')}",
        jax.device_count(),
    )
    counters.set_gauge(
        "engine.mesh_devices", 0 if mesh is None else int(mesh.devices.size)
    )
    print(
        f"minisched_tpu: device engine on platform={dev.platform} "
        f"device_kind={dev.device_kind!r} devices={jax.device_count()} "
        f"layout={layout} compile_cache={cache_dir}",
        file=sys.stderr,
        flush=True,
    )


def start(cfg: ProcessConfig, device_mode: bool = False, mesh_devices: int = 0):
    """Boot the stack; returns (client, api_base_url, stop_fn).  The live
    SchedulerService rides on ``stop_fn.service`` for embedders that need
    the engine itself (chip_smoke.py inspects its compiled programs)."""
    # validate the flag combination BEFORE booting any component — failing
    # after the store/API server/PV controller are live would leak their
    # threads and the open WAL with no stop path
    if mesh_devices and not device_mode:
        raise ValueError(
            "MINISCHED_MESH_DEVICES requires MINISCHED_DEVICE_MODE=1 — the "
            "scalar engine cannot shard waves"
        )
    store = store_from_url(cfg.external_store_url)
    # the reference's client limits (k8sapiserver.go:57-62: QPS/Burst 5000)
    client = Client(store=store, qps=DEFAULT_QPS, burst=DEFAULT_BURST)
    backing = client.store
    # the HTTP façade serves the SAME store the in-process client uses
    raw = getattr(backing, "_store", backing)  # unwrap any rate limiter
    server, base, shutdown_api = start_api_server(raw, port=cfg.port)
    pv = start_pv_controller(client)
    service = SchedulerService(client)
    scheduler_cfg = (
        default_full_roster_config() if device_mode else default_scheduler_config()
    )
    mesh = None
    cache_dir = None
    if device_mode:
        # every way of booting the device engine shares one compile cache
        # (main(), chip_smoke.py, embedders) — not main() alone
        from minisched_tpu.utils.compilecache import enable_persistent_cache

        cache_dir = enable_persistent_cache()
        if mesh_devices:
            from minisched_tpu.parallel.sharding import make_mesh

            mesh = make_mesh(mesh_devices)
    sched = service.start_scheduler(
        scheduler_cfg, device_mode=device_mode, device_mesh=mesh
    )
    if device_mode:
        _announce_device(sched, cache_dir)

    def stop() -> None:
        service.close()
        pv.stop()
        shutdown_api()
        if hasattr(raw, "close"):
            raw.close()

    stop.service = service
    return client, base, stop


def main() -> int:
    if len(sys.argv) > 1 and sys.argv[1] == "fsck":
        # the integrity CLI must not boot JAX or the scheduler stack —
        # it runs against dead files, often on a box mid-incident
        from minisched_tpu.controlplane.fsck import main as fsck_main

        return fsck_main(sys.argv[2:])
    if len(sys.argv) > 1 and sys.argv[1] == "metrics":
        # scrape CLI: like fsck, must not boot JAX or the scheduler —
        # it only fetches and parses another process's exposition
        from minisched_tpu.observability.metricsd import scrape_main

        return scrape_main(sys.argv[2:])
    cfg = ProcessConfig.from_env()
    device_mode = os.environ.get("MINISCHED_DEVICE_MODE", "0") == "1"
    mesh_devices = int(os.environ.get("MINISCHED_MESH_DEVICES", "0"))
    _, base, stop = start(
        cfg, device_mode=device_mode, mesh_devices=mesh_devices
    )
    print(f"minisched_tpu: API on {base} (frontend {cfg.frontend_url})", flush=True)
    done = threading.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *_: done.set())
    done.wait()
    stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
