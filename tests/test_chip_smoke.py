"""ISSUE 21 bring-up contracts that a CPU run can hold: chip_smoke.py's
drive-and-audit at a tiny size (the chip check is skipped HERE, not by a
flag in the script), the script's non-zero exit off-chip, compile-cache
placement, import-time backend hygiene, and park visibility."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

import jax

from minisched_tpu.api.objects import make_node, make_pod
from minisched_tpu.controlplane.client import Client
from minisched_tpu.observability import counters, hist
from minisched_tpu.service.config import default_scheduler_config
from minisched_tpu.service.service import SchedulerService

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, env_extra: dict, cwd: str = REPO, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "-c", code], env=env, cwd=cwd,
        capture_output=True, text=True, timeout=300,
    )


def test_drive_and_audit_tiny_on_cpu(monkeypatch):
    """The function chip_smoke.py runs at 5,000 nodes x 10,000 pods, at 64
    nodes x 348 pods: booted through __main__.start, driven over HTTP, all
    four lanes dispatched, REST/metrics/trace audits clean.  A burst of 40
    fits one narrow call, which the engine's own plan would make of it:
    the engine lays it out by its fill here (a wide head, a narrow tail),
    as it does the full size's burst of 1,100."""
    import chip_smoke
    from minisched_tpu.engine.device_scheduler import DeviceScheduler

    monkeypatch.setattr(
        DeviceScheduler, "_plan_blocked_calls", DeviceScheduler._calls_by_fill
    )
    sizes = chip_smoke.Sizes(nodes=64, plain=300, burst=40, tail=8)
    with chip_smoke.booted_stack() as (base, service):
        programs = chip_smoke.drive_and_audit(
            base, service, sizes, seed=0,
            platform=jax.devices()[0].platform,
        )
    # off-TPU the Pallas route is dead code: the same programs lower
    # without the Mosaic call chip_smoke.py demands on the chip
    assert not any(
        "tpu_custom_call" in text for lane in programs.values() for text in lane
    )


def test_chip_smoke_exits_nonzero_without_a_tpu():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300,
    )
    assert r.returncode != 0
    assert "FAILED phase=device" in r.stderr
    assert "JAX_PLATFORMS='cpu'" in r.stderr and "CpuDevice" in r.stderr
    assert '"ok"' not in r.stdout


_CACHE_PROBE = """
import json, os, jax
calls = []
orig = jax.config.update
jax.config.update = lambda k, v: (calls.append(k), orig(k, v))[1]
from minisched_tpu.utils import compilecache
print(json.dumps({
    "effective": compilecache.enable_persistent_cache(),
    "default": compilecache._DEFAULT_DIR,
    "default_exists": os.path.isdir(compilecache._DEFAULT_DIR),
    "set_in_code": "jax_compilation_cache_dir" in calls,
}))
"""


def test_compile_cache_placement(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: exactly that directory, nothing set
    in code, <checkout>/.jax_cache not created.  Unset: <checkout>/.jax_cache
    directly.  The "checkout" is a shadow tree holding only compilecache.py,
    so the real one's existing cache cannot answer for it."""
    checkout = tmp_path / "checkout"
    pkg = checkout / "minisched_tpu" / "utils"
    pkg.mkdir(parents=True)
    (checkout / "minisched_tpu" / "__init__.py").write_text("")
    (pkg / "__init__.py").write_text("")
    shutil.copy(
        os.path.join(REPO, "minisched_tpu", "utils", "compilecache.py"), pkg
    )
    placed = str(tmp_path / "x")
    env = {"PYTHONPATH": str(checkout), "JAX_PLATFORMS": "cpu"}

    r = _run(
        _CACHE_PROBE, {**env, "JAX_COMPILATION_CACHE_DIR": placed},
        cwd=str(checkout), drop=("MINISCHED_CACHE",),
    )
    assert r.returncode == 0, r.stderr
    got = json.loads(r.stdout.splitlines()[-1])
    assert got["effective"] == placed
    assert not got["set_in_code"] and not got["default_exists"]

    r = _run(
        _CACHE_PROBE, env, cwd=str(checkout),
        drop=("MINISCHED_CACHE", "JAX_COMPILATION_CACHE_DIR"),
    )
    assert r.returncode == 0, r.stderr
    got = json.loads(r.stdout.splitlines()[-1])
    assert got["default"] == str(checkout / ".jax_cache")
    assert got["effective"] == got["default"] and got["default_exists"]


_IMPORT_PROBE = """
import importlib, pkgutil
from jax._src import xla_bridge
import minisched_tpu
for m in pkgutil.walk_packages(minisched_tpu.__path__, "minisched_tpu."):
    if m.name.endswith(("__main__", "libminisched_native")):
        continue
    importlib.import_module(m.name)
    assert not xla_bridge.backends_are_initialized(), m.name
print("clean")
"""


def test_no_module_initialises_a_backend_at_import():
    """A parent that imports the engine must not take the chip: its child
    that needs it would fail or hang (ops/fused.py built a device scalar at
    module scope)."""
    r = _run(_IMPORT_PROBE, {"PYTHONPATH": REPO})
    assert r.returncode == 0 and "clean" in r.stdout, r.stderr[-2000:]


def test_forced_evaluate_failure_is_counted_and_requeued(monkeypatch, capfd):
    """A device call that raises parks its wave — visibly: wave.parked
    (total and per cause) on /metrics and one stderr line, with no debug
    variable set; the pods still requeue and bind."""
    monkeypatch.delenv("MINISCHED_DEBUG_HEAL", raising=False)
    client = Client()
    svc = SchedulerService(client)
    sched = svc.start_scheduler(
        default_scheduler_config(time_scale=0.01), device_mode=True,
        max_wave=16,
    )
    before = counters.snapshot()
    real = sched._eval_packed_wave
    fired = []

    def refuse_once(*args, **kwargs):
        if not fired:
            fired.append(1)
            raise RuntimeError("Mosaic failed to compile TPU kernel")
        return real(*args, **kwargs)

    sched._eval_packed_wave = refuse_once
    try:
        client.nodes().create(make_node("node0"))
        client.pods().create_many(
            [make_pod(f"parked{i}") for i in range(8)], return_objects=False
        )
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and not fired:
            time.sleep(0.02)
        # a cluster event re-activates whatever the park sent to the
        # unschedulable queue
        client.nodes().create(make_node("node1"))
        while time.monotonic() < deadline and not all(
            p.spec.node_name for p in client.pods().list()
        ):
            time.sleep(0.05)
        assert all(p.spec.node_name for p in client.pods().list())
    finally:
        svc.shutdown_scheduler()

    def delta(name):
        return counters.get(name) - before.get(name, 0)

    assert delta("wave.parked") == 1
    assert delta("wave.parked.RuntimeError") == 1
    assert (
        f"wave_parked_RuntimeError {counters.get('wave.parked.RuntimeError')}"
        in hist.render_prometheus()
    )
    err = capfd.readouterr().err
    assert "[wave] parked" in err and "Mosaic failed to compile" in err
