"""Test bootstrap: force JAX onto a virtual 8-device CPU mesh.

Tests never require TPU hardware; multi-chip sharding is validated on
virtual CPU devices (the driver's ``dryrun_multichip`` does the same).
What only a chip can show is chip_smoke.py's job.

``JAX_PLATFORMS`` may arrive set to anything (a chip machine exports
``tpu,cpu``), and a plugin that imported jax first would have read it
already — so the platform is forced BOTH ways before the first backend
initialization: the env var for child processes, ``jax.config`` for this
one.  The virtual device count rides ``XLA_FLAGS`` (read lazily at
CPU-client creation).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
# XLA:CPU logs two ERROR lines per persistent-cache load (a benign
# pseudo-feature mismatch; utils/compilecache.py).  Engine threads load
# programs between tests too, outside pytest's capture, and the lines land
# in the middle of the progress dots the tier-1 command counts.  Real XLA
# failures still surface as Python exceptions.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# the live engine auto-shards when >1 device is visible (ISSUE 7,
# parallel/sharding.resolve_mesh) — on this 8-virtual-device test mesh
# that would silently flip EVERY engine test to the sharded path (and
# its compile bills).  Pin the default off; the mesh-live suite
# (tests/test_mesh_live.py) opts in per test with an explicit mesh or
# MINISCHED_MESH=1.
os.environ.setdefault("MINISCHED_MESH", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert jax.devices()[0].platform == "cpu", (
    "tests must run on the virtual CPU mesh, got " + repr(jax.devices())
)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from minisched_tpu.utils.compilecache import enable_persistent_cache  # noqa: E402

enable_persistent_cache()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running soak, excluded from tier-1 (-m 'not slow')",
    )
