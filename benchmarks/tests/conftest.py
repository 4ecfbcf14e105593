"""The benchmark's own tests (not part of the repo's tier-1 suite).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

They run on the CPU: the rehearsal is reached only as a Python argument of
``run.main``, never from the command line.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.dirname(BENCH), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


import pytest


@pytest.fixture(autouse=True)
def a_trace_directory_of_its_own(tmp_path, monkeypatch):
    """``run.py`` keeps a traced run's trace at one fixed path, which two
    traced rehearsals at once (the workers of ``-n``) would empty under
    each other."""
    import run

    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path / "trace"))
