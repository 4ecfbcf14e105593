"""A copy of ``benchmarks/`` with fixture files laid over it, for the tests
and for a run on the chip of a cell that is not (yet) a cell.

A fixture is a directory under ``tests/fixtures/`` shaped like
``benchmarks/`` itself: the files a later PR would add.  ``build`` copies
``benchmarks/`` (without its tests) and lays fixtures over the copy,
refusing to replace a file: what a fixture shows is that new files are
enough.  ``use`` points this process at the copy: ``run.HERE`` and
``manifest.HERE`` (and its ``ROOT``) for the data files, ``sys.path`` for the copy's makers,
references and readers (namespace packages, so they join those that are
there), and ``PYTHONPATH`` so that the copy's ``client.py``, a process of
its own beside no ``minisched_tpu/``, still finds the program.
"""

import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
FIXTURES = os.path.join(HERE, "fixtures")


def build(dest, *fixtures):
    copy_dir = os.path.join(str(dest), "benchmarks")
    shutil.copytree(BENCH, copy_dir, ignore=shutil.ignore_patterns("__pycache__", ".trace", "tests"))
    for fixture in fixtures:
        top = os.path.join(FIXTURES, fixture)
        for folder, _dirs, files in os.walk(top):
            if os.path.basename(folder) == "__pycache__":
                continue
            into = os.path.join(copy_dir, os.path.relpath(folder, top))
            os.makedirs(into, exist_ok=True)
            for name in files:
                if os.path.exists(os.path.join(into, name)):
                    raise FileExistsError(f"fixture {fixture}: {name} is a file the benchmark has")
                shutil.copy(os.path.join(folder, name), into)
    return copy_dir


def use(copy_dir, patch, setenv, syspath_prepend):
    """``patch``, ``setenv`` and ``syspath_prepend`` are pytest's
    ``monkeypatch`` methods, or ``setattr``, ``os.environ.__setitem__`` and
    a ``sys.path`` insert where nothing needs undoing."""
    import manifest
    import run

    patch(run, "HERE", copy_dir)
    patch(manifest, "HERE", copy_dir)
    patch(manifest, "ROOT", os.path.dirname(copy_dir))
    have = os.environ.get("PYTHONPATH")
    setenv("PYTHONPATH", os.path.dirname(BENCH) + (os.pathsep + have if have else ""))
    syspath_prepend(copy_dir)
