#!/usr/bin/env python3
"""The control, at a cell's own size on the chip: the run has to come out
not correct.

    python3 benchmarks/tests/control_on_chip.py <fault> [--fixture <name>]... --workload <cell> --seed <n> --seconds <s>

``<fault>`` is a name in ``faults.py``, or ``none`` for the same run with
nothing broken (through a fixture, say).  The configurations state no
precision, so the control breaks one guarantee they do state: the stack is
booted and driven exactly as a benchmark run, with the bind path, or the
façade's delete, altered underneath.  ``--fixture`` lays files of
``tests/fixtures/<name>/`` over a copy of ``benchmarks/`` first (``overlay.py``): a
cell that is not in ``BENCHMARK.json`` runs at its own size so.  Exit 0
when ``correct`` came out false (true for ``none``), 1 otherwise.
"""

import io
import json
import os
import shutil
import sys
import tempfile
from contextlib import redirect_stdout

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)


def main() -> int:
    import faults
    import overlay
    import run

    name, argv = sys.argv[1], sys.argv[2:]
    fixtures = []
    while argv[:1] == ["--fixture"]:
        fixtures.append(argv[1])
        argv = argv[2:]
    if "--trace" not in argv:
        argv += ["--trace", "0"]
    scratch = tempfile.mkdtemp(prefix="bench-overlay.") if fixtures else None
    try:
        if fixtures:
            overlay.use(overlay.build(scratch, *fixtures), setattr, os.environ.__setitem__, lambda p: sys.path.insert(0, p))
        fault = None if name == "none" else getattr(faults, name)(setattr)
        out = io.StringIO()
        with redirect_stdout(out):
            run.main(argv, fault=fault)
    finally:
        if scratch:
            shutil.rmtree(scratch, ignore_errors=True)
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    failing = {k: v for k, v in result["compared"].items() if v["number"] > v["limit"]}
    print(json.dumps({"control": name, "correct": result["correct"], "failing": failing,
                      "failed": result["failed"], "attempted": result["attempted"],
                      "metrics": result["metrics"], "window": result["window"], "device": result["device"],
                      "breakdown": result.get("breakdown")}))
    return 0 if result["correct"] is (name == "none") else 1


if __name__ == "__main__":
    sys.exit(main())
