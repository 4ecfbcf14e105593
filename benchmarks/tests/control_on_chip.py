#!/usr/bin/env python3
"""The control, at a cell's own size on the chip: the run has to come out
not correct.

    python3 benchmarks/tests/control_on_chip.py <fault> --workload <cell> --seed <n> --seconds <s>

``<fault>`` is a name in ``faults.py``.  The configurations state no
precision, so the control breaks one guarantee they do state: the stack is
booted and driven exactly as a benchmark run, with the bind path altered
underneath.  Exit 0 when ``correct`` came out false, 1 when the broken run
passed.
"""

import io
import json
import os
import sys
from contextlib import redirect_stdout

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)


def main() -> int:
    import faults
    import run

    fault = getattr(faults, sys.argv[1])(setattr)
    out = io.StringIO()
    with redirect_stdout(out):
        run.main(sys.argv[2:] + ["--trace", "0"], fault=fault)
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    failing = {k: v for k, v in result["compared"].items() if v["number"] > v["limit"]}
    print(json.dumps({"control": sys.argv[1], "correct": result["correct"], "failing": failing,
                      "failed": result["failed"], "attempted": result["attempted"]}))
    return 0 if result["correct"] is False else 1


if __name__ == "__main__":
    sys.exit(main())
