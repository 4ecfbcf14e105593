#!/usr/bin/env python3
"""The one sweep that fixes an open-loop cell's rate: stages of the window's
length at rising rates, in one process and one set-up.

    python3 benchmarks/tests/sweep_on_chip.py <cell> <seed> <seconds> <rate> <rate> ...

One JSON line a stage: what was sent, what was outstanding when the stage
closed, the bind times and the generator's own lateness.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main() -> int:
    import run

    cell = run.load_cell(sys.argv[1])
    seed, seconds = int(sys.argv[2]), float(sys.argv[3])
    with run.warm_stack(cell, seed) as st:
        for rate in (float(r) for r in sys.argv[4:]):
            stage = dict(st.traffic, rate_per_s=rate)
            w = st.client.call("run", traffic=stage, phase=f"r{rate}", seconds=seconds)
            g = st.client.call("grace", seconds=30)
            print(json.dumps({"rate_per_s": rate, **w, **g}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
