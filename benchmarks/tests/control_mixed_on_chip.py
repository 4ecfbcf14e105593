#!/usr/bin/env python3
"""``control_on_chip.py`` with ``faults_mixed.py``'s controls beside
``faults.py``'s: the same arguments, the same exit code.

    python3 benchmarks/tests/control_mixed_on_chip.py <fault> --workload mixed-5000n.drain --seed <n> --seconds <s>
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import control_on_chip
import faults
import faults_mixed

for _name in ("untolerated_into_pool", "constraint_stripped", "pinned_never_bound"):
    setattr(faults, _name, getattr(faults_mixed, _name))

if __name__ == "__main__":
    sys.exit(control_on_chip.main())
