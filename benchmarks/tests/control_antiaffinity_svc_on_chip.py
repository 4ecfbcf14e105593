#!/usr/bin/env python3
"""``control_on_chip.py`` with ``faults_antiaffinity_svc.py``'s controls
beside ``faults.py``'s: the same arguments, the same exit code.

    python3 benchmarks/tests/control_antiaffinity_svc_on_chip.py svc_as_plain --workload antiaffinity-svc-5000n.recycle-1k --seed <n> --seconds <s>
    python3 benchmarks/tests/control_antiaffinity_svc_on_chip.py block_carry_dropped --workload antiaffinity-svc-5000n.recycle-1k --seed <n> --seconds <s>
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import control_on_chip
import faults
import faults_antiaffinity_svc

faults.svc_as_plain = faults_antiaffinity_svc.svc_as_plain
faults.block_carry_dropped = faults_antiaffinity_svc.block_carry_dropped

if __name__ == "__main__":
    sys.exit(control_on_chip.main())
