#!/usr/bin/env python3
"""``control_on_chip.py`` with ``faults_antiaffinity_host.py``'s control
beside ``faults.py``'s: the same arguments, the same exit code.

    python3 benchmarks/tests/control_antiaffinity_host_on_chip.py green_as_plain --workload antiaffinity-host-5000n.recycle-1k --seed <n> --seconds <s>
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import control_on_chip
import faults
import faults_antiaffinity_host

faults.green_as_plain = faults_antiaffinity_host.green_as_plain

if __name__ == "__main__":
    sys.exit(control_on_chip.main())
