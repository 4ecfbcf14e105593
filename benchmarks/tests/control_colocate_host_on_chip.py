#!/usr/bin/env python3
"""``control_on_chip.py`` with ``faults_colocate_host.py``'s controls beside
``faults.py``'s: the same arguments, the same exit code.

    python3 benchmarks/tests/control_colocate_host_on_chip.py web_as_plain --workload colocate-host-5000n.recycle-1k --seed <n> --seconds <s>
    python3 benchmarks/tests/control_colocate_host_on_chip.py cache_delete_late --workload colocate-host-5000n.recycle-1k --seed <n> --seconds <s>
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import control_on_chip
import faults
import faults_colocate_host

faults.web_as_plain = faults_colocate_host.web_as_plain
faults.cache_delete_late = faults_colocate_host.cache_delete_late

if __name__ == "__main__":
    sys.exit(control_on_chip.main())
