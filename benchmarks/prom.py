"""A reader of the Prometheus text that ``/metrics`` serves.

The benchmark's own copy: the program has one in ``observability/hist.py``
(``parse_prometheus``), and a yardstick that imported it could be moved by
a later change to the program.
"""

from __future__ import annotations

import re
import urllib.request
from typing import Dict, List, Optional, Tuple

#: a label's quoted value may hold a brace (route="pod/{name}"), and a bucket's
#: line may go on after its value with an exemplar ( # {key="default/pod-1"} 0.043)
_LINE = re.compile(r'^([A-Za-z_:][A-Za-z0-9_:]*)(\{(?:[^"}]|"(?:[^"\\]|\\.)*")*\})?\s+(\S+)')
_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')

Sample = Tuple[str, Tuple[Tuple[str, str], ...], float]


def parse(text: str) -> List[Sample]:
    out: List[Sample] = []
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _LINE.match(line)
        if not m:
            continue
        labels = tuple(sorted(_LABEL.findall(m.group(2) or "")))
        out.append((m.group(1), labels, float(m.group(3))))
    return out


def scrape(base: str) -> List[Sample]:
    with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
        return parse(r.read().decode())


def total(samples: List[Sample], name: str, labels: Optional[Dict[str, str]] = None) -> float:
    """Sum of a series over all its label sets, or over those that carry
    every one of ``labels`` (0 where it is absent: the program registers a
    counter at its first increment)."""
    want = set((labels or {}).items())
    return sum(v for n, have, v in samples if n == name and want <= set(have))


def buckets(samples: List[Sample], name: str) -> Dict[float, float]:
    """Cumulative bucket counts of histogram ``name`` by upper bound,
    summed over every label but ``le``."""
    out: Dict[float, float] = {}
    for n, labels, v in samples:
        if n != name + "_bucket":
            continue
        le = dict(labels)["le"]
        bound = float("inf") if le == "+Inf" else float(le)
        out[bound] = out.get(bound, 0.0) + v
    return out
