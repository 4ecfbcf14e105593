#!/usr/bin/env python3
"""``BENCHMARK.json`` from the files under ``benchmarks/``, and its self-check.

    python3 benchmarks/manifest.py            # check BENCHMARK.json
    python3 benchmarks/manifest.py --write    # write it anew from the files

The manifest repeats what the files of each configuration, cell and metric
say; this builds it from them (with ``head.json`` for what belongs to none
of them: the command, the paths, the window's length and the end-to-end
metrics with their bounds; which cells report a metric is worked out from
the cells' own files), so the two cannot drift, and ``check`` holds
the result to the alphabet and the limits the driver refuses a manifest
over.  PR 22 died of one space in a ``layer``.
"""

from __future__ import annotations

import json
import os
import re
import sys
from typing import Any, Dict, List

import run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def _load(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def _dir(name: str) -> List[Dict[str, Any]]:
    return [_load(name, f) for f in sorted(os.listdir(os.path.join(HERE, name))) if f.endswith(".json")]


def build() -> Dict[str, Any]:
    """Worked out from the cell side: a cell's file names its configuration,
    its traffic (whose file says which end-to-end metrics the mix reports)
    and its per-layer metrics, so a new cell edits no file that is there."""
    head = _load("head.json")
    cells = {c["name"]: c for c in _dir("workloads")}
    configs = {c["name"]: c for c in _dir("configs")}
    traffic = {t["name"]: t for t in _dir("traffic")}

    def reporting(metric: str) -> List[str]:
        return [n for n, c in cells.items() if metric in traffic[c["traffic"]]["end_to_end"] + ["setup_s"]]

    def listing(metric: str) -> List[str]:
        return [n for n, c in cells.items() if metric in c["per_layer"]]

    return {
        "command": head["command"],
        "paths": head["paths"],
        "run_seconds": head["run_seconds"],
        "configs": [
            {
                "name": c["name"],
                "source": c["source"],
                "file": f"benchmarks/configs/{c['name']}.json",
                "reduced": c["reduced"],
                "why": c["deployment"],
            }
            for c in configs.values()
            if any(cell["config"] == c["name"] for cell in cells.values())
        ],
        "workloads": [
            {k: c[k] for k in ("name", "config", "traffic", "chips", "why")} for c in cells.values()
        ],
        "end_to_end": [
            dict(m) if len(reporting(m["name"])) == len(cells) else dict(m, workloads=reporting(m["name"]))
            for m in head["end_to_end"]
            if reporting(m["name"])
        ],
        "per_layer": [
            dict({k: m[k] for k in ("name", "unit", "better", "source", "layer", "moves")}, workloads=listing(m["name"]))
            for m in sorted(_dir("metrics"), key=lambda m: (m["layer"], m["name"]))
            if listing(m["name"])
        ],
    }


def check(manifest: Dict[str, Any]) -> List[str]:
    """Everything wrong with a manifest, in words; empty when it stands."""
    bad: List[str] = []

    def name_ok(what: str, value: Any) -> None:
        if not isinstance(value, str) or not NAME.match(value):
            bad.append(f"{what}: {value!r} is not a name")

    def line_ok(what: str, value: Any) -> None:
        if not isinstance(value, str) or not 1 <= len(value) <= 200 or "\n" in value or "\t" in value:
            bad.append(f"{what}: not one line of 1 to 200 characters")

    if set(manifest) != {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}:
        bad.append(f"keys: {sorted(manifest)}")
    if not 1 <= manifest["run_seconds"] <= 51 or int(manifest["run_seconds"]) != manifest["run_seconds"]:
        bad.append("run_seconds: a whole number from 1 to 51")
    for word in manifest["command"]:
        line_ok("command", word)
    cells = {w["name"]: w for w in manifest["workloads"]}
    configs = {c["name"]: c for c in manifest["configs"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in manifest[group]]
        if len(names) != len(set(names)):
            bad.append(f"{group}: a name appears twice")
    for c in manifest["configs"]:
        name_ok("config", c["name"])
        line_ok(f"{c['name']}.source", c["source"])
        line_ok(f"{c['name']}.why", c["why"])
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config {c['name']}: keys {sorted(c)}")
        if not os.path.isfile(os.path.join(ROOT, c["file"])):
            bad.append(f"config {c['name']}: no file {c['file']}")
        if not any(c["file"].startswith(p + "/") for p in manifest["paths"]):
            bad.append(f"config {c['name']}: {c['file']} is outside paths")
        for key in c["reduced"]:
            name_ok(f"{c['name']}.reduced", key)
        if os.path.isfile(os.path.join(ROOT, c["file"])):
            with open(os.path.join(ROOT, c["file"])) as f:
                data = json.load(f)
            for door, folder in (("maker", "makers"), ("reference", "references")):
                if data.get(door) and not os.path.isfile(os.path.join(HERE, folder, data[door] + ".py")):
                    bad.append(f"config {c['name']}: no benchmarks/{folder}/{data[door]}.py")
            if run.rehearsal_keys_refused(data):
                bad.append(
                    f"config {c['name']}: rehearsal {run.rehearsal_keys_refused(data)}: not sizes a configuration may state"
                )
        if not any(w["config"] == c["name"] for w in manifest["workloads"]):
            bad.append(f"config {c['name']}: no cell uses it")
    pairs = set()
    for w in manifest["workloads"]:
        name_ok("cell", w["name"])
        name_ok("traffic", w["traffic"])
        line_ok(f"{w['name']}.why", w["why"])
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"cell {w['name']}: keys {sorted(w)}")
        if w["config"] not in configs:
            bad.append(f"cell {w['name']}: unknown config {w['config']}")
        if w["chips"] not in (1, 4):
            bad.append(f"cell {w['name']}: chips {w['chips']}")
        if (w["config"], w["traffic"]) in pairs:
            bad.append(f"cell {w['name']}: its pair of config and traffic appears twice")
        pairs.add((w["config"], w["traffic"]))
        for kind, fname in (("workloads", w["name"]), ("traffic", w["traffic"]), ("configs", w["config"])):
            if not os.path.isfile(os.path.join(HERE, kind, fname + ".json")):
                bad.append(f"cell {w['name']}: no benchmarks/{kind}/{fname}.json")
    if sum(w["chips"] == 4 for w in manifest["workloads"]) > max(1, len(cells) // 2):
        bad.append("more than half of the cells ask for 4 chips")
    if "setup_s" not in e2e:
        bad.append("end_to_end: no setup_s")
    reports: Dict[str, set] = {n: set() for n in cells}
    for m in manifest["end_to_end"]:
        name_ok("end_to_end", m["name"])
        if not UNIT.match(m["unit"]):
            bad.append(f"{m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"{m['name']}: better {m['better']!r}")
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"{m['name']}: an end-to-end source is host_clock or device_trace")
        if not 0.01 <= m["bound"] <= 0.25:
            bad.append(f"{m['name']}: bound {m['bound']}")
        if not set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}:
            bad.append(f"{m['name']}: keys {sorted(m)}")
        for w in m.get("workloads", list(cells)):
            if w not in cells:
                bad.append(f"{m['name']}: unknown cell {w}")
            else:
                reports[w].add(m["name"])
    for w, have in reports.items():
        if "setup_s" not in have or len(have) < 2:
            bad.append(f"cell {w}: reports {sorted(have)}; needs setup_s and one more")
    layered = set()
    for m in manifest["per_layer"]:
        name_ok("per_layer", m["name"])
        name_ok(f"{m['name']}.layer", m["layer"])
        if not UNIT.match(m["unit"]):
            bad.append(f"{m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"{m['name']}: better {m['better']!r}")
        if m["source"] not in SOURCES:
            bad.append(f"{m['name']}: source {m['source']!r}")
        if set(m) - {"workloads"} != {"name", "unit", "better", "source", "layer", "moves"}:
            bad.append(f"{m['name']}: keys {sorted(m)}")
        if m["moves"] not in e2e or m["moves"] == "setup_s":
            bad.append(f"{m['name']}: moves {m['moves']!r}")
        for w in m.get("workloads", list(cells)):
            if w not in cells:
                bad.append(f"{m['name']}: unknown cell {w}")
            elif m["moves"] not in reports[w]:
                bad.append(f"{m['name']}: cell {w} does not report {m['moves']}")
            else:
                layered.add(w)
    for w in set(cells) - layered:
        bad.append(f"cell {w}: no per-layer metric")
    if len(json.dumps(manifest)) > 64 * 1024:
        bad.append("over 64 KiB")
    return bad


def main() -> int:
    path = os.path.join(ROOT, "BENCHMARK.json")
    built = build()
    if "--write" in sys.argv:
        with open(path, "w") as f:
            json.dump(built, f, indent=1)
            f.write("\n")
    with open(path) as f:
        on_disk = json.load(f)
    bad = check(on_disk)
    if on_disk != built:
        bad.append("BENCHMARK.json and the files under benchmarks/ disagree: run with --write")
    for line in bad:
        print("manifest:", line)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
