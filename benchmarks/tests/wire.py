"""What a configuration puts on the wire for one seed, as a digest.

The nodes, the first create of the init pods and one create of measured
pods are encoded as ``RemoteClient`` encodes them and hashed.  Values that
are empty (``None``, ``""``, ``0``, ``{}``, ``[]``) are struck out first,
so that a field the program adds later with an empty default does not move
the digest: what it pins is what the maker sets.

    python3 benchmarks/tests/wire.py <commit's benchmarks dir>   # prints the recording
"""

import hashlib
import json
import os
import sys

SEED = 3000000019
CONFIGS = ("basic-5000n", "spread-5000n")


def _set_only(value):
    if isinstance(value, dict):
        kept = {k: _set_only(v) for k, v in value.items()}
        return {k: v for k, v in kept.items() if v not in (None, "", 0, {}, [])}
    if isinstance(value, list):
        return [_set_only(v) for v in value]
    return value


def digests(maker, config, seed=SEED):
    from minisched_tpu.controlplane.checkpoint import _encode

    groups = {
        "nodes": maker.make_nodes(config, seed),
        "init_pods": maker.make_pods(config["init_pods"], f"s{seed}-init", 0, 1000),
        "measured_pods": maker.make_pods(config["measured_pods"], f"s{seed}-pod", 4096, 1024),
    }
    out = {}
    for name, objects in groups.items():
        encoded = [_set_only(_encode(o)) for o in objects]
        body = json.dumps(encoded, sort_keys=True).encode()
        out[name] = {"objects": len(encoded), "sha256": hashlib.sha256(body).hexdigest(), "first": encoded[0]}
    return out


if __name__ == "__main__":
    bench = os.path.abspath(sys.argv[1])
    sys.path[:0] = [os.path.dirname(bench), bench]
    import cluster

    recording = {}
    for name in CONFIGS:
        with open(os.path.join(bench, "configs", name + ".json")) as f:
            recording[name] = digests(cluster, json.load(f))
    print(json.dumps({"seed": SEED, "configs": recording}, indent=1))
