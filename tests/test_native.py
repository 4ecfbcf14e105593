"""Native host-table kernels: bit-equality with the Python reference, and
fast-path/slow-path table equivalence."""

from __future__ import annotations

import random

import numpy as np

from minisched_tpu import native
from minisched_tpu.api.objects import Toleration, make_pod
from minisched_tpu.models.tables import (
    _name_suffix,
    _pod_is_simple,
    build_pod_table,
    fnv1a32,
    pod_seed,
)


def _random_strings(rng: random.Random, n: int):
    alphabet = "abcdefghijklmnopqrstuvwxyz0123456789-."
    # "pod٧" ends in a Unicode (Arabic-Indic) digit: suffix must be -1 in
    # BOTH paths (Go's strconv.Atoi accepts ASCII digits only)
    out = ["", "x", "pod7", "日本語7", "pod٧", "pod²"]
    for _ in range(n):
        out.append(
            "".join(rng.choice(alphabet) for _ in range(rng.randrange(1, 40)))
        )
    return out


def test_native_kernels_match_python_reference():
    rng = random.Random(0)
    ss = _random_strings(rng, 500)
    assert native.fnv1a32_batch(ss).tolist() == [fnv1a32(s) for s in ss]
    assert native.name_suffix_batch(ss).tolist() == [_name_suffix(s) for s in ss]
    assert native.pod_seed_batch(ss).tolist() == [pod_seed(s) for s in ss]


def test_python_fallback_matches_native():
    rng = random.Random(1)
    ss = _random_strings(rng, 100)
    if not native.HAVE_NATIVE:
        return  # fallback IS the only path; covered above
    import minisched_tpu.native as n

    saved = n.HAVE_NATIVE
    try:
        n.HAVE_NATIVE = False
        fallback = (
            n.fnv1a32_batch(ss).tolist(),
            n.name_suffix_batch(ss).tolist(),
            n.pod_seed_batch(ss).tolist(),
        )
    finally:
        n.HAVE_NATIVE = saved
    assert fallback == (
        n.fnv1a32_batch(ss).tolist(),
        n.name_suffix_batch(ss).tolist(),
        n.pod_seed_batch(ss).tolist(),
    )


def test_fast_path_table_equals_slow_path():
    """The columnar fast path and the per-pod loop must produce identical
    PodTables for simple pods."""
    rng = random.Random(2)
    pods = [
        make_pod(
            f"pod{rng.randrange(10**6)}",
            requests={"cpu": rng.choice(["100m", "1"]), "memory": "512Mi"}
            if rng.random() < 0.5
            else None,
        )
        for i in range(50)
    ]
    assert all(_pod_is_simple(p) for p in pods)
    fast, fast_names = build_pod_table(pods)
    # force the slow path by marking one pod non-simple, then strip it
    poisoned = pods + [make_pod("t", tolerations=[Toleration(key="k")])]
    slow, slow_names = build_pod_table(poisoned)
    assert fast_names == slow_names[:-1]
    from dataclasses import fields

    for f in fields(type(fast)):
        a = np.asarray(getattr(fast, f.name))
        b = np.asarray(getattr(slow, f.name))
        # full-capacity comparison: padding rows must match too (the 51st
        # row of `slow` holds the poison pod — blank it to the fast path's
        # padding values before comparing)
        if f.name in ("num_tols", "tol_key", "tol_value", "valid", "req_pods",
                      "req_cpu", "req_mem", "seed", "num_containers"):
            b = b.copy()
            b[50] = a[50]
        assert (a == b).all(), f"column {f.name} differs between paths"


def test_non_simple_pods_take_slow_path():
    pod = make_pod("p", tolerations=[Toleration(key="k")])
    assert not _pod_is_simple(pod)
    table, _ = build_pod_table([pod])
    assert int(table.num_tols[0]) == 1


def test_stale_so_is_rebuilt_not_loaded(tmp_path, monkeypatch, capfd):
    """A library whose recorded source digest is not the current
    native/tablebuilder.cc's (or that has none) is rebuilt before loading;
    a failed build is one loud stderr line and the NumPy path."""
    import hashlib

    so = str(tmp_path / "libminisched_native.so")
    for attr in ("_lib", "HAVE_NATIVE"):  # restored after the test
        monkeypatch.setattr(native, attr, getattr(native, attr))
    with open(so, "wb") as f:
        f.write(b"not an ELF: a stale build from another checkout")
    with open(so + ".sha256", "w") as f:
        f.write("0" * 64 + "\n")
    native._load(so, native._SRC)
    assert native.HAVE_NATIVE
    with open(native._SRC, "rb") as f:
        want = hashlib.sha256(f.read()).hexdigest()
    with open(so + ".sha256") as f:
        assert f.read().strip() == want
    with open(so, "rb") as f:
        assert f.read(4) == b"\x7fELF"
    assert native.fnv1a32_batch(["pod7"]).tolist() == [fnv1a32("pod7")]

    bad_src = tmp_path / "broken.cc"
    bad_src.write_text("this is not C++")
    native._load(str(tmp_path / "other.so"), str(bad_src))
    assert not native.HAVE_NATIVE
    assert "g++ build of" in capfd.readouterr().err
    assert native.fnv1a32_batch(["pod7"]).tolist() == [fnv1a32("pod7")]
