"""Executable-shape discipline: every padded capacity in the device
tables is an executable shape — a capacity that steps with cluster
content recompiles the wave evaluator MID-RUN (a full-roster compile
inside a wave).  These tests pin the quantization invariants so a
"small" capacity tweak can't silently reintroduce that class:

* node label/taint profiles (Dp) quantize to 64,
* combo/ex-term/claim/volume axes hold tiers (32, 256, 2048, ...:
  ``constraints.cap_tier``; tests/test_mixed_deployment.py steps over
  them) and the topology-key axis quantizes to 4,
* scan chunks use exactly two capacities, the blocked lane's wide layout
  one more, its narrow layout exactly one whatever it carries (a whole
  grouping that fits one narrow call takes it),
* pod tables have exactly TWO packed schemas per capacity (fast/slow),
  and the slow one can be force-packed below the size threshold (the
  prewarm relies on it).
"""

from __future__ import annotations

import numpy as np
import pytest

from minisched_tpu.api.objects import (
    LabelSelector,
    Taint,
    Toleration,
    TopologySpreadConstraint,
    make_node,
    make_pod,
)
from minisched_tpu.engine.device_scheduler import DeviceScheduler
from minisched_tpu.models.constraints import build_constraint_tables
from minisched_tpu.models.tables import (
    build_node_table,
    build_pod_table,
    node_profile_capacity,
)
from minisched_tpu.observability import counters


def _spread_pod(name: str, app: str) -> object:
    pod = make_pod(name, labels={"app": app})
    pod.spec.topology_spread_constraints = [
        TopologySpreadConstraint(
            max_skew=1,
            topology_key="zone",
            when_unsatisfiable="ScheduleAnyway",
            label_selector=LabelSelector(match_labels={"app": app}),
        )
    ]
    return pod


def test_profile_capacity_stable_under_growth():
    """1 profile and 50 profiles land on the same Dp=64 plane."""
    few = [make_node(f"n{i}") for i in range(10)]
    many = [
        make_node(f"n{i}", labels={"zone": f"z{i}"}, taints=[Taint(f"k{i}", "v", "NoSchedule")])
        for i in range(50)
    ]
    assert node_profile_capacity(few) == 64
    assert node_profile_capacity(many) == 64
    t_few, _ = build_node_table(few)
    t_many, _ = build_node_table(many, capacity=t_few.capacity)
    assert np.asarray(t_few.prof_label_key).shape == np.asarray(t_many.prof_label_key).shape
    assert np.asarray(t_few.prof_taint_key).shape == np.asarray(t_many.prof_taint_key).shape


def test_constraint_capacities_stable_under_growth():
    """1 combo and 20 combos (and their topo keys) share one table shape."""
    nodes = [make_node(f"n{i}", labels={"zone": f"z{i % 4}"}) for i in range(8)]
    one = build_constraint_tables([_spread_pod("p0", "a")], nodes, [])
    twenty = build_constraint_tables(
        [_spread_pod(f"p{i}", f"app{i}") for i in range(20)], nodes, [],
        pod_capacity=np.asarray(one.ts_combo).shape[0],
    )
    for field in ("combo_dsum", "combo_here", "combo_excl", "claim_mask",
                  "vol_any", "topo_domain", "topo_onehot"):
        assert (
            np.asarray(getattr(one, field)).shape
            == np.asarray(getattr(twenty, field)).shape
        ), field


def test_scan_chunks_use_exactly_two_capacities():
    caps = {DeviceScheduler._scan_cap(n) for n in (1, 64, 128, 129, 700, 1024)}
    assert caps == {DeviceScheduler.SCAN_MIN_CAP, DeviceScheduler.SCAN_MAX_CHUNK}
    # the blocked lane adds exactly one bigger tier
    bcaps = {
        DeviceScheduler._blocked_cap(n)
        for n in (1, 128, 129, 1024, 1025, 4096, 4097)
    }
    assert bcaps == {
        DeviceScheduler.SCAN_MIN_CAP,
        DeviceScheduler.SCAN_MAX_CHUNK,
        DeviceScheduler.BLOCKED_MAX_CHUNK,
    }
    # chunks above the top tier never exceed it (the stride pins them)
    assert DeviceScheduler._blocked_cap(
        DeviceScheduler.BLOCKED_MAX_CHUNK
    ) == DeviceScheduler.BLOCKED_MAX_CHUNK


def _grouping(head_blocks, suffix):
    """``head_blocks`` blocks of 32, 31, ... live pods, then ``suffix``
    blocks of one."""
    B = DeviceScheduler.SCAN_BLOCK_SIZE
    head = [
        [f"h{b}.{i}" for i in range(B - b)] + [None] * b
        for b in range(head_blocks)
    ]
    tail = [[f"s{i}"] + [None] * (B - 1) for i in range(suffix)]
    return head, tail


#: the live pods of three head blocks (32 + 31 + 30)
HEAD3 = 93


@pytest.mark.parametrize("head_blocks", [0, 3])
@pytest.mark.parametrize(
    "suffix",
    [1, 33, DeviceScheduler.SCAN_MAX_CHUNK - HEAD3,
     DeviceScheduler.SCAN_MAX_CHUNK - HEAD3 + 1, 1024, 1025, 8192],
)
def test_narrow_layout_runs_at_exactly_one_capacity(suffix, head_blocks):
    """Whatever the length of the run of one-pod blocks a grouping ends
    in, its calls share ONE pod capacity (a short last call pads up to
    it), carry at most SCAN_MAX_CHUNK live pods each and keep block order;
    the blocks before it keep the wide layout's tiers and run first —
    unless the whole grouping's live pods fit one narrow call (at most
    SCAN_MAX_CHUNK, the edge is a case of its own), which is then the
    grouping's one call, every live pod in block order."""
    S = DeviceScheduler
    B, W = S.SCAN_BLOCK_SIZE, S.SCAN_NARROW_WIDTH
    head, tail = _grouping(head_blocks, suffix)
    folded_before = counters.get("scan.calls_folded")
    calls = S._plan_blocked_calls(head + tail)
    folded = counters.get("scan.calls_folded") - folded_before
    narrow = [(part, cap) for is_narrow, part, cap in calls if is_narrow]
    wide = [(part, cap) for is_narrow, part, cap in calls if not is_narrow]
    assert [is_narrow for is_narrow, _, _ in calls] == (
        [False] * len(wide) + [True] * len(narrow)
    )
    assert {cap for _, cap in narrow} == {S.SCAN_MAX_CHUNK * W}
    for part, cap in narrow:
        assert len(part) <= cap and len(part) % W == 0
        assert all((m is not None) == (i % W == 0) for i, m in enumerate(part))
    live_head = [m for b in head for m in b if m]
    if head and len(live_head) + suffix <= S.SCAN_MAX_CHUNK:
        # head and tail fit one narrow call: it is the only one, and it
        # saved the call the tail would have taken
        assert not wide and len(narrow) == 1 and folded == 1
        assert [m for m in narrow[0][0] if m] == live_head + [
            f"s{i}" for i in range(suffix)
        ]
        return
    assert folded == 0
    assert len(narrow) == -(-suffix // S.SCAN_MAX_CHUNK)
    assert [m for part, _ in narrow for m in part if m] == [
        f"s{i}" for i in range(suffix)
    ]
    assert [m for part, _ in wide for m in part] == [m for b in head for m in b]
    assert {cap for _, cap in wide} <= {S._blocked_cap(B * head_blocks)}


@pytest.mark.parametrize("layout", ["wide", "narrow"])
def test_a_plan_of_one_call_is_left_as_it_was(layout):
    """A grouping its fill already lays out as one call keeps that call:
    full blocks alone stay one wide call at their tier, one-pod blocks
    alone one narrow call, and no call counts as folded."""
    S = DeviceScheduler
    head, tail = _grouping(3, 0) if layout == "wide" else _grouping(0, 40)
    folded_before = counters.get("scan.calls_folded")
    calls = S._plan_blocked_calls(head + tail)
    assert calls == S._calls_by_fill(head + tail)
    assert counters.get("scan.calls_folded") == folded_before
    ((is_narrow, rows, cap),) = calls
    if layout == "wide":
        assert not is_narrow and cap == S._blocked_cap(len(rows))
        assert rows == [m for b in head for m in b]
    else:
        assert is_narrow and cap == S.SCAN_MAX_CHUNK * S.SCAN_NARROW_WIDTH
        assert [m for m in rows if m] == [f"s{i}" for i in range(40)]


def test_pod_table_has_two_schemas_per_capacity():
    """Simple pods share ONE fast schema; any non-simple pod shares ONE
    slow schema — a third schema per capacity would be a new mid-run
    compile (prewarm only warms these two)."""
    from minisched_tpu.models.tables import _col_metas

    def schema(pods):
        t, _ = build_pod_table(pods, capacity=128)
        cols = {
            f.name: np.asarray(getattr(t, f.name))
            for f in type(t).__dataclass_fields__.values()
        }
        return _col_metas(cols)

    simple_a = schema([make_pod("a", requests={"cpu": "1"})])
    simple_b = schema([make_pod("b")])
    slow_sel = schema([make_pod("c", node_selector={"x": "y"})])
    slow_tol = schema([make_pod("d", tolerations=[Toleration("k", "v")])])
    assert simple_a == simple_b
    assert slow_sel == slow_tol
    # fast and slow MATERIALIZE identically (shapes/dtypes) — only the
    # wire-side splitter schema differs (zero_metas) — so the evaluator
    # executable is shared between them
    assert simple_a == slow_sel


def test_force_packed_builds_splitter_below_threshold():
    """The prewarm warms the small-cap slow splitter via force_packed —
    without it the build falls under the packed-path size threshold and
    warms nothing.  Pinned via the splitter cache: a FRESH small slow
    schema must create a splitter entry only when force_packed asks."""
    from minisched_tpu.models import tables as T

    pod = make_pod("warmsel", node_selector={"warm": "true"})
    # negative control: first-ever build of a fresh small schema takes
    # the per-leaf path (no splitter compiled)
    before = T._flat_splitter.cache_info().currsize
    build_pod_table([pod], capacity=132)  # unique cap → unseen schema
    assert T._flat_splitter.cache_info().currsize == before
    # force_packed on another fresh schema builds the splitter NOW
    build_pod_table([pod], capacity=136, force_packed=True)
    assert T._flat_splitter.cache_info().currsize == before + 1
