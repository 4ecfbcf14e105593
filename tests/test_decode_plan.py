"""``checkpoint._decode`` through a plan built once per type (ISSUE 27).

The recursive decoder this replaced is kept below as the plain reference:
it imports nothing of the program's codec, and every object the plans
decode is held against its answer, for every REST kind, bare and with
every optional part filled."""

from __future__ import annotations

import copy
import dataclasses
import json
import threading
import time
import typing
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple, Union

import pytest

from minisched_tpu.api import objects as o
from minisched_tpu.controlplane import checkpoint
from minisched_tpu.controlplane.checkpoint import _decode, _encode
from minisched_tpu.controlplane.client import Client
from minisched_tpu.controlplane.httpserver import (
    REST_KINDS,
    HTTPClient,
    start_api_server,
)
from minisched_tpu.observability import counters


def reference_decode(tp: Any, data: Any) -> Any:
    """The decoder as it was before the plans: a recursion on the
    annotation that derives every dataclass's hints at every object."""
    if data is None:
        return None
    origin = typing.get_origin(tp)
    if origin is typing.Union:  # Optional[X]
        args = [a for a in typing.get_args(tp) if a is not type(None)]
        return reference_decode(args[0], data)
    if origin in (list, tuple):
        (item_tp,) = typing.get_args(tp)[:1] or (Any,)
        return [reference_decode(item_tp, v) for v in data]
    if origin is dict:
        _, val_tp = typing.get_args(tp) or (Any, Any)
        return {k: reference_decode(val_tp, v) for k, v in data.items()}
    if dataclasses.is_dataclass(tp):
        hints = typing.get_type_hints(tp)
        kwargs = {
            f.name: reference_decode(hints[f.name], data[f.name])
            for f in dataclasses.fields(tp)
            if f.name in data
        }
        return tp(**kwargs)
    return data


# ---------------------------------------------------------------------------
# one object of every kind, bare and full
# ---------------------------------------------------------------------------


def _meta(name: str, namespace: str = "default") -> o.ObjectMeta:
    return o.ObjectMeta(
        name=name,
        namespace=namespace,
        uid=f"obj-{len(name):08d}",
        labels={"app": "web", "color": "blue"},
        annotations={"note": "a b c"},
        resource_version=41,
        creation_timestamp=1234.5,
    )


def _selector() -> o.LabelSelector:
    return o.LabelSelector(
        match_labels={"color": "blue"},
        match_expressions=[
            o.LabelSelectorRequirement("tier", "In", ["a", "b"]),
            o.LabelSelectorRequirement("legacy", "DoesNotExist"),
        ],
    )


def _pod_term(key: str) -> o.PodAffinityTerm:
    return o.PodAffinityTerm(_selector(), key, ["default", "team-a"])


def _full_pod() -> o.Pod:
    node_term = o.NodeSelectorTerm([o.LabelSelectorRequirement("zone", "In", ["moon-1"])])
    resources = o.ResourceList(100, 500 * o.MIB, 1, 7, {"example.com/gpu": 2})
    return o.Pod(
        metadata=_meta("full"),
        spec=o.PodSpec(
            node_name="node-3",
            containers=[
                o.Container("main", "img:1", resources, resources.clone(), [80, 443]),
                o.Container("side"),
            ],
            node_selector={"disk": "ssd"},
            tolerations=[
                o.Toleration("dedicated", o.TOLERATION_OP_EQUAL, "batch", o.TAINT_EFFECT_NO_SCHEDULE),
                o.Toleration(operator=o.TOLERATION_OP_EXISTS),
            ],
            affinity=o.Affinity(
                node_affinity=o.NodeAffinity(
                    required_terms=[node_term, o.NodeSelectorTerm()],
                    preferred=[o.PreferredSchedulingTerm(30, node_term)],
                ),
                pod_affinity=o.PodAffinity(
                    required=[_pod_term("zone")],
                    preferred=[o.WeightedPodAffinityTerm(10, _pod_term("rack"))],
                ),
                pod_anti_affinity=o.PodAntiAffinity(
                    required=[_pod_term("kubernetes.io/hostname")],
                    preferred=[o.WeightedPodAffinityTerm(5, _pod_term("zone"))],
                ),
            ),
            topology_spread_constraints=[
                o.TopologySpreadConstraint(1, "zone", "DoNotSchedule", _selector()),
                o.TopologySpreadConstraint(2, "rack", "ScheduleAnyway"),
            ],
            volumes=["claim-a", "claim-b"],
            priority=7,
            scheduler_name="other",
            gang=o.GangSpec("g", 4, 12.5),
        ),
        status=o.PodStatus(
            phase=o.POD_RUNNING,
            conditions=[{"type": "PodScheduled", "status": "True"}, {}],
            nominated_node_name="node-9",
        ),
    )


def _full_node() -> o.Node:
    node = o.make_node(
        "full",
        unschedulable=True,
        labels={"zone": "moon-1"},
        capacity={o.CPU: "4", o.MEMORY: "32Gi", o.PODS: 110, "example.com/gpu": 8},
        taints=[o.Taint("dedicated", "batch"), o.Taint("gone", effect=o.TAINT_EFFECT_NO_EXECUTE)],
        slice_id="s7",
        torus=(1, 2, 3),
        host_index=5,
        slice_dims=(4, 4, 2),
    )
    node.metadata.annotations = {"a": "b"}
    node.status.images = {"img:1": 123456}
    return node


OBJECTS = {
    "Node-bare": o.Node(metadata=o.ObjectMeta(name="n")),
    "Node-full": _full_node(),
    "Pod-bare": o.Pod(metadata=o.ObjectMeta(name="p")),
    "Pod-full": _full_pod(),
    "PersistentVolume-bare": o.PersistentVolume(metadata=o.ObjectMeta(name="pv")),
    "PersistentVolume-full": o.PersistentVolume(
        _meta("pv", ""), o.PVSpec(5 << 30, "default/claim-a", {"zone": "moon-1"}, "ebs")
    ),
    "PersistentVolumeClaim-bare": o.PersistentVolumeClaim(metadata=o.ObjectMeta(name="c")),
    "PersistentVolumeClaim-full": o.PersistentVolumeClaim(
        _meta("claim-a"), o.PVCSpec(1 << 30, "pv", True, "gcepd"), o.PVCStatus("Bound")
    ),
    "Lease-bare": o.Lease(metadata=o.ObjectMeta(name="l")),
    "Lease-full": o.Lease(_meta("member-0", "kube-system"), o.LeaseSpec("me", 2.5, 10.0, 11.5, 3, 8)),
    "Event-bare": o.Event(),
    "Event-full": o.Event(_meta("ev"), "Warning", "FailedScheduling", "0/3 nodes", "default/full", "x"),
}


def _containers(value: Any, seen: Dict[int, Any]) -> Dict[int, Any]:
    """Every list and dict reachable from ``value``, by id."""
    if dataclasses.is_dataclass(value):
        for f in dataclasses.fields(value):
            _containers(getattr(value, f.name), seen)
    elif isinstance(value, (list, dict)):
        seen[id(value)] = value
        for v in value.values() if isinstance(value, dict) else value:
            _containers(v, seen)
    return seen


@pytest.mark.parametrize("case", sorted(OBJECTS))
def test_every_kind_decodes_to_the_same_object(case):
    obj = OBJECTS[case]
    tp = REST_KINDS[case.split("-")[0]]
    assert type(obj) is tp
    wire = json.loads(json.dumps(_encode(obj)))  # what a request body is
    kept = copy.deepcopy(wire)
    got = _decode(tp, wire)
    assert type(got) is tp
    assert got == obj
    assert got == reference_decode(tp, wire)
    assert wire == kept  # the body is read, not changed
    shared = set(_containers(got, {})) & set(_containers(wire, {}))
    assert not shared, "the object holds a list or dict of the request body"
    assert _encode(got) == wire


# ---------------------------------------------------------------------------
# what the recursion did at the edges, kept
# ---------------------------------------------------------------------------


def _full_wire() -> Dict[str, Any]:
    return json.loads(json.dumps(_encode(OBJECTS["Pod-full"])))


def test_a_missing_key_takes_the_dataclass_default():
    wire = _full_wire()
    del wire["spec"]["gang"]  # a document from before the field existed
    del wire["spec"]["containers"][0]["limits"]
    del wire["status"]
    del wire["metadata"]["labels"]
    got = _decode(o.Pod, wire)
    assert got == reference_decode(o.Pod, wire)
    assert got.spec.gang is None
    assert got.spec.containers[0].limits == o.ResourceList()
    assert got.status == o.PodStatus()
    assert got.metadata.labels == {}
    assert _decode(o.Pod, {"metadata": {"name": "x"}}) == o.Pod(o.ObjectMeta(name="x"))


def test_an_unknown_key_is_ignored_at_every_level():
    wire = _full_wire()
    wire["apiVersion"] = "v1"
    wire["metadata"]["managedFields"] = [{"manager": "kubectl"}]
    wire["spec"]["containers"][0]["securityContext"] = {"privileged": False}
    wire["spec"]["affinity"]["pod_affinity"]["required"][0]["label_selector"]["x"] = 1
    got = _decode(o.Pod, wire)
    assert got == OBJECTS["Pod-full"]
    assert got == reference_decode(o.Pod, wire)


NONE_AT = [
    (),
    ("metadata",),
    ("metadata", "labels"),
    ("spec",),
    ("spec", "containers"),
    ("spec", "containers", 0),
    ("spec", "containers", 0, "requests"),
    ("spec", "containers", 0, "requests", "scalar"),
    ("spec", "containers", 0, "ports"),
    ("spec", "node_selector"),
    ("spec", "affinity"),
    ("spec", "affinity", "node_affinity", "required_terms"),
    ("spec", "affinity", "pod_affinity", "preferred", 0, "term"),
    ("spec", "topology_spread_constraints", 0, "label_selector", "match_expressions"),
    ("spec", "gang"),
    ("spec", "priority"),
    ("status", "conditions", 1),
]


@pytest.mark.parametrize("path", NONE_AT, ids=lambda p: ".".join(map(str, p)) or "top")
def test_none_decodes_to_none_at_every_level(path):
    wire: Any = _full_wire()
    if not path:
        wire = None
    else:
        holder = wire
        for step in path[:-1]:
            holder = holder[step]
        holder[path[-1]] = None
    got = _decode(o.Pod, wire)
    assert got == reference_decode(o.Pod, wire)
    at = got
    for step in path:
        at = at[step] if isinstance(step, int) else getattr(at, step)
    assert at is None


MALFORMED = {
    # the recursion took a string for a mapping that names no field
    # (``"node_name" in "abc"``) and answered with defaults; a plan reads
    # ``.items()`` and refuses it like any other non-mapping
    "spec-a-string": {"metadata": {"name": "x"}, "spec": "abc"},
    "spec-a-field-name": {"metadata": {"name": "x"}, "spec": "containers"},
    "spec-a-number": {"metadata": {"name": "x"}, "spec": 3},
    "spec-a-list": {"metadata": {"name": "x"}, "spec": [["node_name", "n"]]},
    "containers-a-number": {"metadata": {"name": "x"}, "spec": {"containers": 5}},
    "container-a-string": {"metadata": {"name": "x"}, "spec": {"containers": ["main"]}},
    "labels-a-list": {"metadata": {"name": "x", "labels": ["a", "b"]}},
    "no-metadata": {"spec": {}},
    "body-a-list": [{"metadata": {"name": "x"}}],
    "body-a-string": "pod",
}


@pytest.mark.parametrize("case", sorted(MALFORMED))
def test_malformed_input_raises(case):
    with pytest.raises(Exception):
        _decode(o.Pod, MALFORMED[case])


@dataclass
class _Tree:
    """Refers to itself, directly and through containers."""

    name: str = ""
    left: Optional[_Tree] = None
    kids: List[_Tree] = field(default_factory=list)
    by_name: Dict[str, _Tree] = field(default_factory=dict)


@dataclass
class _Shapes:
    pair: Tuple[_Tree, ...] = ()
    either: Union[None, _Tree, int] = None
    anything: Any = None
    bare: list = field(default_factory=list)
    ints: Tuple[int, ...] = ()


def test_a_self_referring_type_decodes():
    tree = _Tree("root", _Tree("l", kids=[_Tree("ll")]), [_Tree("k")], {"x": _Tree("x", _Tree("xl"))})
    wire = json.loads(json.dumps(_encode(tree)))
    assert _decode(_Tree, wire) == tree == reference_decode(_Tree, wire)


def test_tuples_decode_to_lists_and_a_union_takes_its_first_member():
    wire = {
        "pair": [{"name": "a"}, {"name": "b"}],
        "either": {"name": "e"},
        "anything": {"k": [1, 2]},
        "bare": [1, 2],
        "ints": [3, 4],
    }
    got = _decode(_Shapes, wire)
    assert got == reference_decode(_Shapes, wire)
    assert got.pair == [_Tree("a"), _Tree("b")] and got.ints == [3, 4]
    assert got.either == _Tree("e")
    # nothing to rebuild: taken as it is, as the recursion took it
    assert got.anything is wire["anything"] and got.bare is wire["bare"]
    assert got.ints is not wire["ints"]


def test_plain_types_and_bare_containers_pass_through():
    assert _decode(str, "s") == "s" and _decode(int, 3) == 3 and _decode(Any, [1]) == [1]
    assert _decode(List[int], (1, 2)) == [1, 2]
    assert _decode(Dict[str, int], {"a": 1}) == {"a": 1}
    assert _decode(typing.List, [1]) == [1] and _decode(typing.Dict, {"a": 1}) == {"a": 1}
    assert _decode(Optional[List[o.Taint]], [{"key": "k"}]) == [o.Taint("k")]
    assert _decode(Optional[List[o.Taint]], None) is None


# ---------------------------------------------------------------------------
# over HTTP: a malformed item fails alone
# ---------------------------------------------------------------------------


@pytest.fixture()
def api():
    store_client = Client()
    _server, base, shutdown = start_api_server(store_client.store)
    try:
        yield store_client, HTTPClient(base), base
    finally:
        shutdown()


def test_create_many_answers_bad_request_for_the_malformed_item_alone(api):
    store_client, http, _ = api
    good = [_encode(o.make_pod("a")), _encode(o.make_pod("c", requests={"cpu": "250m"}))]
    out = http._req(
        "POST",
        "/api/v1/namespaces/default/pods",
        {"items": [good[0], {"metadata": {"name": "b"}, "spec": "abc"}, good[1]]},
    )["items"]
    assert [sorted(item) for item in out] == [["object"], ["error", "type"], ["object"]]
    assert out[1]["type"] == "BadRequest" and out[1]["error"].startswith("malformed item")
    assert [_decode(o.Pod, item["object"]).name for item in (out[0], out[2])] == ["a", "c"]
    assert sorted(p.name for p in store_client.pods().list()) == ["a", "c"]
    assert store_client.pods().get("c").spec.containers[0].requests.milli_cpu == 250


def test_a_malformed_single_create_is_a_400(api):
    _, http, _ = api
    with pytest.raises(RuntimeError, match="400"):
        http._req("POST", "/api/v1/namespaces/default/pods", {"metadata": {"name": "b"}, "spec": "abc"})
    assert http.pods().list() == []


# ---------------------------------------------------------------------------
# the mechanism engages once
# ---------------------------------------------------------------------------


class _CountingHints:
    """Stands in for ``typing.get_type_hints``; slow enough that threads
    planning one type at once overlap."""

    def __init__(self, delay_s: float = 0.0) -> None:
        self.calls = 0
        self.delay_s = delay_s
        self._real = typing.get_type_hints

    def __call__(self, tp: Any, *args: Any, **kw: Any) -> Dict[str, Any]:
        self.calls += 1
        if self.delay_s:
            time.sleep(self.delay_s)
        return self._real(tp, *args, **kw)


def _fresh_types() -> Tuple[Any, Any]:
    """Two dataclasses that no test and no import has planned."""
    leaf = dataclasses.make_dataclass("Leaf", [("n", int, 0), ("tags", Dict[str, str], field(default_factory=dict))])
    root = dataclasses.make_dataclass(
        "Root", [("name", str, ""), ("leaves", List[leaf], field(default_factory=list)), ("one", Optional[leaf], None)]
    )
    return root, leaf


def test_a_thousand_pods_derive_no_type_hints_and_build_no_plan(monkeypatch):
    hints = _CountingHints()
    monkeypatch.setattr(typing, "get_type_hints", hints)
    # the stand-in is where the codec looks: a type not met before reaches it
    root, leaf = _fresh_types()
    built = counters.GLOBAL.get("decode.plans_built")
    assert _decode(root, {"leaves": [{"n": 1}]}) == root("", [leaf(1)])
    assert hints.calls == 2  # Root and Leaf, once each
    assert counters.GLOBAL.get("decode.plans_built") == built + 3  # and List[Leaf]

    wires = [json.loads(json.dumps(_encode(OBJECTS["Pod-full"]))) for _ in range(1001)]
    first = _decode(o.Pod, wires[0])
    hints.calls = 0
    built = counters.GLOBAL.get("decode.plans_built")
    pods = [_decode(o.Pod, w) for w in wires[1:]]
    assert hints.calls == 0
    assert counters.GLOBAL.get("decode.plans_built") == built
    assert all(p == first for p in pods) and len(pods) == 1000
    assert _decode(root, {"one": {"n": 2}}) == root(one=leaf(2))
    assert hints.calls == 0


@pytest.mark.parametrize("kind", sorted(REST_KINDS))
def test_every_rest_kind_is_planned_at_import(kind, monkeypatch):
    hints = _CountingHints()
    monkeypatch.setattr(typing, "get_type_hints", hints)
    built = counters.GLOBAL.get("decode.plans_built")
    obj = OBJECTS[kind + "-full"]
    assert _decode(REST_KINDS[kind], _encode(obj)) == obj
    assert hints.calls == 0
    assert counters.GLOBAL.get("decode.plans_built") == built


def test_eight_threads_planning_one_type_at_once_agree(monkeypatch):
    monkeypatch.setattr(typing, "get_type_hints", _CountingHints(delay_s=0.02))
    root, leaf = _fresh_types()
    wire = {"name": "r", "leaves": [{"n": 1, "tags": {"a": "b"}}, None], "one": {"n": 2}, "extra": 1}
    want = root("r", [leaf(1, {"a": "b"}), None], leaf(2))
    built = counters.GLOBAL.get("decode.plans_built")
    gate = threading.Barrier(8)
    results: List[Any] = [None] * 8

    def work(i: int) -> None:
        gate.wait()
        try:
            results[i] = _decode(root, copy.deepcopy(wire))
        except BaseException as e:  # noqa: BLE001 (shown by the assert below)
            results[i] = e

    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results == [want] * 8
    # each type is counted once, whoever planned it: Root, Leaf, List[Leaf]
    # (Dict[str, str] has been an ObjectMeta's since import)
    assert counters.GLOBAL.get("decode.plans_built") - built == 3
    assert _decode(root, wire) == want


def test_the_codec_derives_type_hints_in_one_place():
    with open(checkpoint.__file__, encoding="utf-8") as f:
        src = f.read()
    assert src.count("get_type_hints(") == 1
    assert "MINISCHED_" not in src and "environ" not in src
