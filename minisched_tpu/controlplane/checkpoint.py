"""Checkpoint / resume: durable snapshots of the cluster state store.

The reference delegates durability entirely to etcd behind the apiserver
(SURVEY.md §5.4: k8sapiserver.go:93-105; docker-compose.yml volume) —
scheduler-internal state is in-memory and a restart repopulates from the
store via informer re-list (scheduler.go:40-47).  This module is the
in-memory control plane's equivalent of that durable layer: the ObjectStore
serializes to a language-neutral JSON document and restores from it; device
tables are never checkpointed — they are reconstructed from the store
(SURVEY.md §5.4 "cluster state store is the checkpoint; device arrays are
reconstructable").

Serialization is generic over the api.objects dataclasses, so new spec
fields checkpoint automatically and need no edit here.  Encoding walks the
object.  Decoding goes through a *plan*: a callable ``data -> object``
derived ONCE per type from its annotation and kept for the life of the
process (``_plan_for``) — a dataclass resolves its ``get_type_hints`` and
``dataclasses.fields`` once and holds one plan per field, ``Optional[X]``
is the plan of ``X``, ``List[X]`` / ``Tuple[X, ...]`` and ``Dict[K, V]``
build a new list / dict over the plan of ``X`` / ``V``, and anything else
(``str``, ``int``, ``Any``) is taken as it is.  api/objects.py is written
under ``from __future__ import annotations``, so resolving a dataclass's
hints compiles every annotation string: per type that is nothing, per
object of every create it was most of the REST façade's CPU (PERF.md §6,
PR 27).
"""

from __future__ import annotations

import dataclasses
import json
import os
import typing
from typing import Any, Callable, Dict, Optional, get_args, get_origin

from minisched_tpu.api import objects
from minisched_tpu.controlplane.store import ObjectStore
from minisched_tpu.observability import counters

CHECKPOINT_VERSION = 1

#: kind string → top-level dataclass
KIND_TYPES = {
    "Node": objects.Node,
    "Pod": objects.Pod,
    "PersistentVolume": objects.PersistentVolume,
    "PersistentVolumeClaim": objects.PersistentVolumeClaim,
    # durable on purpose: a recovered control plane replays member leases
    # with their pre-crash renew_time — already expired by wall clock, so
    # survivors arbitrate takeovers exactly as they would have live
    "Lease": objects.Lease,
}


def _encode(obj: Any) -> Any:
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {
            f.name: _encode(getattr(obj, f.name))
            for f in dataclasses.fields(obj)
        }
    if isinstance(obj, dict):
        return {k: _encode(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_encode(v) for v in obj]
    return obj


Plan = Callable[[Any], Any]

#: type annotation → its decode plan; written only by ``_plan_for``, with
#: finished plans, so a reader needs no lock
_PLANS: Dict[Any, Plan] = {}


def _as_is(data: Any) -> Any:
    """The plan of a type with nothing to rebuild.  A container or a
    dataclass that holds one copies the value without calling it."""
    return data


def _plan_for(tp: Any) -> Plan:
    plan = _PLANS.get(tp)
    if plan is None:
        # plans reachable from ``tp`` are built aside and published when
        # all of them are whole: a self-referring type finds its own plan
        # in ``building``, and no other thread can call a dataclass's
        # plan before every field is in it.  Two threads racing here
        # build the same plans; the first to publish one is counted
        building: Dict[Any, Plan] = {}
        plan = _build_plan(tp, building)
        for built_tp, built in building.items():
            if _PLANS.setdefault(built_tp, built) is built:
                counters.inc("decode.plans_built")
        plan = _PLANS.setdefault(tp, plan)
    return plan


def _build_plan(tp: Any, building: Dict[Any, Plan]) -> Plan:
    plan = _PLANS.get(tp) or building.get(tp)
    if plan is not None:
        return plan
    origin = get_origin(tp)
    if origin is typing.Union:  # Optional[X]
        # every plan answers None with None, so X's plan is Optional[X]'s
        return _build_plan(
            [a for a in get_args(tp) if a is not type(None)][0], building
        )
    if dataclasses.is_dataclass(tp):
        # registered before its fields are planned: one may be ``tp`` again
        field_plans: Dict[str, Plan] = {}
        plan = building[tp] = _dataclass_plan(tp, field_plans)
        hints = typing.get_type_hints(tp)  # the one call, and once a type
        for f in dataclasses.fields(tp):
            field_plans[f.name] = _build_plan(hints[f.name], building)
    elif origin in (list, tuple):  # a tuple annotation decodes to a list
        item = _build_plan((get_args(tp) or (Any,))[0], building)
        plan = building[tp] = _list_plan(item)
    elif origin is dict:
        value = _build_plan((get_args(tp) or (Any, Any))[1], building)
        plan = building[tp] = _dict_plan(value)
    else:
        plan = _as_is
    return plan


def _list_plan(item: Plan) -> Plan:
    if item is _as_is:
        return lambda data: None if data is None else list(data)
    return lambda data: None if data is None else [item(v) for v in data]


def _dict_plan(value: Plan) -> Plan:
    if value is _as_is:
        return lambda data: (
            None if data is None else {k: v for k, v in data.items()}
        )
    return lambda data: (
        None if data is None else {k: value(v) for k, v in data.items()}
    )


def _dataclass_plan(tp: Any, field_plans: Dict[str, Plan]) -> Plan:
    """``field_plans`` is filled by the caller after this returns (a field
    may refer back to ``tp``).  A key that is no field is ignored; a field
    the data lacks is left to the dataclass's default, so documents from
    before a field existed still load; data that is no mapping raises."""
    plan_of = field_plans.get

    def decode(data: Any) -> Any:
        if data is None:
            return None
        kwargs = {}
        for name, value in data.items():
            plan = plan_of(name)
            if plan is _as_is:
                kwargs[name] = value
            elif plan is not None:
                kwargs[name] = plan(value)
        return tp(**kwargs)

    return decode


def _decode(tp: Any, data: Any) -> Any:
    return _plan_for(tp)(data)


# planned here, not inside somebody's timed window at the first object
for _tp in KIND_TYPES.values():
    _plan_for(_tp)


def build_snapshot_doc(
    objects_by_kind: Dict[str, Dict[str, Any]], resource_version: int
) -> Dict[str, Any]:
    """Assemble a checkpoint document from raw kind→key→object maps.
    Shared by ``snapshot_store`` (the public, lock-taking path) and
    ``DurableObjectStore.compact`` (already inside the store lock, and
    deliberately NOT via ``store.list`` — compaction is internal
    bookkeeping and must neither clone every object nor draw entropy
    from the fault fabric's ``store.list`` schedule)."""
    return {
        "version": CHECKPOINT_VERSION,
        "resource_version": resource_version,
        # uid watermark: recovery floors the generated-uid sequence here
        # so a restarted process never re-issues a uid — even one whose
        # object was deleted before this snapshot (its put records may be
        # compacted away, leaving no other trace of the uid)
        "uid_floor": objects.uid_floor(),
        "objects": {
            kind: [_encode(o) for o in objs.values()]
            for kind in KIND_TYPES
            if (objs := objects_by_kind.get(kind))
        },
    }


def snapshot_store(store: ObjectStore) -> Dict[str, Any]:
    """Serialize every object (all kinds) + the resource version, under ONE
    lock hold — a torn snapshot (pod bound to a node the snapshot missed)
    would silently lose resource accounting after restore."""
    with store.locked():
        return build_snapshot_doc(store._objects, store.resource_version)


def save_checkpoint(store: ObjectStore, path: str) -> None:
    """Durable write: temp file + atomic rename, so a crash mid-dump never
    destroys the previous good checkpoint."""
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(snapshot_store(store), f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def restore_store(
    doc: Dict[str, Any], store: Optional[ObjectStore] = None
) -> ObjectStore:
    """Rebuild an ObjectStore from a snapshot document, preserving every
    object's uid/resourceVersion and the global version counter (RV
    bookmarks taken before a resume must stay monotonic).  ADDED events
    fan out so watchers attached afterwards replay a consistent cache
    (informer re-list semantics, scheduler.go:72-73)."""
    if doc.get("version") != CHECKPOINT_VERSION:
        raise ValueError(f"unsupported checkpoint version {doc.get('version')!r}")
    store = store or ObjectStore()
    uid_max = int(doc.get("uid_floor", 0))
    for kind, items in doc.get("objects", {}).items():
        tp = KIND_TYPES[kind]
        for data in items:
            obj = _decode(tp, data)
            uid_max = max(uid_max, objects._uid_suffix(obj.metadata.uid))
            store.restore_object(kind, obj)
    store.set_resource_version(int(doc.get("resource_version", 0)))
    # uid continuity (see build_snapshot_doc): creates after a restore
    # must never re-issue a restored object's uid
    objects.ensure_uid_floor(uid_max)
    return store


def load_checkpoint(path: str, store: Optional[ObjectStore] = None) -> ObjectStore:
    with open(path) as f:
        return restore_store(json.load(f), store)
