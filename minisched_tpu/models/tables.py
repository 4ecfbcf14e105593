"""Struct-of-arrays cluster state: NodeTable / PodTable.

The TPU-native replacement for per-object ``NodeInfo`` graphs (SURVEY.md §7
design stance): cluster state lives as flat, statically-shaped arrays in HBM
so every registered plugin can evaluate as a vectorized ``(pods × nodes)``
computation inside one jit.  The reference instead re-lists all nodes and
re-wraps them per pod every cycle (minisched/minisched.go:40,126-127) — the
#1 pattern not to copy.

Conventions:

* CPU in milli-cores (int32), memory in MiB (int32) — integer units keep
  parity with the scalar oracle bit-exact (no float resource math).
* Tables are padded to TPU-friendly sizes (multiples of 128 lanes) with a
  ``valid`` mask; kernels must mask, never rely on dynamic shapes
  (recompilation is the enemy — SURVEY.md §7 hard part 4).
* String data (label keys/values, taints) is carried as stable 32-bit
  FNV-1a hashes computed host-side; kernels compare ints.
"""

from __future__ import annotations

from dataclasses import dataclass, fields
from typing import Any, Dict, List, Optional, Sequence, Tuple

import os

import jax
import jax.numpy as jnp
import numpy as np

from minisched_tpu.api.objects import (
    DEFAULT_POD_CPU_REQUEST,
    DEFAULT_POD_MEMORY_REQUEST,
    MIB,
    gang_key as _gang_key,
)

# upstream GetNonzeroRequests defaults in device units, applied by the
# resource *scorers* (never the Fit filter) to pods with no explicit
# request — derived from the canonical api.objects constants so the scalar
# oracle and the tables can never quantize differently
DEFAULT_NONZERO_CPU = DEFAULT_POD_CPU_REQUEST  # milli-CPU
DEFAULT_NONZERO_MEM_MIB = DEFAULT_POD_MEMORY_REQUEST // MIB

# Fixed per-object capacities for variable-length k8s fields; overflow raises
# host-side at table-build time (static shapes are non-negotiable under jit).
MAX_TAINTS = 8
MAX_TOLERATIONS = 8
MAX_LABELS = 16
MAX_IMAGES = 8  # images cached per node (ImageLocality)
MAX_CONTAINERS = 4  # containers per pod
MAX_PORTS = 8  # host ports per pod / in-use ports tracked per node
MAX_AFF_TERMS = 4  # required node-affinity NodeSelectorTerms per pod
MAX_PREF_TERMS = 4  # preferred node-affinity terms per pod
MAX_AFF_REQS = 4  # match expressions per term
MAX_AFF_VALS = 4  # operand values per In/NotIn expression

EFFECT_NONE = 0
EFFECT_NO_SCHEDULE = 1
EFFECT_PREFER_NO_SCHEDULE = 2
EFFECT_NO_EXECUTE = 3
_EFFECT_CODES = {
    "": EFFECT_NONE,
    "NoSchedule": EFFECT_NO_SCHEDULE,
    "PreferNoSchedule": EFFECT_PREFER_NO_SCHEDULE,
    "NoExecute": EFFECT_NO_EXECUTE,
}

TOLERATION_OP_EQUAL_CODE = 0
TOLERATION_OP_EXISTS_CODE = 1

# node-affinity / label-selector expression operator codes
OP_IN = 0
OP_NOT_IN = 1
OP_EXISTS = 2
OP_DOES_NOT_EXIST = 3
OP_GT = 4
OP_LT = 5
#: encodes an expression that can never match (e.g. Gt/Lt with a
#: non-integer or missing operand — the scalar path treats those as
#: no-match, never as an error; api/objects.py:_match_expression)
OP_INVALID = 6
_OP_CODES = {
    "In": OP_IN,
    "NotIn": OP_NOT_IN,
    "Exists": OP_EXISTS,
    "DoesNotExist": OP_DOES_NOT_EXIST,
    "Gt": OP_GT,
    "Lt": OP_LT,
}


def fnv1a32(s: str) -> int:
    """Stable 32-bit FNV-1a; returned as signed int32 range for jnp."""
    h = 0x811C9DC5
    for b in s.encode("utf-8"):
        h ^= b
        h = (h * 0x01000193) & 0xFFFFFFFF
    # map to signed int32
    return h - (1 << 32) if h >= (1 << 31) else h


#: hash of the empty string — used as the "absent" sentinel nowhere; absent
#: slots use 0 with a count field instead.
def pad_to(n: int, multiple: int = 128) -> int:
    if n == 0:
        return multiple
    return ((n + multiple - 1) // multiple) * multiple


import functools


#: schema → times seen (batched_device_put packs only on reuse)
_SCHEMA_SEEN: Dict[Tuple, int] = {}


_ZERO_DT = {"bool": jnp.bool_, "uint32": jnp.uint32, "int32": jnp.int32}

#: a column whose last axis is shorter than this does not fill one row of
#: the chip's 8 x 128 tiles (unpack_columns ``fence_narrow``)
NARROW_LAST_AXIS = 128


def unpack_columns(
    flat,
    metas: Tuple[Tuple[str, str, Tuple[int, ...]], ...],
    zero_metas: Tuple[Tuple[str, str, Tuple[int, ...]], ...] = (),
    fence_narrow: bool = False,
) -> Dict[str, Any]:
    """TRACEABLE inverse of ``pack_columns``: slice the flat int32 buffer
    back into named, dtyped columns (+ all-zero columns materialized in
    place).  Usable inside a larger jit — the wave evaluator unpacks its
    tables inside its OWN program so a wave costs one executable and one
    dispatch, not an alternation of splitter programs with the
    evaluator.

    ``fence_narrow``: keep the slice of every column whose last axis is
    narrower than a lane row (``NARROW_LAST_AXIS``) apart from its reshape.
    The TPU compiler turns ``reshape(slice(flat))`` of such a column into
    ``slice(reshape(flat))``: the WHOLE buffer laid out ``[len/4, 4]``,
    copied a few words at a time by straight-line code.  For a constraint
    buffer of 2.7-8.4 M words that one reshape was 139 MB of a scan
    program's 170 MB of code and 150 of its 180 s of compile, and whether
    it happened hung on the offsets the elided columns left (PERF.md
    section 6, PR 33).  Behind the fence the reshape sees 4 K words."""
    out = {}
    off = 0
    for name, kind, shape in metas:
        size = 1
        for d in shape:
            size *= d
        seg = flat[off : off + size]
        if fence_narrow and len(shape) > 1 and shape[-1] < NARROW_LAST_AXIS:
            seg = jax.lax.optimization_barrier(seg)
        seg = seg.reshape(shape)
        off += size
        if kind == "bool":
            out[name] = seg != 0
        elif kind == "uint32":
            out[name] = jax.lax.bitcast_convert_type(seg, jnp.uint32)
        else:
            out[name] = seg
    for name, kind, shape in zero_metas:
        out[name] = jnp.zeros(shape, _ZERO_DT[kind])
    return out


def pack_columns(
    host: Dict[str, Any],
) -> Tuple[Tuple[Tuple[str, str, Tuple[int, ...]], ...], Any]:
    """(metas, flat int32 buffer): the host half of ``batched_device_put``
    without the device call — callers hand the flat buffer to a jitted
    function that runs ``unpack_columns`` with these metas inside."""
    arrays = {k: np.asarray(v) for k, v in host.items()}
    metas = _col_metas(arrays)
    parts = []
    for (k, kind, _shape), v in zip(metas, arrays.values()):
        if kind == "bool":
            parts.append(v.ravel().astype(np.int32))
        elif kind == "uint32":
            parts.append(v.ravel().view(np.int32))
        else:
            parts.append(np.ascontiguousarray(v.ravel(), dtype=np.int32))
    flat = np.concatenate(parts) if parts else np.zeros(0, np.int32)
    return metas, flat


@functools.lru_cache(maxsize=None)
def _flat_splitter(
    metas: Tuple[Tuple[str, str, Tuple[int, ...]], ...],
    zero_metas: Tuple[Tuple[str, str, Tuple[int, ...]], ...] = (),
):
    """Jitted device-side splitter for one packed-table schema."""

    def split(flat):
        return unpack_columns(flat, metas, zero_metas)

    return jax.jit(split)


@dataclass
class PackedTable:
    """A table still on the host, packed for single-buffer transfer: the
    consumer jit takes ``flat`` as an argument and rebuilds the columns
    with ``unpack_columns(flat, metas, zero_metas)`` INSIDE its own
    program.  ``metas``/``zero_metas`` are static (part of the consumer's
    jit cache key); equal schemas hit the same executable."""

    metas: Tuple[Tuple[str, str, Tuple[int, ...]], ...]
    zero_metas: Tuple[Tuple[str, str, Tuple[int, ...]], ...]
    flat: Any  # np.int32[total]
    capacity: int = 0

    @property
    def schema(self) -> Tuple:
        return (self.metas, self.zero_metas)

    def unpack(self, flat=None) -> Dict[str, Any]:
        return unpack_columns(
            self.flat if flat is None else flat, self.metas, self.zero_metas
        )


def pack_table(
    host: Dict[str, Any],
    zero_metas: Tuple = (),
    capacity: int = 0,
    elide_zeros: bool = False,
    elide_groups: Tuple[Tuple[str, ...], ...] = (),
) -> PackedTable:
    """``elide_zeros``: move columns that are entirely zero into
    ``zero_metas`` (materialized on device by the consumer's unpack, zero
    wire bytes).  Bytes not shipped are the cheapest bytes: a plain
    config5 wave's 10MB constraint table is almost entirely zero planes,
    and XLA constant-folds a zero column's whole compute lane out of the
    consumer.  NOTE: the zero-set is part of the
    schema — a column flipping nonzero compiles a new consumer
    executable, so flips must be rare/one-way (combo planes go nonzero
    once cross-pod pods land and stay there).

    ``elide_groups``: the selective middle ground — each GROUP of column
    names elides as a unit, and only when every member is all-zero.
    Consumers whose zero-sets must stay schema-stable against state
    churn (the scan lane) use this for the columns whose zero-ness is a
    property of the WORKLOAD (a spread-only burst carries no affinity
    terms, no volumes): XLA then constant-folds those columns' whole
    compute lanes out of the per-step program, while the schema space
    stays bounded at one executable per group subset actually seen."""
    if elide_zeros:
        live: Dict[str, Any] = {}
        zeros = list(zero_metas)
        for k, v in host.items():
            arr = np.asarray(v)
            if not arr.any():
                zeros.append((k, _wire_kind(arr.dtype), tuple(arr.shape)))
            else:
                live[k] = arr
        host, zero_metas = live, tuple(zeros)
    elif elide_groups:
        zeros = list(zero_metas)
        live = dict(host)
        for group in elide_groups:
            members = [k for k in group if k in live]
            if members and all(
                not np.asarray(live[k]).any() for k in members
            ):
                for k in members:
                    arr = np.asarray(live.pop(k))
                    zeros.append(
                        (k, _wire_kind(arr.dtype), tuple(arr.shape))
                    )
        host, zero_metas = live, tuple(zeros)
    metas, flat = pack_columns(host)
    return PackedTable(metas, tuple(zero_metas), flat, capacity)


class PackedCaller:
    """Per-schema jit cache around a ``consumer(pods, nodes, extra)``
    function: arguments arrive as PackedTables (+ the device-resident
    static node columns) and are unpacked INSIDE the consumer's one jitted
    program: a wave is one executable, one dispatch and three flat
    transfers, where separate splitter programs would alternate with the
    evaluator and ship every column as its own buffer.

    Schemas are static jit-cache keys, so capacities must follow the same
    quantization discipline as device-table consumers.

    ``name`` is the owning lane's: the jitted function carries it, so a
    profiler trace's ``XLA Modules`` read ``jit_<name>`` (``jit_wave``,
    ``jit_scan_blocked``, ``jit_scan_exact``) and tell the lanes apart."""

    def __init__(self, consumer, name: str = "packed"):
        self._consumer = consumer
        self._name = name
        self._fns: Dict[Tuple, Any] = {}
        #: key → argument shapes of the call that built it (lowered_texts)
        self._avals: Dict[Tuple, Any] = {}

    def lowered_texts(self, debug_info: bool = False) -> List[str]:
        """StableHLO text of every program this caller has dispatched,
        re-lowered from the recorded argument shapes (nothing compiles or
        runs).  chip_smoke.py reads it to prove the Mosaic kernel is IN the
        live wave and scan programs, not merely importable.  With
        ``debug_info`` the text carries the locations, and with them the
        ``jax.named_scope`` names (``select_hosts_xla``)."""
        return [
            fn.lower(*self._avals[key]).as_text(debug_info=debug_info)
            for key, fn in list(self._fns.items())
        ]

    def _build_fn(self, key, pod_packed, node_static, node_agg_packed,
                  extra_packed):
        from minisched_tpu.models.constraints import ConstraintTables

        ex_schema = extra_packed.schema if extra_packed is not None else None
        pod_metas, pod_zeros = pod_packed.schema
        agg_metas, agg_zeros = node_agg_packed.schema
        consumer = self._consumer

        def run(pod_flat, agg_flat, ex_flat, static_cols):
            pods = PodTable(
                **unpack_columns(pod_flat, pod_metas, pod_zeros)
            )
            nodes = NodeTable(
                **static_cols,
                **unpack_columns(agg_flat, agg_metas, agg_zeros),
            )
            extra = (
                ConstraintTables(
                    **unpack_columns(ex_flat, *ex_schema, fence_narrow=True)
                )
                if ex_schema is not None
                else None
            )
            return consumer(pods, nodes, extra)

        run.__name__ = run.__qualname__ = self._name
        return jax.jit(run)

    def _key(self, pod_packed, node_static, node_agg_packed, ex_schema):
        """The jit-cache key for one call signature — subclasses extend
        it (the mesh variant folds the mesh factoring in)."""
        return (pod_packed.schema, node_agg_packed.schema, ex_schema,
                tuple(sorted(node_static)))

    def __call__(self, pod_packed, node_static, node_agg_packed,
                 extra_packed=None):
        ex_schema = extra_packed.schema if extra_packed is not None else None
        key = self._key(pod_packed, node_static, node_agg_packed, ex_schema)
        ex_flat = (
            extra_packed.flat
            if extra_packed is not None
            else np.zeros(0, np.int32)
        )
        fn = self._fns.get(key)
        if fn is None:
            fn = self._build_fn(
                key, pod_packed, node_static, node_agg_packed, extra_packed
            )
            self._fns[key] = fn
            self._avals[key] = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                (pod_packed.flat, node_agg_packed.flat, ex_flat, node_static),
            )
        try:
            return fn(
                pod_packed.flat, node_agg_packed.flat, ex_flat, node_static
            )
        except ValueError as err:
            # jax 0.9's C++ dispatch can return a WRONG-ARITY executable
            # for this call after unrelated large programs compiled in the
            # same process ("Execution supplied N buffers but compiled
            # program expected M buffers") — an upstream cache-dispatch
            # bug, not a shape problem on our side: the same signature
            # succeeded before.  Self-heal: drop the poisoned entry,
            # clear that jit's caches, recompile once.
            if "buffers but compiled program expected" not in str(err):
                raise
            import sys

            from minisched_tpu.observability import counters

            # always visible: a heal that repeats is a real bug, and a
            # silent recompile would mask it (chip_smoke.py requires zero)
            counters.inc("wave.dispatch_healed")
            print(
                f"[packed-caller] wrong-arity dispatch, recompiling: "
                f"{str(err)[-160:]}",
                file=sys.stderr,
                flush=True,
            )
            self._fns.pop(key, None)
            try:
                fn.clear_cache()
            except Exception:
                pass
            fn = self._build_fn(
                key, pod_packed, node_static, node_agg_packed, extra_packed
            )
            self._fns[key] = fn
            return fn(
                pod_packed.flat, node_agg_packed.flat, ex_flat, node_static
            )


def _wire_kind(dtype) -> str:
    """Wire-format kind of a column dtype (the packed transfer's only
    three legal dtypes)."""
    if dtype == np.bool_:
        return "bool"
    return "uint32" if dtype == np.uint32 else "int32"


def _col_metas(arrays: Dict[str, Any]) -> Tuple[Tuple[str, str, Tuple[int, ...]], ...]:
    for k, v in arrays.items():
        if v.dtype not in (np.bool_, np.uint32, np.int32):
            raise TypeError(
                f"batched_device_put: column {k!r} has dtype {v.dtype}; only "
                "bool/uint32/int32 ride the packed wire format"
            )
    return tuple(
        (k, _wire_kind(v.dtype), tuple(v.shape)) for k, v in arrays.items()
    )


def batched_device_put(
    t: Dict[str, Any],
    zero_metas: Tuple[Tuple[str, str, Tuple[int, ...]], ...] = (),
    force_packed: bool = False,
    elide_zeros: bool = False,
) -> Dict[str, Any]:
    """Move a dict of host numpy columns to device in ONE transfer.

    Per-array device_put pays one transfer dispatch per LEAF (37 for a
    pod table).  Packing every column into one flat int32 buffer makes it
    one transfer; a cached jitted splitter rebuilds the columns on
    device.  bools widen to int32 on the wire; uint32 rides as a bitcast.

    ``zero_metas``: extra (name, kind, shape) columns known to be all-zero
    — created inside the SAME compiled splitter (zero wire bytes, and no
    second executable to compile and load).

    ``elide_zeros``: auto-detect all-zero columns and move them into
    zero_metas.  The zero-set keys the splitter executable, so this is
    for ONE-SHOT big builds (a 100k-pod table's wide affinity planes are
    hundreds of MB of zeros) — wave-loop builds whose feature mix flips
    per wave must not use it.
    """
    arrays = {k: np.asarray(v) for k, v in t.items()}
    if elide_zeros:
        live: Dict[str, Any] = {}
        zeros = list(zero_metas)
        for k, v in arrays.items():
            if v.size >= 4096 and not v.any():
                zeros.append((k, _wire_kind(v.dtype), tuple(v.shape)))
            else:
                live[k] = v
        arrays, zero_metas = live, tuple(zeros)
    metas = _col_metas(arrays)
    total = sum(v.size for v in arrays.values())
    _SCHEMA_SEEN[metas] = _SCHEMA_SEEN.get(metas, 0) + 1
    if _SCHEMA_SEEN[metas] == 1 and os.environ.get("MINISCHED_LOG_SCHEMAS"):
        import sys as _sys
        import time as _time

        cols = ",".join(f"{k}{list(v.shape)}" for k, v in arrays.items())
        print(
            f"[schema t={_time.monotonic():.1f}] total={total} {cols[:400]}",
            file=_sys.stderr,
            flush=True,
        )
    if (not force_packed and not zero_metas and total < 50_000
            and _SCHEMA_SEEN[metas] < 2):
        # small one-shot tables (tests, tiny scenarios): per-leaf puts are
        # fine.  Anything big OR repeated takes the packed path — the
        # splitter's compile is served by the persistent compilation cache
        # (utils/compilecache.py) after the first-ever build, so even a
        # one-shot 39-column constraint table is one transfer, not 39.
        return {k: jnp.asarray(v) for k, v in arrays.items()}
    _, flat = pack_columns(arrays)
    return _flat_splitter(metas, zero_metas)(flat)


def _register_table(cls):
    """Register a dataclass of jnp arrays as a pytree."""
    names = [f.name for f in fields(cls)]
    jax.tree_util.register_pytree_node(
        cls,
        lambda t: ([getattr(t, n) for n in names], None),
        lambda _, leaves: cls(**dict(zip(names, leaves))),
    )
    return cls


@_register_table
@dataclass
class NodeTable:
    """All scheduler-relevant node state, shape (N,) or (N, K)."""

    # identity
    name_hash: Any  # i32[N] fnv of node name (NodeName filter)
    # resources
    alloc_cpu: Any  # i32[N] allocatable milli-cpu
    alloc_mem: Any  # i32[N] allocatable MiB
    alloc_eph: Any  # i32[N] allocatable ephemeral-storage MiB
    alloc_pods: Any  # i32[N] allocatable pod count
    req_cpu: Any  # i32[N] requested (sum of assigned pods)
    req_mem: Any  # i32[N]
    req_eph: Any  # i32[N]
    req_pods: Any  # i32[N]
    # NonZeroRequested aggregates (upstream applies 100m CPU / 200Mi memory
    # defaults to request-less pods for the scorers only)
    nzreq_cpu: Any  # i32[N]
    nzreq_mem: Any  # i32[N]
    # flags
    unschedulable: Any  # bool[N] (spec.unschedulable)
    # nodenumber plugin
    suffix: Any  # i32[N] trailing-digit of name, -1 if none
    # multi-host slice topology (gang/topology-aware placement):
    # fnv hash of spec.slice_id (0 = not part of a slice), torus
    # coordinates within the slice, host index, and the slice's torus
    # DIMENSIONS (0 = unknown → non-wrapping distance) — static node
    # columns read by the GangTopology locality scorer
    slice_hash: Any  # i32[N]
    torus_x: Any  # i32[N]
    torus_y: Any  # i32[N]
    torus_z: Any  # i32[N]
    host_index: Any  # i32[N] (-1 = none)
    slice_dx: Any  # i32[N] torus ring size per axis (0 = unknown)
    slice_dy: Any  # i32[N]
    slice_dz: Any  # i32[N]
    # label/taint PROFILES: real clusters are built from node pools, so
    # 10k nodes collapse to a handful of distinct (labels, taints)
    # signatures.  Label/taint-dependent kernels (NodeAffinity,
    # TaintToleration, spread's eligibility gate) evaluate per
    # (pod × profile) — the heavy unrolled expression machinery shrinks
    # by N/Dp (~300× at config5 scale) — and expand to (pod × node) with
    # ONE gather through profile_id.  Padded node rows point at profile
    # 0; the evaluators' valid mask excludes them regardless.
    profile_id: Any  # i32[N] node → profile row
    # per-profile taints
    prof_taint_key: Any  # i32[Dp, MAX_TAINTS] fnv hash
    prof_taint_value: Any  # i32[Dp, MAX_TAINTS]
    prof_taint_effect: Any  # i32[Dp, MAX_TAINTS] effect code
    prof_num_taints: Any  # i32[Dp]
    # per-profile labels
    prof_label_key: Any  # i32[Dp, MAX_LABELS]
    prof_label_value: Any  # i32[Dp, MAX_LABELS]
    prof_label_numval: Any  # i32[Dp, MAX_LABELS] value parsed as int (Gt/Lt)
    prof_label_num_ok: Any  # bool[Dp, MAX_LABELS] value was an integer
    prof_num_labels: Any  # i32[Dp]
    # cached images (ImageLocality)
    image_key: Any  # i32[N, MAX_IMAGES] fnv of image name
    image_size_mb: Any  # i32[N, MAX_IMAGES]
    num_images: Any  # i32[N]
    # host ports claimed by assigned pods (NodePorts)
    used_port: Any  # i32[N, MAX_PORTS]
    num_used_ports: Any  # i32[N]
    # padding mask
    valid: Any  # bool[N]

    @property
    def capacity(self) -> int:
        return int(self.valid.shape[0])


@_register_table
@dataclass
class PodTable:
    """All scheduler-relevant pending-pod state, shape (P,) or (P, K)."""

    req_cpu: Any  # i32[P] requested milli-cpu (sum of containers)
    req_mem: Any  # i32[P] MiB
    req_eph: Any  # i32[P] MiB
    req_pods: Any  # i32[P] (1)
    suffix: Any  # i32[P] trailing digit of name, -1 if none
    spec_node_name: Any  # i32[P] fnv of spec.node_name, 0 = unset (NodeName)
    # tolerations
    tol_key: Any  # i32[P, MAX_TOLERATIONS]
    tol_value: Any  # i32[P, MAX_TOLERATIONS]
    tol_effect: Any  # i32[P, MAX_TOLERATIONS]
    tol_op: Any  # i32[P, MAX_TOLERATIONS] 0=Equal 1=Exists
    tol_empty_key: Any  # bool[P, MAX_TOLERATIONS] key=="" (Exists-all)
    num_tols: Any  # i32[P]
    # node selector (spec.nodeSelector match_labels)
    sel_key: Any  # i32[P, MAX_LABELS]
    sel_value: Any  # i32[P, MAX_LABELS]
    num_sel: Any  # i32[P]
    # required node affinity: OR over terms, AND over requirements
    aff_required: Any  # bool[P] required affinity present (even if 0 terms)
    aff_key: Any  # i32[P, MAX_AFF_TERMS, MAX_AFF_REQS]
    aff_op: Any  # i32[P, T, R] operator code (OP_*)
    aff_vals: Any  # i32[P, T, R, MAX_AFF_VALS] value hashes (In/NotIn)
    aff_nvals: Any  # i32[P, T, R]
    aff_numval: Any  # i32[P, T, R] integer operand (Gt/Lt)
    aff_nreqs: Any  # i32[P, T]
    aff_nterms: Any  # i32[P] 0 = no required affinity
    # preferred node affinity: weighted terms (NodeAffinity score)
    pref_weight: Any  # i32[P, MAX_PREF_TERMS]
    pref_key: Any  # i32[P, MAX_PREF_TERMS, MAX_AFF_REQS]
    pref_op: Any  # i32[P, T, R]
    pref_vals: Any  # i32[P, T, R, MAX_AFF_VALS]
    pref_nvals: Any  # i32[P, T, R]
    pref_numval: Any  # i32[P, T, R]
    pref_nreqs: Any  # i32[P, T]
    pref_nterms: Any  # i32[P]
    # container images + host ports
    image_key: Any  # i32[P, MAX_CONTAINERS]
    num_containers: Any  # i32[P]
    port: Any  # i32[P, MAX_PORTS]
    num_ports: Any  # i32[P]
    # gang/topology placement (GangTopology scorer): gang identity hash
    # (0 = singleton) plus the gang's ALREADY-PLACED aggregate, computed
    # host-side at table build (engine/gang.py): majority slice hash,
    # torus coordinate SUMS (centroid × count — integer math, no
    # division until the kernel) and placed-member count
    gang_id: Any  # i32[P] fnv of 'namespace/gangname', 0 = none
    gang_slice: Any  # i32[P] majority slice of placed members, 0 = none
    gang_sx: Any  # i32[P] sum of placed members' torus_x
    gang_sy: Any  # i32[P]
    gang_sz: Any  # i32[P]
    gang_n: Any  # i32[P] placed-member count
    # deterministic tie-break seed per pod
    seed: Any  # u32[P]
    valid: Any  # bool[P]

    @property
    def capacity(self) -> int:
        return int(self.valid.shape[0])


# ---------------------------------------------------------------------------
# Builders (host side, numpy)
# ---------------------------------------------------------------------------


def _name_suffix(name: str) -> int:
    """Trailing single ASCII digit of an object name, -1 if absent — the
    nodenumber plugin's key (nodenumber.go:21,50-64 parses the last rune
    with strconv.Atoi, which accepts ASCII digits only; str.isdigit would
    also accept Unicode digits and diverge from both Go and the native
    batch kernel)."""
    if name and "0" <= name[-1] <= "9":
        return int(name[-1])
    return -1


def pod_seed(uid: str) -> int:
    """Deterministic per-pod tie-break seed (unsigned 32-bit)."""
    return fnv1a32(uid) & 0xFFFFFFFF


#: NodeTable columns with a leading PROFILE axis (replicated on a mesh —
#: they are tiny and the node sharding must not split them)
NODE_PROFILE_COLS = (
    "prof_taint_key", "prof_taint_value", "prof_taint_effect",
    "prof_num_taints", "prof_label_key", "prof_label_value",
    "prof_label_numval", "prof_label_num_ok", "prof_num_labels",
)


def _node_table_skeleton(cap: int, prof_cap: int) -> Dict[str, Any]:
    def zeros(shape, dtype=np.int32):
        return np.zeros(shape, dtype)

    return dict(
        name_hash=zeros(cap),
        alloc_cpu=zeros(cap), alloc_mem=zeros(cap), alloc_eph=zeros(cap),
        alloc_pods=zeros(cap),
        req_cpu=zeros(cap), req_mem=zeros(cap), req_eph=zeros(cap),
        req_pods=zeros(cap), nzreq_cpu=zeros(cap), nzreq_mem=zeros(cap),
        unschedulable=np.zeros(cap, bool), suffix=np.full(cap, -1, np.int32),
        slice_hash=zeros(cap), torus_x=zeros(cap), torus_y=zeros(cap),
        torus_z=zeros(cap), host_index=np.full(cap, -1, np.int32),
        slice_dx=zeros(cap), slice_dy=zeros(cap), slice_dz=zeros(cap),
        profile_id=zeros(cap),
        prof_taint_key=zeros((prof_cap, MAX_TAINTS)),
        prof_taint_value=zeros((prof_cap, MAX_TAINTS)),
        prof_taint_effect=zeros((prof_cap, MAX_TAINTS)),
        prof_num_taints=zeros(prof_cap),
        prof_label_key=zeros((prof_cap, MAX_LABELS)),
        prof_label_value=zeros((prof_cap, MAX_LABELS)),
        prof_label_numval=zeros((prof_cap, MAX_LABELS)),
        prof_label_num_ok=np.zeros((prof_cap, MAX_LABELS), bool),
        prof_num_labels=zeros(prof_cap),
        image_key=zeros((cap, MAX_IMAGES)), image_size_mb=zeros((cap, MAX_IMAGES)),
        num_images=zeros(cap),
        used_port=zeros((cap, MAX_PORTS)), num_used_ports=zeros(cap),
        valid=np.zeros(cap, bool),
    )


class _ProfileRegistry:
    """Dedupes nodes into (labels, taints) profiles.  Pass 1 assigns ids
    (``pid_for``); the skeleton is then sized ``capacity`` (a multiple of
    64 — see there) and pass 2 encodes one row per profile
    (``encode_rows``)."""

    def __init__(self) -> None:
        self.ids: Dict[Tuple, int] = {}
        self.nodes: List[Any] = []  # representative node per profile

    def pid_for(self, node: Any) -> int:
        labels = node.metadata.labels
        if len(labels) > MAX_LABELS:
            raise ValueError(f"node {node.metadata.name}: >{MAX_LABELS} labels")
        taints = node.spec.taints
        if len(taints) > MAX_TAINTS:
            raise ValueError(f"node {node.metadata.name}: >{MAX_TAINTS} taints")
        sig = (
            tuple(sorted(labels.items())),
            # sorted: taint matching is order-independent, so [A,B] and
            # [B,A] must share a profile (spurious profiles waste Dp rows
            # and can cross the 64 boundary → recompile)
            tuple(sorted((t.key, t.value, t.effect) for t in taints)),
        )
        pid = self.ids.get(sig)
        if pid is None:
            pid = self.ids[sig] = len(self.nodes)
            self.nodes.append(node)
        return pid

    @property
    def capacity(self) -> int:
        # quantized HARD (multiples of 64): Dp is an executable shape, so
        # every distinct value is a fresh compile — a cluster gaining its
        # 17th label signature mid-run must not recompile the wave
        # evaluator (measured: a 75s compile inside a wave).  64 covers
        # any sane pool layout; past each multiple of 64 the next step
        # (and one recompile) is unavoidable.
        return pad_to(max(len(self.nodes), 1), 64)

    def encode_rows(self, t: Dict[str, Any]) -> None:
        for pid, node in enumerate(self.nodes):
            for j, taint in enumerate(node.spec.taints):
                t["prof_taint_key"][pid, j] = fnv1a32(taint.key)
                t["prof_taint_value"][pid, j] = fnv1a32(taint.value)
                t["prof_taint_effect"][pid, j] = _EFFECT_CODES[taint.effect]
            t["prof_num_taints"][pid] = len(node.spec.taints)
            labels = node.metadata.labels
            for j, (k, v) in enumerate(sorted(labels.items())):
                t["prof_label_key"][pid, j] = fnv1a32(k)
                t["prof_label_value"][pid, j] = fnv1a32(v)
                try:
                    t["prof_label_numval"][pid, j] = int(v)
                    t["prof_label_num_ok"][pid, j] = True
                except ValueError:
                    pass
            t["prof_num_labels"][pid] = len(labels)


def _prof_cap(reg: "_ProfileRegistry", requested: int = None) -> int:
    """Requested profile capacity, validated against the registry —
    warm builds pass the LIVE cluster's Dp so shapes match."""
    if requested is None:
        return reg.capacity
    if len(reg.nodes) > requested:
        raise ValueError(
            f"{len(reg.nodes)} profiles exceed requested capacity {requested}"
        )
    return requested


def node_profile_capacity(nodes: Sequence[Any]) -> int:
    """The profile-axis capacity (Dp) a table over ``nodes`` will get —
    for warm builds that must match the live executable's shapes."""
    reg = _ProfileRegistry()
    for node in nodes:
        reg.pid_for(node)
    return reg.capacity


def _encode_node_static(t: Dict[str, Any], i: int, node: Any, pid: int) -> None:
    """Everything about row ``i`` that comes from the Node object itself
    (identity, allocatable, images, profile membership) — the assigned-pod
    aggregates are filled by the caller, the label/taint planes live on
    the profile rows."""
    t["name_hash"][i] = fnv1a32(node.metadata.name)
    alloc = node.status.allocatable
    t["alloc_cpu"][i] = alloc.milli_cpu
    t["alloc_mem"][i] = alloc.memory // MIB
    t["alloc_eph"][i] = alloc.ephemeral_storage // MIB
    t["alloc_pods"][i] = alloc.pods
    t["unschedulable"][i] = node.spec.unschedulable
    t["suffix"][i] = _name_suffix(node.metadata.name)
    # written unconditionally: _patch_rows re-encodes updated rows in
    # place, and a node LEAVING a slice must clear its old coordinates
    has_slice = bool(node.spec.slice_id)
    t["slice_hash"][i] = fnv1a32(node.spec.slice_id) if has_slice else 0
    t["torus_x"][i] = node.spec.torus_x if has_slice else 0
    t["torus_y"][i] = node.spec.torus_y if has_slice else 0
    t["torus_z"][i] = node.spec.torus_z if has_slice else 0
    t["host_index"][i] = node.spec.host_index
    t["slice_dx"][i] = node.spec.slice_dx if has_slice else 0
    t["slice_dy"][i] = node.spec.slice_dy if has_slice else 0
    t["slice_dz"][i] = node.spec.slice_dz if has_slice else 0
    t["profile_id"][i] = pid
    images = node.status.images
    if len(images) > MAX_IMAGES:
        raise ValueError(f"node {node.metadata.name}: >{MAX_IMAGES} images")
    for j, (img, size) in enumerate(sorted(images.items())):
        t["image_key"][i, j] = fnv1a32(img)
        t["image_size_mb"][i, j] = size // MIB
    t["num_images"][i] = len(images)
    t["valid"][i] = True


def _encode_node_ports(t: Dict[str, Any], i: int, node_name: str, pods) -> None:
    used_ports: List[int] = []
    for p in pods:
        for c in p.spec.containers:
            if c.ports:
                used_ports.extend(c.ports)
    if len(used_ports) > MAX_PORTS:
        raise ValueError(f"node {node_name}: >{MAX_PORTS} used ports")
    for j, port in enumerate(used_ports):
        t["used_port"][i, j] = port
    t["num_used_ports"][i] = len(used_ports)


def build_node_table(nodes: Sequence[Any], pods_by_node: Dict[str, List[Any]] = None,
                     capacity: int = None,
                     prof_capacity: int = None) -> Tuple[NodeTable, List[str]]:
    """Build a NodeTable from Node objects (+ already-assigned pods).

    Returns (table, node_names) where node_names[i] is row i's name; the
    order is the given order (callers sort for determinism).
    """
    pods_by_node = pods_by_node or {}
    n = len(nodes)
    cap = capacity or pad_to(n)
    if n > cap:
        raise ValueError(f"{n} nodes exceed table capacity {cap}")
    reg = _ProfileRegistry()
    pids = [reg.pid_for(node) for node in nodes]
    t = _node_table_skeleton(cap, _prof_cap(reg, prof_capacity))
    reg.encode_rows(t)
    names: List[str] = []
    for i, node in enumerate(nodes):
        names.append(node.metadata.name)
        _encode_node_static(t, i, node, pids[i])
        assigned = pods_by_node.get(node.metadata.name, ())
        for p in assigned:
            req = p.resource_requests()
            t["req_cpu"][i] += req.milli_cpu
            t["req_mem"][i] += req.memory // MIB
            t["req_eph"][i] += req.ephemeral_storage // MIB
            t["req_pods"][i] += 1
            t["nzreq_cpu"][i] += req.milli_cpu or DEFAULT_NONZERO_CPU
            t["nzreq_mem"][i] += (req.memory // MIB) or DEFAULT_NONZERO_MEM_MIB
        _encode_node_ports(t, i, node.metadata.name, assigned)
    return NodeTable(**batched_device_put(t)), names


def build_node_table_from_infos(
    node_infos: Sequence[Any], capacity: int = None
) -> Tuple[NodeTable, List[str]]:
    """NodeTable straight from NodeInfo snapshots: reuses the request
    aggregates the snapshot already computed instead of re-walking every
    assigned pod (NodeInfo accumulates with the same MiB-floored integer
    discipline — see framework/nodeinfo.py — so the two builders are
    bit-identical).  The wave engine rebuilds the table every wave; at
    100k assigned pods the re-walk was the dominant host cost."""
    n = len(node_infos)
    cap = capacity or pad_to(n)
    if n > cap:
        raise ValueError(f"{n} nodes exceed table capacity {cap}")
    reg = _ProfileRegistry()
    pids = [reg.pid_for(ni.node) for ni in node_infos]
    t = _node_table_skeleton(cap, reg.capacity)
    reg.encode_rows(t)
    names: List[str] = []
    for i, ni in enumerate(node_infos):
        names.append(ni.name)
        _encode_node_static(t, i, ni.node, pids[i])
        _fill_aggregate_row(t, i, ni)
    return NodeTable(**batched_device_put(t)), names


def _fill_aggregate_row(t: Dict[str, Any], i: int, ni: Any) -> None:
    """The assigned-pod aggregate columns of row ``i`` from a NodeInfo
    (NodeInfo maintains them incrementally, ports included)."""
    t["req_cpu"][i] = ni.requested.milli_cpu
    t["req_mem"][i] = ni.req_mem_mib
    t["req_eph"][i] = ni.req_eph_mib
    t["req_pods"][i] = len(ni.pods)
    t["nzreq_cpu"][i] = ni.non_zero_requested.milli_cpu
    t["nzreq_mem"][i] = ni.nzreq_mem_mib
    ports = ni.used_ports
    if len(ports) > MAX_PORTS:
        raise ValueError(f"node {ni.name}: >{MAX_PORTS} used ports")
    for j, port in enumerate(ports):
        t["used_port"][i, j] = port
    t["num_used_ports"][i] = len(ports)


#: NodeTable columns that come from the Node OBJECT (cacheable across
#: waves keyed on resource_version) vs. the assigned-pod aggregates
#: (cheap, re-filled per wave from NodeInfo's incremental sums)
_NODE_STATIC_COLS = (
    "name_hash", "alloc_cpu", "alloc_mem", "alloc_eph", "alloc_pods",
    "unschedulable", "suffix", "profile_id",
    "slice_hash", "torus_x", "torus_y", "torus_z", "host_index",
    "slice_dx", "slice_dy", "slice_dz",
    "image_key", "image_size_mb", "num_images", "valid",
) + NODE_PROFILE_COLS
_NODE_AGG_COLS = (
    "req_cpu", "req_mem", "req_eph", "req_pods", "nzreq_cpu", "nzreq_mem",
    "used_port", "num_used_ports",
)

#: sentinel for "caller does not participate in dirty tracking" — distinct
#: from None, which means "everything is dirty, rebuild the base fully"
DIRTY_UNTRACKED = object()


def _agg_delta_fp(agg_delta) -> Tuple:
    """Canonical fingerprint of a per-node assume-delta (see
    CachedNodeTableBuilder._apply_agg_delta's row shape) — the idle-wave
    gate compares THIS, not identity: two consecutive waves folding the
    same surviving assumptions produce byte-identical aggregate columns,
    so re-folding is pure waste.  O(len(delta)); () for no delta."""
    if not agg_delta:
        return ()
    return tuple(
        sorted(
            (name, tuple(d[:6]), tuple(d[6]))
            for name, d in agg_delta.items()
        )
    )


class CachedNodeTableBuilder:
    """Per-wave NodeTable builds with the static columns cached.

    The wave engine rebuilds its NodeTable every wave, but the node
    OBJECTS rarely change — only the assigned-pod aggregates do.  The
    static encode (hashing names/labels/taints for 10k nodes) is ~0.3s
    per wave; this builder re-runs it only when the name-sorted
    (name, resource_version) signature changes (node added/removed/
    updated) and otherwise just re-fills the aggregate columns from the
    NodeInfos' incrementally-maintained sums.
    """

    def __init__(self, device_static: bool = True, mesh: Any = None):
        import threading

        # scan lanes (loop thread) and the wave-pipeline build worker
        # share ONE builder — the static cache, aggregate base, and
        # double buffers below are all mutable state, so every build
        # serializes through this lock (contention only when a scan
        # flush coincides with a pipelined build)
        self._build_lock = threading.RLock()
        #: jax.sharding.Mesh — static columns then live device-resident
        #: SHARDED on the node axis (profile planes replicated), and node
        #: capacities quantize to lcm(128, node-axis size) so every shard
        #: gets equal whole tiles (parallel/sharding.cap_multiple)
        self._mesh = mesh
        self._cap_mult = 128
        if mesh is not None:
            from minisched_tpu.parallel.sharding import (
                cap_multiple,
                mesh_axis_sizes,
            )

            self._cap_mult = cap_multiple(128, mesh_axis_sizes(mesh)[1])
        #: lazily-built single-default-device copy of the static columns
        #: — the mesh engine's per-wave sharding-failure fallback runs
        #: the single-device evaluator against it (see
        #: DeviceScheduler._eval_packed_wave)
        self._static_dev_fallback: Optional[Dict[str, Any]] = None
        self._sig = None
        #: moves whenever ``_sig`` does: the version of the node roster
        #: (names, order, resource_versions) the statics encode
        self._roster_epoch = 0
        #: per thread, the roster epoch of that thread's last build
        #: (roster_epoch)
        self._tls = threading.local()
        self._static: Dict[str, Any] = {}
        self._static_dev: Dict[str, Any] = {}
        # incremental AGGREGATE base: persistent host copies of the
        # assigned-pod sum columns, re-encoded only for the rows a
        # snapshot's dirty-set names (informer events mark nodes dirty;
        # SchedulerCache.snapshot_for_tables drains the set atomically
        # with the snapshot).  A full _fill_aggregates walk is O(all
        # nodes) of Python attribute access per wave (~0.7s of the
        # config5 wave loop); the incremental path is O(touched nodes).
        self._agg_base: Optional[Dict[str, Any]] = None
        self._agg_base_names: Tuple[str, ...] = ()
        #: dirty rows re-encoded by the last build (0 = full rebuild
        #: counted as len(nodes)); observability reads it per wave
        self.last_dirty_rows = 0
        #: True when the last tracked build took the idle-wave skip path
        #: (tables reused wholesale — no encode, no fold, no transfer);
        #: the pipeline copies it onto the PreparedWave per wave
        self.last_build_skipped = False
        # idle-wave reuse cache (ISSUE 8): the last TRACKED build's
        # output, reusable wholesale when a later snapshot proves nothing
        # changed — dirty-set empty, same capacities, same assume-delta
        # fingerprint, and the statics unchanged (cache epoch match, or
        # the (name, rv) signature compare when the caller has no epoch).
        # Invalidated whenever the statics re-encode or the aggregate
        # base is touched; packing paths copy out of the scratch buffers,
        # so the cached tables can never be mutated by later builds.
        self._reuse_packed: Optional[Tuple] = None
        self._reuse_table: Optional[Tuple] = None
        self._reuse_key: Optional[Tuple] = None
        self._reuse_epoch: Optional[int] = None
        # reusable per-wave aggregate scratch: the assume-delta folds
        # into a COPY of the base (never the base itself).  ONE buffer
        # suffices — what keeps an in-flight wave's tables safe from the
        # next build is not buffer rotation but the copy every packing
        # path makes under _build_lock (pack_columns' np.concatenate /
        # batched_device_put) before the lock releases.
        self._agg_scratch: Optional[Dict[str, Any]] = None
        # incremental-rebuild state: host copy of the static columns, the
        # persistent profile registry, and the encoded profile capacity —
        # a node UPDATE re-encodes just its row instead of all N (a 2k-
        # node label change used to re-encode 10k nodes, ~1.2s host work)
        self._host_static: Dict[str, Any] = {}
        self._reg: Any = None
        self._prof_cap_val: int = 0
        #: keep the static columns device-resident between builds.  Turn
        #: OFF when the consumer donates its node-table argument against
        #: a sharding that could alias these buffers (the mesh engine:
        #: sharded steps donate argnum 0 — a 1-device mesh's device_put
        #: may alias instead of copy, and a donated cached buffer poisons
        #: every later wave)
        self._device_static = device_static
        self._names: List[str] = []
        self._name_index: Dict[str, int] = {}

    def _static_sig(self, node_infos: Sequence[Any], cap: int,
                    prof_capacity: int) -> Tuple:
        return (
            cap,
            prof_capacity,
            tuple(
                (ni.node.metadata.name, ni.node.metadata.resource_version)
                for ni in node_infos
            ),
        )

    def roster_epoch(self) -> Optional[int]:
        """The version of the node roster this thread's last build encoded
        (None before one): equal versions mean the same nodes in the same
        order with the same resource_versions, so what derives from the
        nodes' objects alone may be kept across builds (the constraint
        build's TopoAxisCache).  Per thread, because the wave build worker
        and the loop thread build through one builder."""
        return getattr(self._tls, "roster", None)

    def _drop_reuse(self) -> None:
        """Invalidate the idle-wave reuse cache (statics about to
        re-encode, aggregate base about to change, or a build failed)."""
        self._reuse_packed = None
        self._reuse_table = None
        self._reuse_key = None
        self._reuse_epoch = None

    def _ensure_static(self, node_infos: Sequence[Any], cap: int,
                       prof_capacity: int) -> None:
        """Re-encode + (optionally) re-upload the static columns only when
        the name-sorted (name, resource_version) signature changes."""
        sig = self._static_sig(node_infos, cap, prof_capacity)
        if sig == self._sig:
            return
        self._drop_reuse()  # statics changing: cached tables are stale
        if self._patch_rows(node_infos, sig):
            return
        reg = _ProfileRegistry()
        pids = [reg.pid_for(ni.node) for ni in node_infos]
        t = _node_table_skeleton(cap, _prof_cap(reg, prof_capacity))
        reg.encode_rows(t)
        names: List[str] = []
        for i, ni in enumerate(node_infos):
            names.append(ni.name)
            _encode_node_static(t, i, ni.node, pids[i])
        self._host_static = {k: t[k] for k in _NODE_STATIC_COLS}
        self._reg = reg
        self._prof_cap_val = _prof_cap(reg, prof_capacity)
        # static columns live on DEVICE between builds: re-uploading the
        # label/taint/image planes for 10k+ nodes every wave is tens of
        # MB of host→device traffic per wave for bytes that only change
        # when a node object changes.  The host copy is retained for row
        # patching (~2MB at 10k nodes).
        self._static = {} if self._device_static else dict(self._host_static)
        if self._device_static:
            self._place_static_dev(self._host_static)
        self._names = names
        self._name_index = {name: i for i, name in enumerate(names)}
        self._sig = sig
        self._roster_epoch += 1

    def _patch_rows(self, node_infos: Sequence[Any], sig: Tuple) -> bool:
        """Incremental static update: same node set/order/capacities, only
        some nodes' resource_versions changed — re-encode just those rows
        in the host copy and re-upload.  Returns False (caller does a full
        rebuild) on membership/order/capacity changes, a stepped profile
        capacity, or an encode error."""
        cap, prof_capacity, rows = sig
        if (
            self._sig is None
            or not self._host_static
            or self._sig[0] != cap
            or self._sig[1] != prof_capacity
            or len(self._sig[2]) != len(rows)
            or any(a[0] != b[0] for a, b in zip(self._sig[2], rows))
        ):
            return False
        changed = [
            i for i, (a, b) in enumerate(zip(self._sig[2], rows)) if a[1] != b[1]
        ]
        t = self._host_static
        try:
            for i in changed:
                node = node_infos[i].node
                pid = self._reg.pid_for(node)
                if _prof_cap(self._reg, prof_capacity) != self._prof_cap_val:
                    return False  # Dp stepped: schema change, rebuild fully
                # clear variable-length slots a shorter re-encode would
                # leave stale
                t["image_key"][i] = 0
                t["image_size_mb"][i] = 0
                _encode_node_static(t, i, node, pid)
        except ValueError:
            return False
        # profile planes: new profiles appended by pid_for get encoded;
        # existing rows are rewritten in place (idempotent)
        self._reg.encode_rows(t)
        if self._device_static:
            self._place_static_dev(t)
        else:
            self._static = dict(t)
        self._sig = sig
        self._roster_epoch += 1
        return True

    def _place_static_dev(self, t: Dict[str, Any]) -> None:
        """Upload the static columns; under a mesh they land SHARDED
        (node axis split, profile planes replicated) so the packed wave
        program consumes them in place — no per-wave resharding."""
        cols = batched_device_put(t)
        if self._mesh is not None:
            from minisched_tpu.parallel.sharding import static_col_shardings

            cols = jax.device_put(
                cols, static_col_shardings(self._mesh, cols)
            )
        self._static_dev = cols
        self._static_dev_fallback = None  # stale: re-derive on demand

    def static_devices(self) -> set:
        """Devices holding the device-resident static columns — under a
        mesh, more than one (chip_smoke.py's proof the roster is sharded)."""
        with self._build_lock:
            return {
                d for col in self._static_dev.values() for d in col.devices()
            }

    def static_dev_default(self) -> Dict[str, Any]:
        """Single-default-device copy of the current static columns —
        what the mesh engine's per-wave fallback evaluator consumes when
        a sharded wave fails (the sharded statics would drag the
        single-device program back onto the mesh)."""
        with self._build_lock:
            if not self._host_static:
                raise RuntimeError("no static columns built yet")
            if self._static_dev_fallback is None:
                self._static_dev_fallback = batched_device_put(
                    dict(self._host_static)
                )
            return self._static_dev_fallback

    @staticmethod
    def _fill_aggregates(node_infos: Sequence[Any], cap: int) -> Dict[str, Any]:
        t: Dict[str, Any] = {}
        for k in _NODE_AGG_COLS:
            t[k] = (
                np.zeros((cap, MAX_PORTS), np.int32)
                if k == "used_port"
                else np.zeros(cap, np.int32)
            )
        for i, ni in enumerate(node_infos):
            _fill_aggregate_row(t, i, ni)
        return t

    def _apply_agg_delta(self, t: Dict[str, Any], agg_delta) -> None:
        """Fold the wave engine's assume-cache deltas into the aggregate
        columns numerically — the alternative (NodeInfo.add_pod per assumed
        pod into cloned infos) cost ~250ms per 16k-pod wave and duplicated
        work the cache's own event path does once the binds land.  A delta
        row is ``[milli_cpu, mem_mib, eph_mib, pods, nz_milli_cpu,
        nz_mem_mib, ports]`` with the exact NodeInfo.add_pod quantization
        (sum-of-floors MiB — parity depends on it)."""
        idx = self._name_index
        for name, d in agg_delta.items():
            i = idx.get(name)
            if i is None:
                continue  # node left the roster; the assumption prunes next
            t["req_cpu"][i] += d[0]
            t["req_mem"][i] += d[1]
            t["req_eph"][i] += d[2]
            t["req_pods"][i] += d[3]
            t["nzreq_cpu"][i] += d[4]
            t["nzreq_mem"][i] += d[5]
            ports = d[6]
            if ports:
                n = int(t["num_used_ports"][i])
                if n + len(ports) > MAX_PORTS:
                    raise ValueError(f"node {name}: >{MAX_PORTS} used ports")
                for j, port in enumerate(ports, start=n):
                    t["used_port"][i, j] = port
                t["num_used_ports"][i] = n + len(ports)

    def node_capacity(self, n: int) -> int:
        """The capacity a table over ``n`` nodes will get — pad_to with
        this builder's mesh-aligned multiple (prewarm must match it or
        the warm executable is wasted)."""
        return pad_to(max(n, 1), self._cap_mult)

    def _cap_for(self, node_infos: Sequence[Any], capacity) -> int:
        n = len(node_infos)
        cap = capacity or pad_to(n, self._cap_mult)
        if n > cap:
            raise ValueError(f"{n} nodes exceed table capacity {cap}")
        if cap % self._cap_mult:
            raise ValueError(
                f"node capacity {cap} not a multiple of {self._cap_mult} "
                "(mesh node-axis shards need equal whole tiles)"
            )
        return cap

    def _update_agg_base(
        self, node_infos: Sequence[Any], cap: int, dirty
    ) -> Dict[str, Any]:
        """Bring the persistent aggregate base up to this snapshot.
        ``dirty`` names the nodes whose aggregates changed since the last
        drained snapshot (None = rebuild everything).  Any failure
        invalidates the base — a partial application must never survive
        into the next wave's increments."""
        names = tuple(ni.name for ni in node_infos)
        base = self._agg_base
        self._drop_reuse()  # base about to change; caller re-caches
        try:
            if (
                base is None
                or dirty is None
                or self._agg_base_names != names
                or base["req_cpu"].shape[0] != cap
            ):
                base = self._fill_aggregates(node_infos, cap)
                self._agg_base = base
                self._agg_base_names = names
                self.last_dirty_rows = len(node_infos)
                return base
            idx = self._name_index
            n = 0
            for name in dirty:
                i = idx.get(name)
                if i is None:
                    continue  # left the roster: membership change would
                    # have arrived as dirty=None; a stray name is stale
                # clear variable-length slots a shorter re-encode would
                # leave stale, then re-encode the row from ITS NodeInfo
                base["used_port"][i] = 0
                _fill_aggregate_row(base, i, node_infos[i])
                n += 1
            self.last_dirty_rows = n
            return base
        except Exception:
            self._agg_base = None  # never trust a half-applied base
            raise

    def _wave_agg_copy(self, base: Dict[str, Any], cap: int) -> Dict[str, Any]:
        """Copy the base into the reusable scratch buffer — the per-wave
        assume-delta folds into the copy, never the base.  Reuse is safe
        because every consumer path copies out of the scratch (see
        _agg_scratch) before _build_lock releases."""
        buf = self._agg_scratch
        if buf is None or buf["req_cpu"].shape[0] != cap:
            buf = self._agg_scratch = {
                k: np.empty_like(v) for k, v in base.items()
            }
        for k, v in base.items():
            np.copyto(buf[k], v)
        return buf

    def _try_reuse(
        self, cached, node_infos: Sequence[Any], cap: int, prof_capacity,
        dirty, agg_delta, epoch,
    ):
        """The idle-wave gate (ISSUE 8): return the previous build's
        output wholesale — no static encode, no aggregate re-fold, no
        packing, no device transfer — when this snapshot provably changes
        nothing: the drained dirty-set is EMPTY (tracked), capacities
        match, the assume-delta fingerprint matches, and the node objects
        are unchanged (cache-epoch handshake; callers without an epoch
        pay an O(nodes) signature compare, still zero build work).
        Returns None when any condition fails — the caller builds."""
        if dirty is DIRTY_UNTRACKED:
            # untracked (scan-lane / prewarm) builds leave the wave
            # stats ALONE: the pipeline's build worker reads
            # last_build_skipped / last_dirty_rows after its tracked
            # build returns, and a concurrent loop-thread scan flush
            # through this same builder must not clobber them
            return None
        self.last_build_skipped = False
        if (
            dirty is None
            or dirty
            or cached is None
            or self._agg_base is None
            or self._reuse_key is None
            or self._reuse_key[0] != cap
            or self._reuse_key[1] != prof_capacity
            or self._reuse_key[2] != _agg_delta_fp(agg_delta)
        ):
            return None
        if epoch is not None and self._reuse_epoch is not None:
            if epoch != self._reuse_epoch:
                return None  # node objects (or aggregates) changed
        elif self._static_sig(node_infos, cap, prof_capacity) != self._sig:
            return None
        from minisched_tpu.observability import counters

        counters.inc("wave_build.skipped")
        self.last_dirty_rows = 0
        self.last_build_skipped = True
        return cached

    def _cache_reuse(
        self, out, packed: bool, cap: int, prof_capacity, agg_delta, epoch
    ):
        """Record a TRACKED build's output for the idle-wave gate and
        return it (possibly upgraded).  One key serves both modes; the
        other mode's cached output is dropped so a mode switch can never
        serve tables keyed for the other.

        Packed single-device outputs get their aggregate flat buffer
        committed to device HERE: the consumer jit then uses the
        committed array directly — the wave that built it still pays its
        one transfer (device_put instead of jit's implicit one), and
        every SKIPPED wave after it ships zero bytes.  Under a mesh the
        flat stays host-side (MeshPackedCaller owns placement there, and
        the per-wave single-device fallback consumes the same buffer)."""
        self._reuse_key = (cap, prof_capacity, _agg_delta_fp(agg_delta))
        self._reuse_epoch = epoch
        if packed:
            if self._mesh is None:
                static_dev, agg, names = out
                agg = PackedTable(
                    agg.metas, agg.zero_metas,
                    jax.device_put(agg.flat), agg.capacity,
                )
                out = (static_dev, agg, names)
            self._reuse_packed, self._reuse_table = out, None
        else:
            self._reuse_table, self._reuse_packed = out, None
        return out

    def _aggregates_for(
        self, node_infos: Sequence[Any], cap: int, dirty, agg_delta
    ) -> Dict[str, Any]:
        if dirty is DIRTY_UNTRACKED:
            # caller outside the dirty protocol (scan lanes, prewarm,
            # one-shot builds): fresh fill, persistent base untouched —
            # its undrained changes stay pending for the wave path, and
            # the wave stats (last_dirty_rows/last_build_skipped) stay
            # the TRACKED builds' (see _try_reuse: the pipeline reads
            # them cross-thread after its build)
            t = self._fill_aggregates(node_infos, cap)
        else:
            base = self._update_agg_base(node_infos, cap, dirty)
            t = self._wave_agg_copy(base, cap)
        if agg_delta:
            self._apply_agg_delta(t, agg_delta)
        return t

    def build(self, node_infos: Sequence[Any], capacity: int = None,
              prof_capacity: int = None, agg_delta=None,
              dirty=DIRTY_UNTRACKED, epoch=None):
        with self._build_lock:
            try:
                cap = self._cap_for(node_infos, capacity)
                reused = self._try_reuse(
                    self._reuse_table, node_infos, cap, prof_capacity,
                    dirty, agg_delta, epoch,
                )
                if reused is not None:
                    self._tls.roster = self._roster_epoch
                    table, names = reused
                    return table, list(names)
                self._ensure_static(node_infos, cap, prof_capacity)
                self._tls.roster = self._roster_epoch
                t = self._aggregates_for(node_infos, cap, dirty, agg_delta)
                if self._device_static:
                    cols = dict(self._static_dev)
                    cols.update(batched_device_put(t))
                else:
                    cols = dict(self._static)
                    cols.update(t)
                    cols = batched_device_put(cols)
                out = NodeTable(**cols), list(self._names)
                if dirty is not DIRTY_UNTRACKED:
                    out = self._cache_reuse(
                        out, False, cap, prof_capacity, agg_delta, epoch
                    )
                return out
            except Exception:
                # a TRACKED build consumed its snapshot's drained dirty
                # set the moment the snapshot was taken — failing at ANY
                # point (static encode, device put) before the base
                # reflects those rows would strand them stale forever;
                # invalidate so the next tracked build refills fully
                if dirty is not DIRTY_UNTRACKED:
                    self._agg_base = None
                self._drop_reuse()
                raise

    def build_packed(self, node_infos: Sequence[Any], capacity: int = None,
                     prof_capacity: int = None, agg_delta=None,
                     dirty=DIRTY_UNTRACKED, epoch=None):
        """Single-program variant: (static device cols, PackedTable of the
        per-wave aggregate columns, names).  The consumer jit unpacks the
        aggregates and merges the device-resident statics inside its own
        program — no splitter executable per wave.  Requires
        ``device_static=True`` (the statics must already live on device).

        ``dirty``: the snapshot's drained dirty-set (see
        SchedulerCache.snapshot_for_tables) — the aggregate columns then
        re-encode only those rows into the persistent base instead of
        walking every NodeInfo.  Callers outside the dirty protocol leave
        the default (full fresh fill, base untouched).

        ``epoch``: the cache epoch the snapshot carried — with an EMPTY
        drained dirty-set and an unchanged assume-delta it arms the
        idle-wave gate (_try_reuse): the previous build's tables come
        back wholesale and ``wave_build.skipped`` increments."""
        with self._build_lock:
            try:
                assert self._device_static, (
                    "build_packed needs device-resident statics"
                )
                cap = self._cap_for(node_infos, capacity)
                reused = self._try_reuse(
                    self._reuse_packed, node_infos, cap, prof_capacity,
                    dirty, agg_delta, epoch,
                )
                if reused is not None:
                    self._tls.roster = self._roster_epoch
                    static_dev, packed, names = reused
                    return static_dev, packed, list(names)
                self._ensure_static(node_infos, cap, prof_capacity)
                self._tls.roster = self._roster_epoch
                t = self._aggregates_for(node_infos, cap, dirty, agg_delta)
                out = (
                    self._static_dev,
                    pack_table(t, (), cap),
                    list(self._names),
                )
                if dirty is not DIRTY_UNTRACKED:
                    out = self._cache_reuse(
                        out, True, cap, prof_capacity, agg_delta, epoch
                    )
                return out
            except Exception:
                # see build(): a failed TRACKED build must not strand the
                # drained dirty rows — invalidate, full refill next time
                if dirty is not DIRTY_UNTRACKED:
                    self._agg_base = None
                self._drop_reuse()
                raise


def _encode_terms(t: Dict[str, Any], prefix: str, i: int, terms, max_terms: int,
                  what: str) -> None:
    """Encode NodeSelectorTerms (or preferred-term preferences) into the
    ``{prefix}_*`` expression arrays of row ``i``."""
    if len(terms) > max_terms:
        raise ValueError(f"{what}: >{max_terms} node-affinity terms")
    for j, term in enumerate(terms):
        reqs = term.match_expressions
        if len(reqs) > MAX_AFF_REQS:
            raise ValueError(f"{what}: >{MAX_AFF_REQS} requirements per term")
        for r, req in enumerate(reqs):
            t[f"{prefix}_key"][i, j, r] = fnv1a32(req.key)
            t[f"{prefix}_op"][i, j, r] = _OP_CODES[req.operator]
            if req.operator in ("In", "NotIn"):
                if len(req.values) > MAX_AFF_VALS:
                    raise ValueError(f"{what}: >{MAX_AFF_VALS} values per expression")
                for v, val in enumerate(req.values):
                    t[f"{prefix}_vals"][i, j, r, v] = fnv1a32(val)
                t[f"{prefix}_nvals"][i, j, r] = len(req.values)
            elif req.operator in ("Gt", "Lt"):
                try:
                    t[f"{prefix}_numval"][i, j, r] = int(req.values[0])
                except (ValueError, IndexError, OverflowError):
                    t[f"{prefix}_op"][i, j, r] = OP_INVALID
        t[f"{prefix}_nreqs"][i, j] = len(reqs)
    t[f"{prefix}_nterms"][i] = len(terms)


def _pod_is_simple(pod: Any) -> bool:
    """A pod the vectorized fast path can encode: default-shaped spec with
    at most resource requests — no tolerations / selector / affinity /
    spread constraints / host ports / pinned node, single container."""
    spec = pod.spec
    return (
        not spec.tolerations
        and not spec.node_selector
        and spec.affinity is None
        and not spec.topology_spread_constraints
        and not spec.node_name
        and spec.gang is None
        and len(spec.containers) <= 1
        and not (spec.containers and spec.containers[0].ports)
    )


#: shared all-zero request vector for container-less simple pods (read-only)
_ZERO_REQS = None  # set lazily below to avoid import cycles


def _get_zero_reqs():
    global _ZERO_REQS
    if _ZERO_REQS is None:
        from minisched_tpu.api.objects import ResourceList

        _ZERO_REQS = ResourceList()
    return _ZERO_REQS


def _build_pod_table_fast(pods: Sequence[Any], cap: int,
                          device: bool = True,
                          invalid_rows: Sequence[Any] = ()):
    """Columnar fast path for simple pods: per-field list comprehensions +
    native batch string kernels (minisched_tpu.native) instead of the
    per-pod row-write loop — ~10× on the host build that feeds the device
    waves (the reference instead re-lists and re-wraps objects per cycle,
    minisched.go:40)."""
    from minisched_tpu import native

    p = len(pods)
    names = [pod.metadata.name for pod in pods]
    # simple pods have ≤1 container, so the request sum IS the container's
    # already-parsed ResourceList — reading it directly skips the
    # per-pod ResourceList allocation + memo write of resource_requests()
    # (~60% of the cold fast build; the memo exists for the paths that DO
    # aggregate per pod: assume-cache, NodeInfo).  req_pods is pinned to 1
    # below, matching resource_requests' max(pods, 1) floor.
    _zero = _get_zero_reqs()
    reqs = [
        pod.spec.containers[0].requests if pod.spec.containers else _zero
        for pod in pods
    ]

    def col(values, dtype=np.int32, fill=0):
        arr = np.full(cap, fill, dtype)
        arr[:p] = values
        return arr

    host = dict(
        req_cpu=col([r.milli_cpu for r in reqs]),
        req_mem=col([r.memory // MIB for r in reqs]),
        req_eph=col([r.ephemeral_storage // MIB for r in reqs]),
        req_pods=col(1),
        # padding rows match the slow path's -1 initializer exactly
        suffix=col(native.name_suffix_batch(names), fill=-1),
        num_containers=col([len(pod.spec.containers) for pod in pods]),
        seed=col(
            native.pod_seed_batch(
                [pod.metadata.uid or pod.metadata.name for pod in pods]
            ),
            np.uint32,
        ),
        valid=col(True, bool),
    )
    img = np.zeros((cap, MAX_CONTAINERS), np.int32)
    img[:p, 0] = [
        fnv1a32(pod.spec.containers[0].image)
        if pod.spec.containers and pod.spec.containers[0].image
        else 0
        for pod in pods
    ]
    host["image_key"] = img
    # every constraint column is all-zero for simple pods: materialized ON
    # DEVICE inside the same compiled splitter as the packed transfer (no
    # wire bytes, no second executable) — the table is ~50× wider than its
    # live fast-path columns, all of it host→device traffic on the wave
    # build's critical path.
    if invalid_rows:
        host["valid"][list(invalid_rows)] = False
    if not device:
        return pack_table(host, _zero_pod_metas(cap), cap), names
    cols = batched_device_put(host, zero_metas=_zero_pod_metas(cap))
    return PodTable(**cols), names


@functools.lru_cache(maxsize=None)
def _zero_pod_metas(cap: int) -> Tuple[Tuple[str, str, Tuple[int, ...]], ...]:
    """(name, kind, shape) of every PodTable column that is all-zero for
    simple pods, for ``batched_device_put``'s on-device zero fill."""
    TR = (cap, MAX_AFF_TERMS, MAX_AFF_REQS)
    PR = (cap, MAX_PREF_TERMS, MAX_AFF_REQS)
    i32, b = "int32", "bool"
    return (
        ("spec_node_name", i32, (cap,)),
        ("tol_key", i32, (cap, MAX_TOLERATIONS)),
        ("tol_value", i32, (cap, MAX_TOLERATIONS)),
        ("tol_effect", i32, (cap, MAX_TOLERATIONS)),
        ("tol_op", i32, (cap, MAX_TOLERATIONS)),
        ("tol_empty_key", b, (cap, MAX_TOLERATIONS)),
        ("num_tols", i32, (cap,)),
        ("sel_key", i32, (cap, MAX_LABELS)),
        ("sel_value", i32, (cap, MAX_LABELS)),
        ("num_sel", i32, (cap,)),
        ("aff_required", b, (cap,)),
        ("aff_key", i32, TR),
        ("aff_op", i32, TR),
        ("aff_vals", i32, TR + (MAX_AFF_VALS,)),
        ("aff_nvals", i32, TR),
        ("aff_numval", i32, TR),
        ("aff_nreqs", i32, TR[:2]),
        ("aff_nterms", i32, (cap,)),
        ("pref_weight", i32, (cap, MAX_PREF_TERMS)),
        ("pref_key", i32, PR),
        ("pref_op", i32, PR),
        ("pref_vals", i32, PR + (MAX_AFF_VALS,)),
        ("pref_nvals", i32, PR),
        ("pref_numval", i32, PR),
        ("pref_nreqs", i32, PR[:2]),
        ("pref_nterms", i32, (cap,)),
        ("port", i32, (cap, MAX_PORTS)),
        ("num_ports", i32, (cap,)),
        ("gang_id", i32, (cap,)),
        ("gang_slice", i32, (cap,)),
        ("gang_sx", i32, (cap,)),
        ("gang_sy", i32, (cap,)),
        ("gang_sz", i32, (cap,)),
        ("gang_n", i32, (cap,)),
    )


def build_pod_table(pods: Sequence[Any], capacity: int = None,
                    force_packed: bool = False, device: bool = True,
                    invalid_rows: Sequence[int] = (),
                    elide_zeros: bool = False,
                    gang_view: Optional[Dict[str, Tuple]] = None):
    """``device=False`` returns (PackedTable, names) instead of a
    device-resident PodTable — for consumers that unpack the flat
    buffer inside their own jitted program (ops/repair packed mode).
    ``invalid_rows``: row indices marked valid=False — INTERIOR padding
    for the blocked scan lane, whose block structure needs placeholder
    rows between real pods (tail padding is automatic).
    ``elide_zeros`` (device=True slow path only): materialize all-zero
    columns on device instead of shipping them — for one-shot big
    builds (see batched_device_put); wave-loop builds must not set it.
    ``gang_view``: gang key → (slice_hash, sx, sy, sz, n) aggregate of
    the gang's ALREADY-PLACED members (engine/gang.py) — encoded into
    each member row's gang_* columns so the GangTopology scorer pulls
    new members toward them; None leaves the aggregates zero (cold
    start / gang-less callers)."""
    p = len(pods)
    cap = capacity or pad_to(p)
    if p > cap:
        raise ValueError(f"{p} pods exceed table capacity {cap}")

    if all(_pod_is_simple(pod) for pod in pods):
        return _build_pod_table_fast(
            pods, cap, device=device, invalid_rows=invalid_rows
        )

    def zeros(shape, dtype=np.int32):
        return np.zeros(shape, dtype)

    TR = (cap, MAX_AFF_TERMS, MAX_AFF_REQS)
    PR = (cap, MAX_PREF_TERMS, MAX_AFF_REQS)
    t = dict(
        req_cpu=zeros(cap), req_mem=zeros(cap), req_eph=zeros(cap),
        req_pods=zeros(cap),
        suffix=np.full(cap, -1, np.int32), spec_node_name=zeros(cap),
        tol_key=zeros((cap, MAX_TOLERATIONS)), tol_value=zeros((cap, MAX_TOLERATIONS)),
        tol_effect=zeros((cap, MAX_TOLERATIONS)), tol_op=zeros((cap, MAX_TOLERATIONS)),
        tol_empty_key=np.zeros((cap, MAX_TOLERATIONS), bool), num_tols=zeros(cap),
        sel_key=zeros((cap, MAX_LABELS)), sel_value=zeros((cap, MAX_LABELS)),
        num_sel=zeros(cap),
        aff_required=np.zeros(cap, bool),
        aff_key=zeros(TR), aff_op=zeros(TR), aff_vals=zeros(TR + (MAX_AFF_VALS,)),
        aff_nvals=zeros(TR), aff_numval=zeros(TR),
        aff_nreqs=zeros(TR[:2]), aff_nterms=zeros(cap),
        pref_weight=zeros((cap, MAX_PREF_TERMS)),
        pref_key=zeros(PR), pref_op=zeros(PR), pref_vals=zeros(PR + (MAX_AFF_VALS,)),
        pref_nvals=zeros(PR), pref_numval=zeros(PR),
        pref_nreqs=zeros(PR[:2]), pref_nterms=zeros(cap),
        image_key=zeros((cap, MAX_CONTAINERS)), num_containers=zeros(cap),
        port=zeros((cap, MAX_PORTS)), num_ports=zeros(cap),
        gang_id=zeros(cap), gang_slice=zeros(cap),
        gang_sx=zeros(cap), gang_sy=zeros(cap), gang_sz=zeros(cap),
        gang_n=zeros(cap),
        seed=np.zeros(cap, np.uint32), valid=np.zeros(cap, bool),
    )
    # common columns go columnar (listcomps + native batch kernels — same
    # encoding as the fast path); the per-pod loop below only touches the
    # complex optional fields a pod actually carries
    from minisched_tpu import native

    names = [pod.metadata.name for pod in pods]
    reqs = [pod.resource_requests() for pod in pods]
    t["req_cpu"][:p] = [r.milli_cpu for r in reqs]
    t["req_mem"][:p] = [r.memory // MIB for r in reqs]
    t["req_eph"][:p] = [r.ephemeral_storage // MIB for r in reqs]
    t["req_pods"][:p] = 1
    t["suffix"][:p] = native.name_suffix_batch(names)
    t["num_containers"][:p] = [len(pod.spec.containers) for pod in pods]
    t["seed"][:p] = native.pod_seed_batch(
        [pod.metadata.uid or pod.metadata.name for pod in pods]
    )
    t["valid"][:p] = True
    t["image_key"][:p, 0] = [
        fnv1a32(pod.spec.containers[0].image)
        if pod.spec.containers and pod.spec.containers[0].image
        else 0
        for pod in pods
    ]

    # pods sharing one affinity structure (every replica of a deployment)
    # encode once: the cache maps the structural signature to the encoded
    # row values, skipping re-hashing per pod
    aff_cache: Dict[Any, Dict[str, Any]] = {}
    _AFF_FIELDS = (
        "aff_required", "aff_key", "aff_op", "aff_vals", "aff_nvals",
        "aff_numval", "aff_nreqs", "aff_nterms", "pref_weight", "pref_key",
        "pref_op", "pref_vals", "pref_nvals", "pref_numval", "pref_nreqs",
        "pref_nterms",
    )

    def _terms_sig(terms):
        return tuple(
            tuple((r.key, r.operator, tuple(r.values)) for r in term.match_expressions)
            for term in terms
        )

    for i, pod in enumerate(pods):
        if pod.spec.node_name:
            t["spec_node_name"][i] = fnv1a32(pod.spec.node_name)
        tols = pod.spec.tolerations
        if tols:
            if len(tols) > MAX_TOLERATIONS:
                raise ValueError(
                    f"pod {pod.metadata.name}: >{MAX_TOLERATIONS} tolerations"
                )
            for j, tol in enumerate(tols):
                t["tol_key"][i, j] = fnv1a32(tol.key)
                t["tol_value"][i, j] = fnv1a32(tol.value)
                t["tol_effect"][i, j] = _EFFECT_CODES[tol.effect]
                t["tol_op"][i, j] = (
                    TOLERATION_OP_EXISTS_CODE if tol.operator == "Exists"
                    else TOLERATION_OP_EQUAL_CODE
                )
                t["tol_empty_key"][i, j] = tol.key == ""
            t["num_tols"][i] = len(tols)
        sel = pod.spec.node_selector
        if sel:
            if len(sel) > MAX_LABELS:
                raise ValueError(
                    f"pod {pod.metadata.name}: >{MAX_LABELS} selector terms"
                )
            for j, (k, v) in enumerate(sorted(sel.items())):
                t["sel_key"][i, j] = fnv1a32(k)
                t["sel_value"][i, j] = fnv1a32(v)
            t["num_sel"][i] = len(sel)
        aff = pod.spec.affinity
        na = aff.node_affinity if aff is not None else None
        if na is not None:
            sig = (
                None
                if na.required_terms is None
                else _terms_sig(na.required_terms),
                tuple(
                    (p.weight, *_terms_sig([p.preference])) for p in na.preferred
                ),
            )
            cached = aff_cache.get(sig)
            if cached is None:
                if na.required_terms is not None:
                    t["aff_required"][i] = True
                    _encode_terms(t, "aff", i, na.required_terms, MAX_AFF_TERMS,
                                  f"pod {pod.metadata.name}")
                _encode_terms(t, "pref", i,
                              [p.preference for p in na.preferred],
                              MAX_PREF_TERMS, f"pod {pod.metadata.name}")
                for j, pref in enumerate(na.preferred):
                    t["pref_weight"][i, j] = pref.weight
                aff_cache[sig] = {f: t[f][i].copy() for f in _AFF_FIELDS}
            else:
                for f, val in cached.items():
                    t[f][i] = val
        containers = pod.spec.containers
        if len(containers) > MAX_CONTAINERS:
            raise ValueError(
                f"pod {pod.metadata.name}: >{MAX_CONTAINERS} containers"
            )
        if len(containers) > 1 or (containers and containers[0].ports):
            ports: List[int] = []
            for j, c in enumerate(containers):
                t["image_key"][i, j] = fnv1a32(c.image) if c.image else 0
                ports.extend(c.ports)
            if len(ports) > MAX_PORTS:
                raise ValueError(f"pod {pod.metadata.name}: >{MAX_PORTS} ports")
            for j, port in enumerate(ports):
                t["port"][i, j] = port
            t["num_ports"][i] = len(ports)
        key = _gang_key(pod)
        if key is not None:
            t["gang_id"][i] = fnv1a32(key)
            agg = (gang_view or {}).get(key)
            if agg is not None:
                t["gang_slice"][i] = agg[0]
                t["gang_sx"][i] = agg[1]
                t["gang_sy"][i] = agg[2]
                t["gang_sz"][i] = agg[3]
                t["gang_n"][i] = agg[4]
    if invalid_rows:
        t["valid"][list(invalid_rows)] = False
    if not device:
        # NO zero-elision here (unlike the constraint tables): the slow
        # pod schema's zero-set varies with each wave's feature mix, and
        # every distinct set is a fresh consumer executable — measured as
        # ~50s of mid-run compiles at config5 scale.  The fast path's
        # FIXED _zero_pod_metas already covers the common all-simple wave.
        return pack_table(t, (), cap), names
    return PodTable(**batched_device_put(
        t, force_packed=force_packed, elide_zeros=elide_zeros
    )), names
