"""The fused (pods × nodes) device evaluator — the TPU hot path.

This is the TPU-native re-design of the reference's per-pod scheduling
cycle (minisched/minisched.go:32-113): instead of a sequential
O(pods × nodes × plugins) CPU loop with a full node re-list per pod
(minisched.go:40,124,167), every registered plugin evaluates as a
vectorized predicate/score kernel over struct-of-arrays tables
(minisched_tpu.models.tables), and the whole chain —

    filter → pre-score → score → normalize → weighted-sum → masked-argmax

— compiles into ONE jitted XLA computation (SURVEY.md §7 stage 6).
``selectHost``'s reservoir-sampled random tie-break (minisched.go:304-325)
becomes the deterministic seeded masked-argmax implemented here, bit-exact
with the scalar oracle's ``engine.tiebreak.select_host``.

Design rules (SURVEY.md §7 hard part 4): static shapes only — infeasible
and padding entries are masked, never dropped; no python control flow on
array values; everything is pure so XLA can fuse.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from minisched_tpu.framework.plugin import implements_batch

# host constants: a module-level jnp scalar is a device array, and creating
# one initialises the JAX backend (takes the chip) at import
UINT32_MAX = np.uint32(0xFFFFFFFF)
NEG_INF_SCORE = int(np.iinfo(np.int32).min)


@dataclass(frozen=True)
class BatchContext:
    """Static per-compilation configuration handed to batch plugin kernels.

    Everything here must be hashable / trace-constant; per-call array data
    lives in the tables, not the context.
    """

    weights: Tuple[Tuple[str, int], ...] = ()

    def weight_of(self, name: str) -> int:
        for n, w in self.weights:
            if n == name:
                return w
        return 1


def mix32(seed, idx):
    """Vector murmur3-finalizer-style mix of (seed, idx) → uint32.

    Bit-for-bit identical to ``engine.tiebreak.mix32`` (same 32-bit ops,
    evaluated in jnp's modular uint32 arithmetic).
    """
    seed = jnp.asarray(seed, jnp.uint32)
    idx = jnp.asarray(idx, jnp.uint32)
    x = seed ^ (idx * jnp.uint32(0x9E3779B9))
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


import os as _os

#: route select_hosts through the one-pass Pallas kernel
#: (ops/pallas_kernels.py).  DEFAULT ON (VERDICT r4 item 2) — the XLA
#: lowering of the tail is ~5 passes over the (P, N) planes, the kernel
#: is one; select_hosts itself still falls back to XLA off-TPU.  Disable
#: with MINISCHED_TPU_PALLAS=0 or set_pallas(False); trace-time
#: constant, so toggle before building evaluators.
_USE_PALLAS = _os.environ.get("MINISCHED_TPU_PALLAS", "1") != "0"

#: test hook: route select_hosts through the Pallas dispatch logic even
#: off-TPU (interpret mode), so the SHAPE fallback below is exercisable
#: on CPU CI — the round-5 regression (P=1 crashing every scan-lane
#: consumer) was invisible to `make test` precisely because the route
#: was dead code off-TPU.
_FORCE_PALLAS_ROUTE = False


def set_pallas(enabled: bool) -> None:
    global _USE_PALLAS
    _USE_PALLAS = enabled


def set_force_pallas_route(enabled: bool) -> None:
    global _FORCE_PALLAS_ROUTE
    _FORCE_PALLAS_ROUTE = enabled


#: trace-time depth of mesh-sharded program builds (parallel/sharding.
#: MeshPackedCaller) — a pallas_call inside a GSPMD-partitioned program
#: would need a shard_map wrapper the kernel doesn't have, so the mesh
#: path takes the (bit-identical) XLA tail instead.  A depth counter,
#: not a bool: nested/overlapping traces from several callers must not
#: clear the guard early.  THREAD-LOCAL: jax traces run on the calling
#: thread, and a multi-engine process (HA plane) can trace a mesh
#: program and a single-device program concurrently — a process-global
#: flag would make the single-device engine permanently compile without
#: its Pallas route.
_MESH_TRACING = threading.local()


class mesh_trace_guard:
    """Context manager marking 'a mesh-sharded program is being traced'
    on this thread.

    Trace-time only — dispatch of an already-compiled executable never
    re-enters select_hosts, so wrapping every sharded call site costs a
    counter bump, and the flag is only ever read during trace."""

    def __enter__(self):
        _MESH_TRACING.depth = getattr(_MESH_TRACING, "depth", 0) + 1
        return self

    def __exit__(self, *exc):
        _MESH_TRACING.depth -= 1
        return False


def tracing_under_mesh() -> bool:
    return getattr(_MESH_TRACING, "depth", 0) > 0


def _pallas_shape_ok(P: int, N: int) -> bool:
    """Whether select_hosts_pallas can tile (P, N) — the kernel's
    smallest tiles are 8 (pods) × 128 (nodes) (pallas_kernels._tiling).
    The bind-exact sequential scan evaluates ONE pod per step (P=1), so
    routing unconditionally on TPU crashed every scan-lane consumer
    (VERDICT r5 headline); non-tiling shapes take the XLA tail instead."""
    return P % 8 == 0 and N % 128 == 0


def select_hosts(scores, mask, seeds):
    """Batched deterministic selectHost (minisched.go:304-325 re-designed).

    scores: i32[P, N] weighted totals; mask: bool[P, N] feasibility;
    seeds: u32[P] per-pod tie-break seeds.

    Returns (choice i32[P] — node index or -1, best_score i32[P]).

    Rule (== engine.tiebreak.select_host): among feasible max-score nodes,
    pick the one minimizing mix32(seed, node_index); remaining ties (hash
    collisions) go to the lowest index.
    """
    if _USE_PALLAS and not tracing_under_mesh():
        import jax as _jax

        # only route to Pallas where it compiles natively — interpreter
        # mode off-TPU would be far slower than the XLA path below (tests
        # exercise the kernel directly with interpret=True), never inside
        # a mesh-sharded trace (a pallas_call under GSPMD needs a
        # shard_map the kernel doesn't have) — and only
        # for shapes the kernel can tile: P=1 scan steps and other
        # non-divisible shapes fall through to the XLA tail (bit-exact
        # either way; the Pallas kernel is a perf route, not a semantic)
        P, N = scores.shape
        if (
            _FORCE_PALLAS_ROUTE or _jax.default_backend() == "tpu"
        ) and _pallas_shape_ok(P, N):
            from minisched_tpu.ops.pallas_kernels import select_hosts_pallas

            return select_hosts_pallas(
                scores, mask, seeds, interpret=_FORCE_PALLAS_ROUTE
            )
    # the XLA tail under its own name: in a device trace its fusions read
    # select_hosts_xla/..., beside the Mosaic kernel's select_hosts
    with jax.named_scope("select_hosts_xla"):
        P, N = scores.shape
        masked = jnp.where(mask, scores, NEG_INF_SCORE)
        best = masked.max(axis=1)  # i32[P]
        cand = mask & (masked == best[:, None])
        h = mix32(seeds[:, None], jnp.arange(N, dtype=jnp.uint32)[None, :])
        hkey = jnp.where(cand, h, UINT32_MAX)
        minh = hkey.min(axis=1)
        # among positions achieving the min hash, prefer real candidates
        # (guards the pathological h == UINT32_MAX collision), then the
        # lowest index
        is_min = hkey == minh[:, None]
        pref = is_min & cand
        has_pref = pref.any(axis=1)
        pick_from = jnp.where(has_pref[:, None], pref, is_min)
        choice = jnp.argmax(pick_from, axis=1).astype(jnp.int32)
        feasible_any = mask.any(axis=1)
        choice = jnp.where(feasible_any, choice, jnp.int32(-1))
        best = jnp.where(feasible_any, best, jnp.int32(0))
    return choice, best


@jax.tree_util.register_pytree_node_class
@dataclass
class PlacementResult:
    """Device-side result of one fused evaluation."""

    choice: Any  # i32[P] node index, -1 = unschedulable
    best_score: Any  # i32[P]
    feasible_count: Any  # i32[P]
    #: bool[K, P, N] per-filter-plugin pass masks (diagnostics; K = number of
    #: filter plugins).  Present only when the evaluator was built with
    #: ``with_diagnostics=True``.
    filter_masks: Optional[Any] = None
    #: i32[K, P, N] per-score-plugin normalized × weighted matrices
    #: (diagnostics).
    score_matrices: Optional[Any] = None
    #: i32[K, P, N] per-score-plugin RAW matrices, pre-normalize/pre-weight
    #: (diagnostics) — the batch analog of the scalar AddScoreResult record.
    raw_score_matrices: Optional[Any] = None

    def tree_flatten(self):
        return (
            (
                self.choice,
                self.best_score,
                self.feasible_count,
                self.filter_masks,
                self.score_matrices,
                self.raw_score_matrices,
            ),
            None,
        )

    @classmethod
    def tree_unflatten(cls, _, leaves):
        return cls(*leaves)


@dataclass
class StaticWavePlanes:
    """Round-invariant planes shared by every round of a repair wave.

    Filters/scores whose kernels don't read intra-wave committed state
    (``Plugin.reads_committed_state`` False — node identity, labels,
    taints, the cross-pod combo planes) produce the same mask / RAW score
    matrix in every round; the repair loop computes them ONCE and per
    round only re-evaluates the committed-state plugins, then
    re-NORMALIZES the cached raw scores against the round's full mask —
    bit-identical to evaluating the whole chain per round (normalization
    is the only mask-dependent score step)."""

    static_mask: Any  # bool[P, N] conjunction of static filter masks
    static_names: frozenset  # names of the filters folded into static_mask
    aux: Dict[str, Dict[str, Any]]  # pre-score aux (static plugins only)
    raw_scores: Dict[str, Any]  # plugin name → i32[P, N] raw score matrix


def precompute_static(
    pods,
    nodes,
    filter_plugins: Sequence[Any],
    pre_score_plugins: Sequence[Any],
    score_plugins: Sequence[Any],
    ctx: BatchContext,
    extra: Any = None,
    extra_dynamic: frozenset = frozenset(),
) -> StaticWavePlanes:
    """Evaluate the round-invariant half of the chain once (traceable).

    ``extra_dynamic``: plugin names to treat as round-varying on top of
    the ``reads_committed_state`` flag — the sequential scans pass the
    plugins whose carried coupling planes (combos/volumes) change mid-
    scan, which the wave/repair split never has to care about."""
    valid = pods.valid[:, None] & nodes.valid[None, :]

    def is_dynamic(pl) -> bool:
        return (
            getattr(pl, "reads_committed_state", False)
            or pl.name() in extra_dynamic
        )

    mask = valid
    names = []
    for pl in filter_plugins:
        if is_dynamic(pl):
            continue
        names.append(pl.name())
        if getattr(pl, "needs_extra", False):
            mask = mask & pl.batch_filter(ctx, pods, nodes, extra)
        else:
            mask = mask & pl.batch_filter(ctx, pods, nodes)
    aux: Dict[str, Dict[str, Any]] = {}
    for pl in pre_score_plugins:
        if not is_dynamic(pl):
            aux[pl.name()] = pl.batch_pre_score(ctx, pods, nodes)
    raw: Dict[str, Any] = {}
    for pl in score_plugins:
        if is_dynamic(pl):
            continue
        if getattr(pl, "needs_extra", False):
            s = pl.batch_score(ctx, pods, nodes, aux.get(pl.name(), {}), extra)
        else:
            s = pl.batch_score(ctx, pods, nodes, aux.get(pl.name(), {}))
        raw[pl.name()] = s
    return StaticWavePlanes(mask, frozenset(names), aux, raw)


def evaluate(
    pods,
    nodes,
    filter_plugins: Sequence[Any],
    pre_score_plugins: Sequence[Any],
    score_plugins: Sequence[Any],
    ctx: BatchContext,
    with_diagnostics: bool = False,
    extra: Any = None,
    static: Optional[StaticWavePlanes] = None,
) -> PlacementResult:
    """One fused scheduling evaluation (traceable; call under jit).

    Mirrors the scalar oracle exactly:
    * filter chain ANDs per-plugin masks (plugin-order short-circuiting,
      minisched.go:130-137, affects only diagnostics, not the mask — the
      conjunction is order-independent);
    * pre-score produces per-plugin aux arrays (the CycleState analog,
      nodenumber.go:58-61);
    * score → per-plugin normalize (mask-aware) → weight → sum
      (minisched.go:164-199, with the weight TODO at :187 implemented);
    * deterministic seeded masked-argmax (select_hosts).

    ``static``: precomputed round-invariant planes (precompute_static) —
    filters in ``static.static_names`` contribute via ``static_mask``
    instead of re-running, and static scorers reuse their cached RAW
    matrices (normalization still runs against THIS call's full mask, so
    results are bit-identical to the unsplit evaluation).  Incompatible
    with ``with_diagnostics`` (per-plugin masks need every filter run).
    """
    valid = pods.valid[:, None] & nodes.valid[None, :]
    if static is not None:
        assert not with_diagnostics, "diagnostics need the unsplit chain"
        mask = valid & static.static_mask
        run_filters = [
            pl for pl in filter_plugins if pl.name() not in static.static_names
        ]
    else:
        mask = valid
        run_filters = list(filter_plugins)
    per_filter = []
    for pl in run_filters:
        if getattr(pl, "needs_extra", False):
            m = pl.batch_filter(ctx, pods, nodes, extra)
        else:
            m = pl.batch_filter(ctx, pods, nodes)
        if with_diagnostics:
            per_filter.append(m)
        mask = mask & m

    aux: Dict[str, Dict[str, Any]] = dict(static.aux) if static else {}
    for pl in pre_score_plugins:
        if pl.name() not in aux:
            aux[pl.name()] = pl.batch_pre_score(ctx, pods, nodes)

    P, N = mask.shape
    totals = jnp.zeros((P, N), jnp.int32)
    per_score = []
    per_raw = []
    for pl in score_plugins:
        if static is not None and pl.name() in static.raw_scores:
            s = static.raw_scores[pl.name()]
        elif getattr(pl, "needs_extra", False):
            s = pl.batch_score(ctx, pods, nodes, aux.get(pl.name(), {}), extra)
        else:
            s = pl.batch_score(ctx, pods, nodes, aux.get(pl.name(), {}))
        if with_diagnostics:
            per_raw.append(s.astype(jnp.int32))
        s = pl.batch_normalize(ctx, s, mask)
        w = s.astype(jnp.int32) * jnp.int32(ctx.weight_of(pl.name()))
        if with_diagnostics:
            per_score.append(w)
        totals = totals + w

    choice, best = select_hosts(totals, mask, pods.seed)
    return PlacementResult(
        choice=choice,
        best_score=best,
        feasible_count=mask.sum(axis=1).astype(jnp.int32),
        filter_masks=jnp.stack(per_filter) if per_filter else None,
        score_matrices=jnp.stack(per_score) if per_score else None,
        raw_score_matrices=jnp.stack(per_raw) if per_raw else None,
    )


def unschedulable_plugin_masks(filter_masks, valid):
    """bool[K, P]: is filter plugin k a FIRST-failing plugin for pod p on
    some node — the batch analog of the scalar Diagnosis collection
    (minisched.go:118-121,134): per node, only the first plugin in chain
    order that rejects is recorded (short-circuit), and a pod's
    ``unschedulable_plugins`` is the union over nodes.

    filter_masks: bool[K, P, N] per-plugin pass masks (PlacementResult
    diagnostics); valid: bool[P, N] the pod×node validity mask.
    """
    prefix = valid
    out = []
    for k in range(filter_masks.shape[0]):
        m = filter_masks[k]
        out.append(jnp.any(prefix & ~m, axis=1))
        prefix = prefix & m
    return jnp.stack(out)


def validate_batch_chains(*chains: Sequence[Any]) -> None:
    """Every plugin in a device chain must implement the batch protocol —
    fail at construction with a clear error, not at trace time."""
    for chain in chains:
        for pl in chain:
            if not implements_batch(pl):
                raise TypeError(
                    f"plugin {pl.name()} has no batch form; "
                    "scalar-only plugins must run through the engine"
                )


class FusedEvaluator:
    """Compiled wrapper: plugin chains fixed at construction; tables vary.

    The jit caches one executable per (P, N) table capacity — capacities are
    padded to lane multiples (models.tables.pad_to) precisely so this cache
    stays small (SURVEY.md §7 hard part 4).
    """

    def __init__(
        self,
        filter_plugins: Sequence[Any],
        pre_score_plugins: Sequence[Any],
        score_plugins: Sequence[Any],
        weights: Optional[Dict[str, int]] = None,
        with_diagnostics: bool = False,
    ):
        validate_batch_chains(filter_plugins, pre_score_plugins, score_plugins)
        self.ctx = BatchContext(
            weights=tuple(sorted((weights or {}).items()))
        )
        self._fn = jax.jit(
            partial(
                evaluate,
                filter_plugins=tuple(filter_plugins),
                pre_score_plugins=tuple(pre_score_plugins),
                score_plugins=tuple(score_plugins),
                ctx=self.ctx,
                with_diagnostics=with_diagnostics,
            )
        )

    def __call__(self, pods, nodes, extra: Any = None) -> PlacementResult:
        return self._fn(pods, nodes, extra=extra)
