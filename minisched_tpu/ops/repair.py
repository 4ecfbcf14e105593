"""Wave scheduling with conflict repair: throughput mode that never
double-books.

The stateless wave (ops/state.wave_step) evaluates every pod against the
pre-wave state and commits all placements — two pods can double-book a
node that single-pod semantics would have caught (SURVEY.md §7 hard part
2).  The sequential scan (ops/sequential.py) is bind-exact but serial.
This module is the middle mode: per round, evaluate all uncommitted pods,
then ACCEPT the conflict-free subset under a deterministic rule — pods in
index order per node, while cumulative demand still fits (cpu / memory /
ephemeral / pod count) and no same-round host-port collision — commit
them, and re-evaluate the rejected remainder against the updated table.
Every round commits at least the lowest-indexed contender per node, so the
``lax.while_loop`` converges; infeasible pods (choice −1) are terminal
because commits only consume resources.

Placements are NOT bit-exact with the sequential loop (scores within a
round see round-start state); the guarantee is safety: the final table
never exceeds any node's allocatable, verified by tests/test_repair.py.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from minisched_tpu.models.tables import NodeTable, PodTable
from minisched_tpu.ops.fused import BatchContext, evaluate, precompute_static
from minisched_tpu.ops.state import apply_placements

# NumPy, not jnp: a module-level device scalar initialises the backend at import
_INF32 = np.int32(2**31 - 1)


def _segment_starts(sorted_keys):
    """positions of each segment's first element under a sorted key array."""
    pos = jnp.arange(sorted_keys.shape[0])
    is_start = jnp.concatenate(
        [jnp.array([True]), sorted_keys[1:] != sorted_keys[:-1]]
    )
    return jax.lax.cummax(jnp.where(is_start, pos, 0))


def accept_placements(
    nodes: NodeTable,
    pods: PodTable,
    choice,
    active,
    check_resources: bool = True,
    check_ports: bool = True,
    vol_state=None,
    restr_state=None,
):
    """bool[P]: which tentative placements commit this round.

    Deterministic rule: group pods by chosen node, take them in pod-index
    order while the node's remaining allocatable covers the cumulative
    demand; among same-round claims of one host port on one node only the
    first pod survives.

    ``check_resources`` / ``check_ports`` mirror whether NodeResourcesFit /
    NodePorts are in the filter chain — acceptance must enforce exactly
    what the chain enforces (a config without the Fit filter over-commits
    on purpose, like the reference would), and with the Fit filter present
    the first candidate per node always fits, which is what guarantees a
    commit per contested node per round (convergence).

    ``vol_state``: list of (pod_amt i32[P], node_count i32[N], max) triples,
    one per volume-limit plugin in the chain (the EBS/GCEPD/Azure/generic
    family split — plugins/volumelimits.py) — each family's counts then
    join the cumulative-demand rule.  (Same-round double-booking of one
    FREE PersistentVolume is out of acceptance's scope: the PV controller
    binds a claim exactly once, so the loser fails at bind time and
    requeues — the same race two racing schedulers have upstream.)

    ``restr_state``: (pod_vol i32[P, V], pod_ro bool[P, V]) — per mount
    slot, the volume row (−1 = unbound/none) and read-only flag — when
    VolumeRestrictions is in the chain.  Same-round claims of one volume
    on one node then follow the sequential-equivalent rule: the first pod
    (index order) always survives; later pods survive only if both they
    and the first are read-only.  (Exactly what a sequential bind order
    yields: a writable first mount blocks everyone, a read-only first
    mount admits read-only followers and rejects writable ones — a
    rejected writable never blocks later read-only mounts.)
    """
    P = choice.shape[0]
    live = active & (choice >= 0)
    if (
        not check_resources
        and not check_ports
        and vol_state is None
        and restr_state is None
    ):
        return live
    # sort by (node, pod index): key groups node segments, index-ordered
    key = jnp.where(live, choice, _INF32 // (P + 1)) * (P + 1) + jnp.arange(P)
    order = jnp.argsort(key)
    s_choice = choice[order]
    s_live = live[order]
    seg = _segment_starts(jnp.where(s_live, s_choice, -2))

    # same-round port dedup: claims of (node, port) keep the first pod
    if check_ports:
        W = pods.port.shape[1]
        slot_in_range = jnp.arange(W)[None, :] < pods.num_ports[:, None]
        # a pod repeating one port across its own containers is a single
        # claim — drop intra-pod duplicate slots so it can't lose to itself
        dup_within = jnp.any(
            (pods.port[:, :, None] == pods.port[:, None, :])
            & (jnp.arange(W)[None, None, :] < jnp.arange(W)[None, :, None])
            & slot_in_range[:, None, :],
            axis=2,
        )  # (P, W): an earlier slot already claims this port
        pair_key = (
            jnp.where(live, choice, -1)[:, None] * jnp.int32(65536) + pods.port
        )  # (P, W); ports < 65536
        pair_live = live[:, None] & slot_in_range & ~dup_within
        flat_key = jnp.where(pair_live, pair_key, _INF32).reshape(-1)
        # jnp.argsort is stable: pod-index order survives within equal keys
        porder = jnp.argsort(flat_key)
        sflat = flat_key[porder]
        first = jnp.concatenate([jnp.array([True]), sflat[1:] != sflat[:-1]])
        loses = jnp.zeros(P * W, bool).at[porder].set(~first & (sflat < _INF32))
        port_ok = ~jnp.any(loses.reshape(P, W), axis=1)  # (P,)
    else:
        port_ok = jnp.ones(P, bool)

    # same-round volume dedup (VolumeRestrictions): per (node, volume),
    # sequential-equivalent rule — first pod in index order survives,
    # later pods only when both they and the first mount read-only
    if restr_state is not None:
        pod_vol, pod_ro, n_vol_rows = restr_state
        V = pod_vol.shape[1]
        # a pod mounting one volume through two claims is a single mount —
        # drop intra-pod duplicate slots so it can't lose to itself (the
        # scalar filter only compares against OTHER pods)
        dup_within = jnp.any(
            (pod_vol[:, :, None] == pod_vol[:, None, :])
            & (pod_vol[:, None, :] >= 0)
            & (jnp.arange(V)[None, None, :] < jnp.arange(V)[None, :, None]),
            axis=2,
        )  # (P, V): an earlier slot already mounts this volume
        slot_live = live[:, None] & (pod_vol >= 0) & ~dup_within
        # key packs (node, volume); requires n_vol_rows * N < 2^31 (same
        # discipline as the port key's node * 65536 above)
        pair_key = choice[:, None] * jnp.int32(n_vol_rows) + pod_vol
        flat_key = jnp.where(slot_live, pair_key, _INF32).reshape(-1)
        # jnp.argsort is stable: pod-index order survives within equal keys
        vorder = jnp.argsort(flat_key)
        s_key = flat_key[vorder]
        s_ro = pod_ro.reshape(-1)[vorder]
        v_first = jnp.concatenate([jnp.array([True]), s_key[1:] != s_key[:-1]])
        first_ro = s_ro[_segment_starts(s_key)]
        ok_slot = v_first | (s_ro & first_ro)
        v_loses = jnp.zeros(P * V, bool).at[vorder].set(
            ~ok_slot & (s_key < _INF32)
        )
        restr_ok = ~jnp.any(v_loses.reshape(P, V), axis=1)  # (P,)
    else:
        restr_ok = jnp.ones(P, bool)

    eligible = s_live & (port_ok & restr_ok)[order]
    if not check_resources and vol_state is None:
        return jnp.zeros(P, bool).at[order].set(eligible) & live

    def prefix_fits(pod_amt, node_req, node_alloc):
        amt = jnp.where(eligible, pod_amt[order], 0)
        incl = jnp.cumsum(amt)
        ex = incl - amt  # exclusive cumsum
        within_ex = ex - ex[seg]  # demand of earlier accepted-candidates
        idx = jnp.where(s_live, s_choice, 0)
        headroom = (node_alloc - node_req)[idx]
        # zero-demand pods always pass, mirroring the filters (a pod that
        # requests nothing fits even an over-committed node — the scalar
        # NodeResourcesFit/NodeVolumeLimits semantics)
        return (amt == 0) | (within_ex + amt <= headroom)

    ones = jnp.ones(P, jnp.int32)
    fits = eligible
    if check_resources:
        fits = (
            fits
            & prefix_fits(pods.req_cpu, nodes.req_cpu, nodes.alloc_cpu)
            & prefix_fits(pods.req_mem, nodes.req_mem, nodes.alloc_mem)
            & prefix_fits(pods.req_eph, nodes.req_eph, nodes.alloc_eph)
            & prefix_fits(ones, nodes.req_pods, nodes.alloc_pods)
        )
    if vol_state is not None:
        for pod_amt, node_count, max_volumes in vol_state:
            fits = fits & prefix_fits(
                pod_amt, node_count, jnp.full_like(node_count, max_volumes)
            )
    # NOTE: the prefix rule is conservative only w.r.t. earlier *candidates*
    # that themselves fit — an earlier pod that does NOT fit still occupies
    # prefix demand this round; it is rejected and retried next round, so
    # convergence and safety both hold (never over-commit: the prefix is an
    # upper bound on what actually commits ahead of a pod).
    accept = jnp.zeros(P, bool).at[order].set(fits)
    return accept & live


def repair_wave_step(
    nodes: NodeTable,
    pods: PodTable,
    filter_plugins: Sequence[Any],
    pre_score_plugins: Sequence[Any],
    score_plugins: Sequence[Any],
    ctx: BatchContext,
    extra: Any = None,
    max_rounds: int = 16,
    with_diagnostics: bool = False,
    split_static: bool = True,
) -> Tuple[Any, ...]:
    """Evaluate-accept-commit rounds until every pod is placed or
    infeasible (bounded by ``max_rounds``).  Traceable; call under jit.

    Returns (updated NodeTable, choice i32[P] with −1 = unplaced,
    rounds_used i32); with ``with_diagnostics`` a fourth element — bool
    [K, P] per-filter-plugin first-failure masks for the UNPLACED pods
    against the final table (ops/fused.unschedulable_plugin_masks) — so
    the engine's FitError names the actually-failing plugin(s), like the
    scalar Diagnosis (minisched.go:118-121,134).

    ``split_static``: compute the round-invariant planes (filters/raw
    scores of plugins with ``reads_committed_state`` False) ONCE and only
    re-evaluate the committed-state plugins per round — bit-identical
    results (ops/fused.StaticWavePlanes), at a fraction of the per-round
    FLOPs (the full default roster re-ran 15 filter kernels per round;
    only 7 read intra-wave state).  Off switch exists for the equivalence
    test.
    """
    P = pods.valid.shape[0]
    names = {pl.name() for pl in filter_plugins}
    check_resources = "NodeResourcesFit" in names
    check_ports = "NodePorts" in names
    # volume-limit plugins in the chain, as (family index, max) pairs —
    # EBS/GCEPD/Azure/generic all carry volume_family_index
    # (plugins/volumelimits.py); detection is attribute-based so simulator
    # wrappers (which forward attributes) are seen too
    fam_limits: Tuple[Tuple[int, int], ...] = ()
    check_restr = False
    if extra is not None:
        fam_limits = tuple(
            (pl.volume_family_index, pl.max_volumes)
            for pl in filter_plugins
            if getattr(pl, "volume_family_index", None) is not None
        )
        check_restr = any(
            getattr(pl, "enforces_volume_restrictions", False)
            for pl in filter_plugins
        )

    # the volume planes are carried whenever something reads them across
    # rounds: VolumeRestrictions (conflicts) or any limit plugin (its
    # unique-attach dedup reads vol_any)
    track_vols = check_restr or bool(fam_limits)
    if track_vols:
        # per-mount-slot volume rows / read-only flags, fixed across rounds
        from minisched_tpu.ops.state import mount_slot_planes

        slot_cnt, slot_vol, slot_ro, slot_fam, slot_dup = mount_slot_planes(
            extra
        )
        n_vol_rows = extra.vol_any.shape[0]
        dummy_row = n_vol_rows - 1  # never referenced by any claim row

    static = (
        precompute_static(
            pods, nodes, filter_plugins, pre_score_plugins, score_plugins,
            ctx, extra=extra,
        )
        if split_static
        else None
    )

    def cond(carry):
        nodes_, committed, final, rnd, progress, vols_fam, va, vr = carry
        return progress & (rnd < max_rounds)

    def body(carry):
        nodes_, committed, final, rnd, _, vols_fam, va, vr = carry
        import dataclasses

        active_pods = dataclasses.replace(
            pods, valid=pods.valid & ~committed
        )
        # feed committed volume state back into the FILTER too — otherwise
        # a node filled to its volume limit (or holding a conflicting
        # mount) in an earlier round keeps winning the argmax and the
        # contender never moves to its runner-up
        extra_ = extra
        if fam_limits:
            extra_ = dataclasses.replace(extra_, node_vols_fam=vols_fam)
        if track_vols:
            extra_ = dataclasses.replace(extra_, vol_any=va, vol_rw=vr)
        result = evaluate(
            active_pods, nodes_, filter_plugins, pre_score_plugins,
            score_plugins, ctx, extra=extra_, static=static,
        )
        accept = accept_placements(
            nodes_, active_pods, result.choice, active_pods.valid,
            check_resources=check_resources, check_ports=check_ports,
            vol_state=(
                [
                    (extra.pod_vols_fam[:, f], vols_fam[f], mx)
                    for f, mx in fam_limits
                ]
                if fam_limits
                else None
            ),
            restr_state=(
                (slot_vol, slot_ro, n_vol_rows) if check_restr else None
            ),
        )
        nodes_ = apply_placements(
            nodes_, active_pods, jnp.where(accept, result.choice, -1)
        )
        idx = jnp.where(accept, result.choice, 0)
        if fam_limits:
            # carry the committed attach counts so later rounds (which see
            # the static extra tables) can't blow the per-node limit —
            # counting only NEW attachments (a volume already on the node,
            # per pre-update vol_any, is not a new attach)
            attached = va[jnp.maximum(slot_cnt, 0), idx[:, None]]  # (P, V)
            new_slot = accept[:, None] & (slot_cnt >= 0) & ~slot_dup & ~attached
            for f in range(vols_fam.shape[0]):
                counts_f = jnp.sum(
                    new_slot & (slot_fam == f), axis=1, dtype=jnp.int32
                )
                vols_fam = vols_fam.at[f, idx].add(counts_f)
            vols_fam = vols_fam.at[0, idx].add(
                jnp.where(accept, extra.pod_missing, 0)
            )
        if track_vols:
            # record the committed pods' mounts in the volume planes;
            # non-accepted slots scatter into the dummy row.  vol_any rows
            # are counting keys (bound PV or unbound claim — the limit
            # plugins' dedup); vol_rw only tracks bound, writable mounts
            # (the restriction conflicts)
            slot_acc = accept[:, None] & (slot_cnt >= 0)
            rows = jnp.where(slot_acc, slot_cnt, dummy_row)
            cols = jnp.broadcast_to(idx[:, None], rows.shape)
            va = va.at[rows, cols].set(True)
            rw_rows = jnp.where(
                accept[:, None] & (slot_vol >= 0) & ~slot_ro, slot_vol, dummy_row
            )
            vr = vr.at[rw_rows, cols].set(True)
        final = jnp.where(accept, result.choice, final)
        committed = committed | accept
        # stop when nothing committed AND no uncommitted pod is feasible
        retryable = active_pods.valid & (result.choice >= 0) & ~accept
        progress = jnp.any(accept) & jnp.any(retryable)
        return nodes_, committed, final, rnd + 1, progress, vols_fam, va, vr

    committed0 = ~pods.valid  # padding rows never schedule
    final0 = jnp.full((P,), -1, jnp.int32)
    vols_fam0 = (
        extra.node_vols_fam
        if fam_limits
        else jnp.zeros((1, nodes.valid.shape[0]), jnp.int32)
    )
    va0 = extra.vol_any if track_vols else jnp.zeros((1, 1), bool)
    vr0 = extra.vol_rw if track_vols else jnp.zeros((1, 1), bool)
    nodes, committed, final, rounds, _, vols_fam, va, vr = jax.lax.while_loop(
        cond,
        body,
        (
            nodes, committed0, final0, jnp.int32(0), jnp.bool_(True),
            vols_fam0, va0, vr0,
        ),
    )
    if not with_diagnostics:
        return nodes, final, rounds

    # one diagnostic evaluation of the unplaced remainder against the
    # FINAL state (committed volume/limit planes included) — filters only
    # (the score chain can't affect unschedulable_plugins), and skipped
    # outright when every pod placed
    import dataclasses

    from minisched_tpu.ops.fused import unschedulable_plugin_masks

    K = len(filter_plugins)
    if K == 0:
        return nodes, final, rounds, jnp.zeros((0, P), bool)
    losers = dataclasses.replace(pods, valid=pods.valid & ~committed)
    extra_f = extra
    if extra is not None and fam_limits:
        extra_f = dataclasses.replace(extra_f, node_vols_fam=vols_fam)
    if extra is not None and track_vols:
        extra_f = dataclasses.replace(extra_f, vol_any=va, vol_rw=vr)

    def diag(_):
        result = evaluate(
            losers, nodes, filter_plugins, (), (), ctx,
            with_diagnostics=True, extra=extra_f,
        )
        valid = losers.valid[:, None] & nodes.valid[None, :]
        return unschedulable_plugin_masks(result.filter_masks, valid)

    unsched = jax.lax.cond(
        jnp.any(losers.valid),
        diag,
        lambda _: jnp.zeros((K, P), bool),
        None,
    )
    return nodes, final, rounds, unsched


class RepairingEvaluator:
    """Compiled wrapper (argument order matches FusedEvaluator).

    ``mesh``: a jax.sharding.Mesh — the repair loop then runs SHARDED over
    the (pods × nodes) device mesh (parallel/sharding.py), inputs are
    re-placed onto the mesh per call, and the SAME construction-time
    guards run (batch-protocol validation + the static-classification
    probe) — a config must behave identically single-device and sharded.
    """

    def __init__(
        self,
        filter_plugins: Sequence[Any],
        pre_score_plugins: Sequence[Any],
        score_plugins: Sequence[Any],
        weights: Optional[dict] = None,
        max_rounds: int = 16,
        with_diagnostics: bool = False,
        split_static: bool = True,
        mesh: Any = None,
    ):
        from minisched_tpu.ops.fused import validate_batch_chains

        validate_batch_chains(filter_plugins, pre_score_plugins, score_plugins)
        ctx = BatchContext(weights=tuple(sorted((weights or {}).items())))
        if split_static:
            # functional guard: a plugin misclassified as round-invariant
            # would silently serve stale verdicts every round — probe each
            # static-classified kernel against perturbed committed-state
            # planes and refuse construction on any sensitivity
            from minisched_tpu.ops.staticcheck import verify_static_classification

            verify_static_classification(
                [
                    pl
                    for pl in filter_plugins
                    if not getattr(pl, "reads_committed_state", False)
                ],
                [
                    pl
                    for pl in score_plugins
                    if not getattr(pl, "reads_committed_state", False)
                ],
                ctx,
            )
        self._mesh = mesh
        # packed-mode state: jitted (flat buffers → results) entry points,
        # keyed on the (pod, node-agg, extra) schemas — see call_packed
        self._chains = (tuple(filter_plugins), tuple(pre_score_plugins),
                        tuple(score_plugins))
        self._ctx = ctx
        self._max_rounds = max_rounds
        self._with_diagnostics = with_diagnostics
        self._split_static = split_static
        self._packed_caller = None
        if mesh is not None:
            from minisched_tpu.parallel.sharding import sharded_repair_step

            self._fn = sharded_repair_step(
                mesh,
                filter_plugins,
                pre_score_plugins,
                score_plugins,
                ctx,
                max_rounds=max_rounds,
                with_diagnostics=with_diagnostics,
                split_static=split_static,
            )
        else:
            self._fn = jax.jit(
                partial(
                    repair_wave_step,
                    filter_plugins=tuple(filter_plugins),
                    pre_score_plugins=tuple(pre_score_plugins),
                    score_plugins=tuple(score_plugins),
                    ctx=ctx,
                    max_rounds=max_rounds,
                    with_diagnostics=with_diagnostics,
                    split_static=split_static,
                ),
            )

    def call_packed(
        self,
        pod_packed: Any,
        node_static: Any,
        node_agg_packed: Any,
        extra_packed: Any = None,
    ):
        """Single-program wave: tables arrive as PACKED host buffers plus
        the device-resident static node columns and are unpacked inside
        the one jitted program (models/tables.PackedCaller: one
        dispatch and three flat transfers per wave).
        Under a mesh the SAME packed contract holds, but the unpacked
        tables get sharding constraints so GSPMD partitions the wave over
        the (pods × nodes) device mesh and the static node columns are
        expected to arrive node-sharded
        (parallel/sharding.MeshPackedCaller — the ISSUE 7 live path)."""
        if self._packed_caller is None:
            filters, pre_scores, scores = self._chains

            def consume(pods, nodes, extra):
                return repair_wave_step(
                    nodes, pods,
                    filter_plugins=filters,
                    pre_score_plugins=pre_scores,
                    score_plugins=scores,
                    ctx=self._ctx,
                    extra=extra,
                    max_rounds=self._max_rounds,
                    with_diagnostics=self._with_diagnostics,
                    split_static=self._split_static,
                )

            if self._mesh is not None:
                from minisched_tpu.parallel.sharding import MeshPackedCaller

                self._packed_caller = MeshPackedCaller(
                    consume, self._mesh, "wave"
                )
            else:
                from minisched_tpu.models.tables import PackedCaller

                self._packed_caller = PackedCaller(consume, "wave")
        return self._packed_caller(
            pod_packed, node_static, node_agg_packed, extra_packed
        )

    def __call__(self, pods: PodTable, nodes: NodeTable, extra: Any = None):
        if self._mesh is not None:
            from minisched_tpu.parallel.sharding import (
                constraint_sharding,
                shard_tables,
            )

            pods, nodes = shard_tables(self._mesh, pods, nodes)
            if extra is not None:
                extra = jax.device_put(
                    extra, constraint_sharding(self._mesh, extra)
                )
            return self._fn(nodes, pods, extra)
        return self._fn(nodes, pods, extra=extra)
