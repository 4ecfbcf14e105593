"""Sequential device engine: bind-exact scheduling via ``lax.scan``.

The reference's loop schedules ONE pod per cycle, so every pod sees the
binds of all pods before it (minisched/minisched.go:32-113).  The wave
evaluator (ops/fused.py + ops/state.py) is the throughput mode — all pods
against the pre-wave state — which is bit-exact only for plugin chains
whose decisions don't depend on earlier binds (e.g. NodeUnschedulable +
NodeNumber).  For bind-dependent chains THIS module is the parity mode: a
``lax.scan`` over the pod axis where each step evaluates one pod row
(still fully vectorized over nodes — the per-step kernel is a (1, N)
slice of the same fused chain) and commits the placement before the next
step.

Cross-pod plugins are supported by carrying their coupling state through
the scan:

* **combo aggregates** (InterPodAffinity / PodTopologySpread): a
  committed pod joins ``combo_global`` / ``combo_here`` / ``combo_dsum``
  for every combo whose selector it matches (``pod_matches_combo``,
  host-precomputed), with the domain mask derived on device from the
  topo-key planes.  Its required anti-affinity terms accumulate into
  ``combo_excl``, which arrives holding the domains of the owners placed
  before the build and which the affinity filter applies to later pods —
  the reverse-direction check, one plane for both.
* **volume planes** (VolumeRestrictions / limit family / VolumeBinding):
  the committed pod's mounts update ``vol_any`` / ``vol_rw`` /
  ``node_vols_fam`` exactly like the repair loop's commit step.

One compiled program schedules the whole table: 100k pods = one scan of
100k fused steps, no host round-trips (SURVEY.md §7 hard part 2 — the
sequential-bind-vs-batch semantic, solved by making the device loop
sequential rather than approximating with repair passes).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from minisched_tpu.models.constraints import (
    HARD_POD_AFFINITY_WEIGHT,
    POD_AXIS_FIELDS,
)
from minisched_tpu.models.tables import NodeTable, PodTable
from minisched_tpu.ops.fused import (
    BatchContext,
    StaticWavePlanes,
    evaluate,
    precompute_static,
)
from minisched_tpu.ops.state import apply_placements, mount_slot_planes


def _slice_pod(pods: PodTable, i) -> PodTable:
    """One-row PodTable view at index i (dynamic, traceable)."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, i, 1, axis=0), pods
    )


def _slice_extra_row(extra: Any, i) -> Any:
    """ConstraintTables with every pod-axis plane narrowed to row i."""
    reps = {
        f: jax.lax.dynamic_slice_in_dim(getattr(extra, f), i, 1, axis=0)
        for f in POD_AXIS_FIELDS
    }
    return dataclasses.replace(extra, **reps)


def _combo_domain_masks(extra: Any, n) -> Any:
    """bool[C, N]: for each combo, the nodes sharing node ``n``'s value of
    the combo's topology key (all-False when n lacks the key).  Unique
    (hostname-like) keys collapse to {n} itself."""
    keys = extra.combo_key  # (C,)
    D = extra.topo_onehot.shape[1]
    d = extra.topo_domain[keys, n]  # (C,) domain id or D sentinel
    has_key = d != D
    dom = extra.topo_onehot[keys, jnp.minimum(d, D - 1), :]  # (C, N)
    N = dom.shape[1]
    onehot_n = jnp.arange(N) == n
    unique = extra.topo_unique[keys]  # (C,)
    return jnp.where(unique[:, None], onehot_n[None, :], dom) & has_key[:, None]


def scan_schedule(
    nodes: NodeTable,
    pods: PodTable,
    filter_plugins: Sequence[Any],
    pre_score_plugins: Sequence[Any],
    score_plugins: Sequence[Any],
    ctx: BatchContext,
    extra: Any = None,
) -> Tuple[NodeTable, Any, Any]:
    """Schedule every pod in order with sequential-bind semantics.

    Returns (final NodeTable, choice i32[P], best_score i32[P]) — the
    placements the reference's one-pod-at-a-time loop would produce,
    computed in one jitted scan.  ``extra`` (the wave's ConstraintTables)
    is required when the chain contains cross-pod plugins; its coupling
    planes are carried and updated per committed pod.
    """
    needs_extra = any(
        getattr(pl, "needs_extra", False)
        for pl in (*filter_plugins, *score_plugins)
    )
    if needs_extra and extra is None:
        names = [
            pl.name()
            for pl in (*filter_plugins, *score_plugins)
            if getattr(pl, "needs_extra", False)
        ]
        raise ValueError(
            f"sequential scan with cross-pod plugins {names} needs the "
            "ConstraintTables — pass `extra`"
        )

    if extra is None:

        def step(carry_nodes, i):
            pod_row = _slice_pod(pods, i)
            result = evaluate(
                pod_row, carry_nodes, filter_plugins, pre_score_plugins,
                score_plugins, ctx,
            )
            carry_nodes = apply_placements(carry_nodes, pod_row, result.choice)
            return carry_nodes, (result.choice[0], result.best_score[0])

        nodes, (choice, best) = jax.lax.scan(
            step, nodes, jnp.arange(pods.valid.shape[0])
        )
        return nodes, choice, best

    # which coupling planes this chain actually needs carried — plugins
    # declare it (scan_carried_planes); an unknown cross-pod plugin without
    # the attribute gets everything (the safe default)
    tracked: set = set()
    for pl in (*filter_plugins, *pre_score_plugins, *score_plugins):
        if getattr(pl, "needs_extra", False):
            tracked |= set(
                getattr(pl, "scan_carried_planes", ("combos", "volumes"))
            )
    track_combos = "combos" in tracked
    track_vols = "volumes" in tracked

    if track_vols:
        slot_cnt, slot_vol, slot_ro, slot_fam, slot_dup = mount_slot_planes(
            extra
        )
        dummy_row = extra.vol_any.shape[0] - 1
        F = extra.node_vols_fam.shape[0]
    A = extra.pan_combo.shape[1]
    _z = jnp.zeros((1, 1), jnp.int32)  # placeholder for untracked carries

    def step(carry, i):
        carry_nodes, dsum, here, glob, excl, revw, va, vr, nvf = carry
        pod_row = _slice_pod(pods, i)
        reps = {}
        if track_combos:
            reps.update(
                combo_dsum=dsum, combo_here=here, combo_global=glob,
                combo_excl=excl, rev_weight=revw,
            )
        if track_vols:
            reps.update(vol_any=va, vol_rw=vr, node_vols_fam=nvf)
        extra_i = dataclasses.replace(_slice_extra_row(extra, i), **reps)
        result = evaluate(
            pod_row, carry_nodes, filter_plugins, pre_score_plugins,
            score_plugins, ctx, extra=extra_i,
        )
        choice = result.choice[0]
        committed = choice >= 0
        n = jnp.maximum(choice, 0)
        carry_nodes = apply_placements(carry_nodes, pod_row, result.choice)

        if track_combos:
            # -- combo aggregates: the committed pod becomes assigned -----
            dom = _combo_domain_masks(extra, n)  # (C, N)
            pmc = extra.pod_matches_combo[i] & committed  # (C,)
            dsum = dsum + (pmc[:, None] & dom).astype(dsum.dtype)
            here = here.at[:, n].add(pmc.astype(here.dtype))
            glob = glob + pmc.astype(glob.dtype)
            # its required anti-affinity terms ban matchers from the domain
            pan_c = extra.pan_combo[i]  # (A,)
            pan_in = (jnp.arange(A) < extra.pan_n[i]) & committed
            excl = excl.at[pan_c].max(pan_in[:, None] & dom[pan_c])
            # symmetric scoring: its preferred terms (signed weight) and
            # required affinity terms (hard weight) now score toward later
            # matching pods over its landing node's domain
            ppa_c = extra.ppa_combo[i]  # (W,)
            W = ppa_c.shape[0]
            ppa_in = (jnp.arange(W) < extra.ppa_n[i]) & committed
            revw = revw.at[ppa_c].add(
                jnp.where(ppa_in, extra.ppa_w[i], 0)[:, None]
                * dom[ppa_c].astype(revw.dtype)
            )
            pa_c = extra.pa_combo[i]  # (PA,)
            pa_in = (
                jnp.arange(pa_c.shape[0]) < extra.pa_n[i]
            ) & committed
            revw = revw.at[pa_c].add(
                jnp.where(pa_in, HARD_POD_AFFINITY_WEIGHT, 0)[:, None]
                * dom[pa_c].astype(revw.dtype)
            )

        if track_vols:
            # -- volume planes: same commit update as the repair loop -----
            sc, sv = slot_cnt[i], slot_vol[i]
            sro, sfam = slot_ro[i], slot_fam[i]
            attached = va[jnp.maximum(sc, 0), n]  # (V,)
            new_slot = committed & (sc >= 0) & ~slot_dup[i] & ~attached
            for f in range(F):
                nvf = nvf.at[f, n].add(
                    jnp.sum(new_slot & (sfam == f), dtype=nvf.dtype)
                )
            nvf = nvf.at[0, n].add(
                jnp.where(committed, extra.pod_missing[i], 0)
            )
            rows = jnp.where(committed & (sc >= 0), sc, dummy_row)
            va = va.at[rows, n].set(True)
            rw_rows = jnp.where(committed & (sv >= 0) & ~sro, sv, dummy_row)
            vr = vr.at[rw_rows, n].set(True)

        carry = (carry_nodes, dsum, here, glob, excl, revw, va, vr, nvf)
        return carry, (choice, result.best_score[0])

    carry0 = (
        nodes,
        extra.combo_dsum if track_combos else _z,
        extra.combo_here if track_combos else _z,
        extra.combo_global if track_combos else _z,
        extra.combo_excl if track_combos else _z,
        extra.rev_weight if track_combos else _z,
        extra.vol_any if track_vols else _z,
        extra.vol_rw if track_vols else _z,
        extra.node_vols_fam if track_vols else _z,
    )
    (nodes, *_), (choice, best) = jax.lax.scan(
        step, carry0, jnp.arange(pods.valid.shape[0])
    )
    return nodes, choice, best


def _slice_pods(pods: PodTable, start, size: int) -> PodTable:
    """A ``size``-row PodTable window starting at dynamic index ``start``."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, start, size, axis=0), pods
    )


def _slice_extra_rows(extra: Any, start, size: int) -> Any:
    reps = {
        f: jax.lax.dynamic_slice_in_dim(getattr(extra, f), start, size, axis=0)
        for f in POD_AXIS_FIELDS
    }
    return dataclasses.replace(extra, **reps)


def blocked_scan_schedule(
    nodes: NodeTable,
    pods: PodTable,
    filter_plugins: Sequence[Any],
    pre_score_plugins: Sequence[Any],
    score_plugins: Sequence[Any],
    ctx: BatchContext,
    extra: Any,
    block_size: int = 32,
) -> Tuple[NodeTable, Any, Any, Any]:
    """Hybrid scan-repair over PRE-GROUPED blocks: the cross-pod lane's
    throughput mode (VERDICT r3 item 4).

    The caller orders pods so every consecutive ``block_size`` window has
    pairwise-DISJOINT cross-pod interaction sets (no pod matches another's
    selector combos or shares a volume — engine/scan_groups.py).  Each
    step then evaluates a whole block against the carried coupling state,
    commits the subset passing repair's deterministic acceptance
    (ops/repair.accept_placements — capacity/port/volume safe), and
    applies every committed pod's plane updates.  Within an interaction
    group the semantics stay sequentially exact — one member per block,
    FIFO across blocks — which is what DoNotSchedule spread / required
    (anti-)affinity correctness needs; across groups, capacity coupling
    gets the repair wave's safety guarantee instead of sequential
    score-exactness (the same trade already accepted for plain pods).

    Returns (nodes, choice i32[P], best i32[P], accepted bool[P]): a pod
    with ``choice >= 0 & ~accepted`` was feasible but lost a same-node
    capacity race to an earlier-in-block pod — the caller retries it (a
    sequential order would never fail it); ``choice < 0`` means
    infeasible against the state its block observed.

    The commit math routes through small matmul chains against the
    hoisted topology one-hot planes — the earlier per-pod (B, A, N)
    domain-mask materializations and the (C, D, N) one-hot einsum read
    ~30MB/step and dominated the step wall, and TPU lowers the obvious
    gather/scatter forms to scalar-core loops.  Fully-padded trailing
    blocks (capacity tiers pad the pod axis) skip the whole step via
    ``lax.cond``.
    """
    from minisched_tpu.ops.repair import accept_placements

    P = pods.valid.shape[0]
    if P % block_size:
        raise ValueError(f"pod capacity {P} not divisible by {block_size}")
    names = {pl.name() for pl in filter_plugins}
    check_resources = "NodeResourcesFit" in names
    check_ports = "NodePorts" in names
    fam_limits = tuple(
        (pl.volume_family_index, pl.max_volumes)
        for pl in filter_plugins
        if getattr(pl, "volume_family_index", None) is not None
    )
    check_restr = any(
        getattr(pl, "enforces_volume_restrictions", False)
        for pl in filter_plugins
    )
    tracked: set = set()
    for pl in (*filter_plugins, *pre_score_plugins, *score_plugins):
        if getattr(pl, "needs_extra", False):
            tracked |= set(
                getattr(pl, "scan_carried_planes", ("combos", "volumes"))
            )
    track_combos = "combos" in tracked
    track_vols = "volumes" in tracked or bool(fam_limits) or check_restr
    if track_vols:
        slot_cnt, slot_vol, slot_ro, slot_fam, slot_dup = mount_slot_planes(
            extra
        )
        dummy_row = extra.vol_any.shape[0] - 1
        F = extra.node_vols_fam.shape[0]
    A = extra.pan_combo.shape[1]
    W = extra.ppa_combo.shape[1]
    PA = extra.pa_combo.shape[1]
    _z = jnp.zeros((1, 1), jnp.int32)
    B = block_size
    # static/dynamic roster split (the repair waves' precompute_static,
    # extended): plugins whose verdict can change mid-scan — committed
    # node state or the carried coupling planes — re-evaluate per step;
    # everything else evaluates ONCE over the whole chunk at batched
    # throughput and enters each step as sliced mask/raw-score rows.
    # HBM residency note: the cached planes are (P_cap, N) per static
    # scorer plus the bool mask — ~1.1GB at the 8192×10k tier with the
    # full roster's three static scorers.  Measured fine on a 16GB v5e
    # next to the node tables; shrink BLOCKED_MAX_CHUNK before adding
    # many static scorers on smaller parts.
    # evaluate() re-normalizes cached raw scores against each step's full
    # mask, so the split is bit-identical to the unsplit chain.  The
    # full-roster step was ~5.5ms of evaluate at (32, 10k) — op-count
    # bound, dominated by the ~14 static plugins this hoists.
    scan_dynamic = frozenset(
        pl.name()
        for pl in (*filter_plugins, *pre_score_plugins, *score_plugins)
        if getattr(pl, "needs_extra", False)
        and set(getattr(pl, "scan_carried_planes", ("combos", "volumes")))
        & tracked
    )
    static_planes = precompute_static(
        pods, nodes, filter_plugins, pre_score_plugins, score_plugins,
        ctx, extra=extra, extra_dynamic=scan_dynamic,
    )
    # per-pod pre-score aux re-derives from each step's sliced rows
    # instead of slicing cached entries (none of the cacheable plugins'
    # aux is worth the slicing machinery)
    static_planes = StaticWavePlanes(
        static_planes.static_mask, static_planes.static_names, {},
        static_planes.raw_scores,
    )

    def _slice_static(start):
        return StaticWavePlanes(
            jax.lax.dynamic_slice_in_dim(
                static_planes.static_mask, start, B, 0
            ),
            static_planes.static_names,
            {},
            {
                k: jax.lax.dynamic_slice_in_dim(v, start, B, 0)
                for k, v in static_planes.raw_scores.items()
            },
        )

    if track_combos:
        # hoisted per-call tensors: every step's zone-domain commit
        # updates are expressed as small matmul chains through these —
        # TPU lowers big gathers/scatters to slow per-element loops, so
        # the step routes (combo, domain) increments through the MXU
        # instead (counts/weights are small ints, exact in f32)
        keys = extra.combo_key  # (C,) combo → topo key id
        C = keys.shape[0]
        K = extra.topo_onehot.shape[0]
        D = extra.topo_onehot.shape[1]
        uniq_c = extra.topo_unique[keys]  # (C,)
        arange_c = jnp.arange(C)
        onehot_f = extra.topo_onehot.astype(jnp.float32)  # (K, D, N)
        key_oh = (keys[None, :] == jnp.arange(K)[:, None]).astype(
            jnp.float32
        )  # (K, C)

    def step(carry, b):
        start = b * B
        pod_block = _slice_pods(pods, start, B)

        def skip_step(carry):
            # fully-padded trailing block (capacity tier > pod count):
            # the whole evaluate/commit body would be masked no-ops
            return carry, (
                jnp.full((B,), -1, jnp.int32),
                jnp.zeros((B,), jnp.int32),
                jnp.zeros((B,), bool),
            )

        def live_step(carry):
            carry_nodes, dsum, here, glob, excl, revw, va, vr, nvf = carry
            reps = {}
            if track_combos:
                reps.update(
                    combo_dsum=dsum, combo_here=here, combo_global=glob,
                    combo_excl=excl, rev_weight=revw,
                )
            if track_vols:
                reps.update(vol_any=va, vol_rw=vr, node_vols_fam=nvf)
            extra_b = dataclasses.replace(
                _slice_extra_rows(extra, start, B), **reps
            )
            result = evaluate(
                pod_block, carry_nodes, filter_plugins, pre_score_plugins,
                score_plugins, ctx, extra=extra_b,
                static=_slice_static(start),
            )
            choice = result.choice  # (B,)
            accept = accept_placements(
                carry_nodes, pod_block, choice, pod_block.valid,
                check_resources=check_resources, check_ports=check_ports,
                vol_state=(
                    [
                        (extra_b.pod_vols_fam[:, f], nvf[f], mx)
                        for f, mx in fam_limits
                    ]
                    if fam_limits
                    else None
                ),
                restr_state=(
                    (
                        jax.lax.dynamic_slice_in_dim(slot_vol, start, B, 0),
                        jax.lax.dynamic_slice_in_dim(slot_ro, start, B, 0),
                        extra.vol_any.shape[0],
                    )
                    if check_restr
                    else None
                ),
            )
            committed = accept & (choice >= 0)
            n_b = jnp.maximum(choice, 0)  # (B,)
            carry_nodes = apply_placements(
                carry_nodes, pod_block, jnp.where(committed, choice, -1)
            )

            if track_combos:
                # -- combo-count updates as matmul chains: each committed
                # pod's landing node defines, per topology key, a one-hot
                # domain row; (K, B, D) one-hots matmul through the
                # hoisted (K, D, N) planes into per-pod domain masks, and
                # a second matmul distributes them onto the (C, N)
                # planes.  The former per-combo einsum read the full
                # (C, D, N) one-hot (~21MB/step); this reads (K, D, N)
                # once and rides the MXU (~5MB/step at K=4).
                pmc = extra_b.pod_matches_combo & committed[:, None]  # (B, C)
                d_kb = extra.topo_domain[:, n_b]  # (K, B)
                has_kb = d_kb != D
                oh_kbd = (
                    (d_kb[..., None] == jnp.arange(D)) & has_kb[..., None]
                ).astype(jnp.float32)  # (K, B, D)
                dom_kbn = jnp.einsum(
                    "kbd,kdn->kbn", oh_kbd, onehot_f
                )  # (K, B, N) — pod j's domain mask under key k
                has = jnp.einsum("kc,kb->cb", key_oh, has_kb.astype(
                    jnp.float32)) > 0  # (C, B) — selects each combo's key
                zone_ok = has & ~uniq_c[:, None] & pmc.T  # (C, B)
                zkc = zone_ok.astype(jnp.float32)[None] * key_oh[
                    :, :, None
                ]  # (K, C, B)
                dsum = dsum + jnp.einsum(
                    "kcb,kbn->cn", zkc, dom_kbn
                ).astype(dsum.dtype)
                # hostname-like (unique) keys: the domain is the node itself
                uniq_add = (uniq_c[:, None] & has & pmc.T).astype(dsum.dtype)
                dsum = dsum.at[:, n_b].add(uniq_add)
                here = here.at[:, n_b].add(pmc.T.astype(here.dtype))
                glob = glob + jnp.sum(pmc, axis=0).astype(glob.dtype)

                def _term_chain(combo_rows, weights_z, valid):
                    # Σ over a pod's terms: weighted (C, B) membership by
                    # combo, split zone-like vs unique, then the zone part
                    # matmuls through the per-pod domain masks onto (C, N).
                    # Precision.HIGHEST: summed weights exceed 256, and the
                    # TPU default would feed them to the MXU as bf16
                    row_oh = (
                        combo_rows[..., None] == arange_c
                    )  # (B, T, C) — tiny
                    u_r = uniq_c[combo_rows]  # (B, T)
                    wz = jnp.where(valid & ~u_r, weights_z, 0).astype(
                        jnp.float32
                    )
                    m_cb = jnp.einsum(
                        "btc,bt->cb", row_oh.astype(jnp.float32), wz,
                        precision=jax.lax.Precision.HIGHEST,
                    )  # (C, B) zone-weight by combo
                    mk = m_cb[None] * key_oh[:, :, None]  # (K, C, B)
                    inc = jnp.einsum(
                        "kcb,kbn->cn", mk, dom_kbn,
                        precision=jax.lax.Precision.HIGHEST,
                    )  # (C, N)
                    return inc, (valid & u_r)

                # the committed pod's required anti-affinity terms ban
                # matchers from its landing domain
                pan_c = extra_b.pan_combo  # (B, A)
                pan_in = (
                    jnp.arange(A)[None, :] < extra_b.pan_n[:, None]
                ) & committed[:, None]
                pan_has = extra.topo_domain[keys[pan_c], n_b[:, None]] != D
                inc, vu = _term_chain(
                    pan_c, jnp.ones_like(pan_c), pan_in & pan_has
                )
                excl = excl | (inc > 0)
                excl = excl.at[
                    pan_c, jnp.broadcast_to(n_b[:, None], pan_c.shape)
                ].max(vu)

                # symmetric scoring: preferred terms (signed weight) and
                # required-affinity terms (hard weight) in one signed-add
                # increment
                rev_rows = jnp.concatenate(
                    [extra_b.ppa_combo, extra_b.pa_combo], axis=1
                )  # (B, W + PA)
                ppa_in = (
                    jnp.arange(W)[None, :] < extra_b.ppa_n[:, None]
                ) & committed[:, None]
                pa_in = (
                    jnp.arange(PA)[None, :] < extra_b.pa_n[:, None]
                ) & committed[:, None]
                rev_in = jnp.concatenate([ppa_in, pa_in], axis=1)
                rev_w = jnp.concatenate(
                    [
                        extra_b.ppa_w,
                        jnp.full(
                            (B, PA), HARD_POD_AFFINITY_WEIGHT,
                            extra_b.ppa_w.dtype,
                        ),
                    ],
                    axis=1,
                )
                rev_has = (
                    extra.topo_domain[keys[rev_rows], n_b[:, None]] != D
                )
                inc, vu = _term_chain(rev_rows, rev_w, rev_in & rev_has)
                revw = revw + inc.astype(revw.dtype)
                revw = revw.at[
                    rev_rows,
                    jnp.broadcast_to(n_b[:, None], rev_rows.shape),
                ].add(jnp.where(vu, rev_w, 0).astype(revw.dtype))

            if track_vols:
                # batched volume-plane commit (same math as the repair
                # round, over the block): disjointness guarantees no two
                # block pods share a volume, so per-pod scatters never
                # collide
                sc = jax.lax.dynamic_slice_in_dim(slot_cnt, start, B, 0)
                sv = jax.lax.dynamic_slice_in_dim(slot_vol, start, B, 0)
                sro = jax.lax.dynamic_slice_in_dim(slot_ro, start, B, 0)
                sfam = jax.lax.dynamic_slice_in_dim(slot_fam, start, B, 0)
                sdup = jax.lax.dynamic_slice_in_dim(slot_dup, start, B, 0)
                attached = va[jnp.maximum(sc, 0), n_b[:, None]]  # (B, V)
                new_slot = committed[:, None] & (sc >= 0) & ~sdup & ~attached
                for f in range(F):
                    counts_f = jnp.sum(
                        new_slot & (sfam == f), axis=1, dtype=nvf.dtype
                    )
                    nvf = nvf.at[f, n_b].add(counts_f)
                nvf = nvf.at[0, n_b].add(
                    jnp.where(committed, extra_b.pod_missing, 0)
                )
                rows = jnp.where(
                    committed[:, None] & (sc >= 0), sc, dummy_row
                )
                cols = jnp.broadcast_to(n_b[:, None], rows.shape)
                va = va.at[rows, cols].set(True)
                rw_rows = jnp.where(
                    committed[:, None] & (sv >= 0) & ~sro, sv, dummy_row
                )
                vr = vr.at[rw_rows, cols].set(True)

            carry = (carry_nodes, dsum, here, glob, excl, revw, va, vr, nvf)
            return carry, (choice, result.best_score, accept)

        return jax.lax.cond(
            jnp.any(pod_block.valid), live_step, skip_step, carry
        )

    carry0 = (
        nodes,
        extra.combo_dsum if track_combos else _z,
        extra.combo_here if track_combos else _z,
        extra.combo_global if track_combos else _z,
        extra.combo_excl if track_combos else _z,
        extra.rev_weight if track_combos else _z,
        extra.vol_any if track_vols else _z,
        extra.vol_rw if track_vols else _z,
        extra.node_vols_fam if track_vols else _z,
    )
    (nodes, *_), (choice, best, accepted) = jax.lax.scan(
        step, carry0, jnp.arange(P // B)
    )
    return (
        nodes,
        choice.reshape(P),
        best.reshape(P),
        accepted.reshape(P),
    )


def _make_packed_caller(consume, mesh: Any, name: str):
    """PackedCaller for the scan lanes: single-device by default; under
    a mesh the scan layout (node axis sharded, pods replicated — the
    scan is sequential over pods by construction, so only the node-side
    reductions parallelize).  ``name`` names the lane's programs."""
    if mesh is not None:
        from minisched_tpu.parallel.sharding import MeshPackedCaller

        return MeshPackedCaller(consume, mesh, name, scan_layout=True)
    from minisched_tpu.models.tables import PackedCaller

    return PackedCaller(consume, name)


class BlockedSequentialScheduler:
    """Compiled wrapper for ``blocked_scan_schedule`` — same calling
    surface as SequentialScheduler plus the returned ``accepted`` mask."""

    def __init__(
        self,
        filter_plugins: Sequence[Any],
        pre_score_plugins: Sequence[Any],
        score_plugins: Sequence[Any],
        weights: Optional[dict] = None,
        block_size: int = 32,
        mesh: Any = None,
    ):
        from minisched_tpu.ops.fused import validate_batch_chains

        validate_batch_chains(filter_plugins, pre_score_plugins, score_plugins)
        ctx = BatchContext(
            weights=tuple(sorted((weights or {}).items()))
        )
        self._chains = (tuple(filter_plugins), tuple(pre_score_plugins),
                        tuple(score_plugins))
        self._ctx = ctx
        self._block_size = block_size
        #: jax.sharding.Mesh — packed chunks then run with the node axis
        #: sharded (pods replicated; see sharded_scan_step's layout rule)
        self._mesh = mesh
        self._packed_caller = None
        self._fn = jax.jit(
            partial(
                blocked_scan_schedule,
                filter_plugins=self._chains[0],
                pre_score_plugins=self._chains[1],
                score_plugins=self._chains[2],
                ctx=ctx,
                block_size=block_size,
            )
        )

    def __call__(self, pods: PodTable, nodes: NodeTable, extra: Any):
        return self._fn(nodes, pods, extra=extra)

    def call_packed(
        self,
        pod_packed: Any,
        node_static: Any,
        node_agg_packed: Any,
        extra_packed: Any,
    ):
        if self._packed_caller is None:
            filters, pre_scores, scores = self._chains
            block_size = self._block_size

            def consume(pods, nodes, extra):
                return blocked_scan_schedule(
                    nodes, pods,
                    filter_plugins=filters,
                    pre_score_plugins=pre_scores,
                    score_plugins=scores,
                    ctx=self._ctx,
                    extra=extra,
                    block_size=block_size,
                )

            self._packed_caller = _make_packed_caller(
                consume, self._mesh, "scan_blocked"
            )
        return self._packed_caller(
            pod_packed, node_static, node_agg_packed, extra_packed
        )


class SequentialScheduler:
    """Compiled wrapper (the scan analog of FusedEvaluator)."""

    def __init__(
        self,
        filter_plugins: Sequence[Any],
        pre_score_plugins: Sequence[Any],
        score_plugins: Sequence[Any],
        weights: Optional[dict] = None,
        mesh: Any = None,
    ):
        from minisched_tpu.ops.fused import validate_batch_chains

        validate_batch_chains(filter_plugins, pre_score_plugins, score_plugins)
        ctx = BatchContext(
            weights=tuple(sorted((weights or {}).items()))
        )
        self._chains = (tuple(filter_plugins), tuple(pre_score_plugins),
                        tuple(score_plugins))
        self._ctx = ctx
        self._mesh = mesh
        self._packed_caller = None
        self._fn = jax.jit(
            partial(
                scan_schedule,
                filter_plugins=tuple(filter_plugins),
                pre_score_plugins=tuple(pre_score_plugins),
                score_plugins=tuple(score_plugins),
                ctx=ctx,
            )
        )

    def __call__(self, pods: PodTable, nodes: NodeTable, extra: Any = None):
        """Argument order matches FusedEvaluator (pods first); the inner
        scan keeps state-first like wave_step."""
        if extra is not None:
            return self._fn(nodes, pods, extra=extra)
        return self._fn(nodes, pods)

    def call_packed(
        self,
        pod_packed: Any,
        node_static: Any,
        node_agg_packed: Any,
        extra_packed: Any = None,
    ):
        """Single-program scan chunk: tables arrive as packed host flat
        buffers (+ device-resident static node columns) and are unpacked
        INSIDE the jitted program (models/tables.PackedCaller — same
        rationale as RepairingEvaluator.call_packed).  Under a mesh the
        chunk runs node-sharded (see _make_packed_caller)."""
        if self._packed_caller is None:
            filters, pre_scores, scores = self._chains

            def consume(pods, nodes, extra):
                return scan_schedule(
                    nodes, pods,
                    filter_plugins=filters,
                    pre_score_plugins=pre_scores,
                    score_plugins=scores,
                    ctx=self._ctx,
                    extra=extra,
                )

            self._packed_caller = _make_packed_caller(
                consume, self._mesh, "scan_exact"
            )
        return self._packed_caller(
            pod_packed, node_static, node_agg_packed, extra_packed
        )
