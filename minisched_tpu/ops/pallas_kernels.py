"""Pallas TPU kernels for the scheduling hot path.

``select_hosts`` (ops/fused.py) is the reduction tail of every fused
evaluation: masked max over nodes, tie-candidate mask, per-candidate
mix32 hash, hash argmin — ~5 XLA passes over the (P, N) matrices.  The
Pallas kernel here does it in ONE pass: tiles of the score/mask matrices
stream HBM→VMEM once, and per-pod running (best score, best hash, best
index) accumulators merge lexicographically across node tiles in VMEM
scratch.  Bit-exact with ``fused.select_hosts`` (tested), including the
hash-collision and no-feasible-node edge cases.

Enable with ``MINISCHED_TPU_PALLAS=1`` (the benchmark does) or
``fused.set_pallas(True)``; off CPU the kernel runs in interpreter mode
(tests), on TPU it compiles to Mosaic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# plain Python ints: a module-level jnp scalar would be a captured constant
# inside the pallas kernel, which pallas_call rejects
UINT32_MAX = 0xFFFFFFFF
NEG_INF_SCORE = int(jnp.iinfo(jnp.int32).min)
IDX_INF = 0x7FFFFFFF

POD_TILE = 128  # sublane dim of one grid step
NODE_TILE = 2048  # lane dim of one grid step (multiple of 128)


def _tiling(P: int, N: int):
    """(pod_tile, node_tile, grid) with loud validation — a non-divisible
    shape would silently truncate the grid and return garbage."""
    pod_tile = POD_TILE if P % POD_TILE == 0 else 8
    node_tile = NODE_TILE if N % NODE_TILE == 0 else 128
    if P % pod_tile or N % node_tile:
        raise ValueError(
            f"pallas select_hosts needs P % {pod_tile} == 0 and "
            f"N % {node_tile} == 0; got P={P}, N={N} "
            "(pad tables with models.tables.pad_to)"
        )
    return pod_tile, node_tile, (P // pod_tile, N // node_tile)


def _mix32(seed, idx):
    """== fused.mix32 (same modular uint32 ops)."""
    x = seed ^ (idx * jnp.uint32(0x9E3779B9))
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def _reduce_and_merge(
    masked, mask, seeds, choice_ref, best_ref, acc_score, acc_hash, acc_idx,
    node_tile: int,
):
    """Shared reduction tail of both kernels: per-tile lexicographic winner
    (score desc, hash asc, idx asc) merged into the VMEM accumulators,
    with init on the first node tile and the final write on the last."""
    nj = pl.program_id(1)
    n_tiles = pl.num_programs(1)

    @pl.when(nj == 0)
    def _init():
        acc_score[:] = jnp.full_like(acc_score, NEG_INF_SCORE)
        acc_hash[:] = jnp.full_like(acc_hash, IDX_INF)
        acc_idx[:] = jnp.full_like(acc_idx, IDX_INF)

    base = nj * node_tile
    gidx = base + jax.lax.broadcasted_iota(jnp.int32, masked.shape, 1)
    h = _mix32(seeds, gidx.astype(jnp.uint32))  # (TP, TN) uint32
    # Mosaic has no uint32 reductions: bitcast + sign-bit flip is an
    # order-isomorphic map onto int32 (uint32 0xFFFFFFFF ↦ int32 max)
    h_i = jax.lax.bitcast_convert_type(h, jnp.int32) ^ jnp.int32(-(1 << 31))

    # tile-local winner per pod row; hkey only competes among max-score
    # candidates
    tile_best = jnp.max(masked, axis=1, keepdims=True)  # (TP, 1)
    cand = mask & (masked == tile_best)
    hkey = jnp.where(cand, h_i, IDX_INF)
    tile_minh = jnp.min(hkey, axis=1, keepdims=True)
    # lowest index among positions at (cand & min hash); if no cand (all
    # infeasible), tile_best = NEG_INF and the merge below discards it
    at_min = cand & (hkey == tile_minh)
    idx_key = jnp.where(at_min, gidx, IDX_INF)
    tile_idx = jnp.min(idx_key, axis=1, keepdims=True)

    better = (tile_best > acc_score[:]) | (
        (tile_best == acc_score[:])
        & (
            (tile_minh < acc_hash[:])
            | ((tile_minh == acc_hash[:]) & (tile_idx < acc_idx[:]))
        )
    )
    acc_score[:] = jnp.where(better, tile_best, acc_score[:])
    acc_hash[:] = jnp.where(better, tile_minh, acc_hash[:])
    acc_idx[:] = jnp.where(better, tile_idx, acc_idx[:])

    @pl.when(nj == n_tiles - 1)
    def _finish():
        feasible = acc_score[:] > NEG_INF_SCORE
        choice_ref[:] = jnp.where(feasible, acc_idx[:], -1)
        best_ref[:] = jnp.where(feasible, acc_score[:], 0)


def _select_kernel(
    scores_ref,
    mask_ref,
    seeds_ref,
    choice_ref,
    best_ref,
    acc_score,
    acc_hash,
    acc_idx,
    *,
    node_tile: int,
):
    """Grid (pods/pod_tile, nodes/node_tile); node axis is the reduction."""
    scores = scores_ref[:]  # (TP, TN) i32
    mask = mask_ref[:]  # (TP, TN) bool
    masked = jnp.where(mask, scores, NEG_INF_SCORE)
    _reduce_and_merge(
        masked, mask, seeds_ref[:], choice_ref, best_ref,
        acc_score, acc_hash, acc_idx, node_tile,
    )


def _nn_fused_kernel(
    unsched_ref,
    nsuffix_ref,
    nvalid_ref,
    tol_ref,
    psuffix_ref,
    seeds_ref,
    pvalid_ref,
    choice_ref,
    best_ref,
    acc_score,
    acc_hash,
    acc_idx,
    *,
    node_tile: int,
    match_score: int,
):
    """Fully-fused flagship chain (NodeUnschedulable filter + NodeNumber
    score + seeded argmax): inputs are table COLUMNS only — the (P, N)
    mask/score matrices exist solely in VMEM registers, never in HBM."""
    unsched = unsched_ref[:]  # (1, TN) bool
    nsuffix = nsuffix_ref[:]  # (1, TN) i32
    nvalid = nvalid_ref[:]  # (1, TN) bool
    tol = tol_ref[:]  # (TP, 1) bool
    psuffix = psuffix_ref[:]  # (TP, 1) i32
    pvalid = pvalid_ref[:]  # (TP, 1) bool

    mask = (pvalid & nvalid) & (~unsched | tol)  # (TP, TN)
    match = (psuffix == nsuffix) & (psuffix >= 0) & (nsuffix >= 0)
    scores = jnp.where(match, match_score, 0)
    masked = jnp.where(mask, scores, NEG_INF_SCORE)
    _reduce_and_merge(
        masked, mask, seeds_ref[:], choice_ref, best_ref,
        acc_score, acc_hash, acc_idx, node_tile,
    )


@functools.partial(jax.jit, static_argnames=("interpret", "match_score"))
def nodenumber_select_hosts(
    pods, nodes, match_score: int = 10, interpret: bool = False
):
    """(choice, best_score) for the flagship NodeUnschedulable+NodeNumber
    chain, fully fused — bit-exact with FusedEvaluator on that chain, but
    with only O(P + N) HBM traffic per wave."""
    from minisched_tpu.plugins.nodeunschedulable import tolerates_unschedulable

    P = pods.valid.shape[0]
    N = nodes.valid.shape[0]
    pod_tile, node_tile, grid = _tiling(P, N)
    tol = tolerates_unschedulable(pods)  # (P,) — tiny XLA prologue

    node_spec = pl.BlockSpec((1, node_tile), lambda i, j: (0, j), memory_space=pltpu.VMEM)
    pod_spec = pl.BlockSpec((pod_tile, 1), lambda i, j: (i, 0), memory_space=pltpu.VMEM)
    kernel = functools.partial(
        _nn_fused_kernel, node_tile=node_tile, match_score=match_score
    )
    choice, best = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[node_spec, node_spec, node_spec, pod_spec, pod_spec, pod_spec,
                  pod_spec],
        out_specs=[pod_spec, pod_spec],
        out_shape=[
            jax.ShapeDtypeStruct((P, 1), jnp.int32),
            jax.ShapeDtypeStruct((P, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((pod_tile, 1), jnp.int32),
            pltpu.VMEM((pod_tile, 1), jnp.int32),
            pltpu.VMEM((pod_tile, 1), jnp.int32),
        ],
        interpret=interpret,
        name="nodenumber_select_hosts",
    )(
        nodes.unschedulable[None, :],
        nodes.suffix[None, :],
        nodes.valid[None, :],
        tol[:, None],
        pods.suffix[:, None],
        pods.seed[:, None],
        pods.valid[:, None],
    )
    return choice[:, 0], best[:, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def select_hosts_pallas(scores, mask, seeds, interpret: bool = False):
    """One-pass (choice, best_score) — drop-in for fused.select_hosts.

    scores i32[P, N]; mask bool[P, N]; seeds u32[P].  P and N must be
    multiples of the tile sizes (tables.pad_to guarantees 128; POD_TILE=8
    divides 128).
    """
    P, N = scores.shape
    pod_tile, node_tile, grid = _tiling(P, N)

    kernel = functools.partial(_select_kernel, node_tile=node_tile)
    choice, best = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (pod_tile, node_tile), lambda i, j: (i, j), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (pod_tile, node_tile), lambda i, j: (i, j), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec((pod_tile, 1), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((pod_tile, 1), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((pod_tile, 1), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((P, 1), jnp.int32),
            jax.ShapeDtypeStruct((P, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((pod_tile, 1), jnp.int32),
            pltpu.VMEM((pod_tile, 1), jnp.int32),  # hash in biased-int32 order
            pltpu.VMEM((pod_tile, 1), jnp.int32),
        ],
        interpret=interpret,
        name="select_hosts",  # the kernel's name in a device trace
    )(scores, mask, seeds[:, None])
    return choice[:, 0], best[:, 0]
