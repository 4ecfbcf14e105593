"""Construction-time guard for the repair loop's static/dynamic split.

``repair_wave_step(split_static=True)`` computes plugins with
``reads_committed_state = False`` once per wave.  That classification is a
hand-maintained flag whose failure mode is silent: a kernel that actually
reads committed state (the planes ``ops/state.apply_placements`` scatters
into — req_*/nzreq_*/used_port — or the volume planes the repair loop
carries) would keep serving round-1 verdicts and the wave could commit
invalid placements with no error anywhere.

This module probes the classification FUNCTIONALLY: each static-classified
plugin's batch kernels run twice on a tiny probe cluster — once as built,
once with EVERY committed-state plane perturbed.  Any output difference
means the plugin reads committed state and the constructor refuses with
the fix spelled out.  RepairingEvaluator runs this once per construction.

The probe runs on the CPU backend when the platform list has one (the
probe tables are built eagerly, op by op, and ~40 tiny programs stay out
of the accelerator's executable set), else on the default device — a
``JAX_PLATFORMS=tpu`` process must still be able to build its evaluator.
The probe programs go through the persistent compile cache like any other.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import numpy as np

#: NodeTable planes apply_placements updates intra-wave
_NODE_COMMITTED = (
    "req_cpu", "req_mem", "req_eph", "req_pods", "nzreq_cpu", "nzreq_mem",
    "used_port", "num_used_ports",
)
#: ConstraintTables planes the repair loop carries/updates across rounds
_EXTRA_COMMITTED = ("vol_any", "vol_rw", "node_vols_fam")


def _probe_device():
    """The CPU device when this process has a CPU backend, else the
    default device (see the module docstring)."""
    import jax

    try:
        return jax.devices("cpu")[0]
    except RuntimeError:  # "Unknown backend": cpu is not in JAX_PLATFORMS
        return jax.devices()[0]


def _probe_tables():
    """A tiny cluster whose committed-state perturbation flips verdicts:
    nodes near-full on every resource, a pod carrying a host port and a
    PVC — so any kernel consulting those planes must answer differently."""
    from minisched_tpu.api.objects import (
        PersistentVolume,
        PersistentVolumeClaim,
        ObjectMeta,
        PVCSpec,
        PVSpec,
        make_node,
        make_pod,
    )
    from minisched_tpu.models.constraints import build_constraint_tables
    from minisched_tpu.models.tables import build_node_table, build_pod_table

    nodes = [
        make_node(
            f"probe{i}",
            labels={"zone": f"z{i % 2}"},
            capacity={"cpu": "1", "memory": "1Gi", "pods": 2,
                      "ephemeral-storage": "1Gi"},
        )
        for i in range(4)
    ]
    pod = make_pod(
        "probe-pod",
        requests={"cpu": "600m", "memory": "600Mi",
                  "ephemeral-storage": "600Mi"},
        volumes=["probe-claim"],
    )
    pod.spec.containers[0].ports = [8080]
    pv = PersistentVolume(
        ObjectMeta(name="probe-pv", namespace=""),
        PVSpec(capacity=1 << 30, claim_ref="default/probe-claim", driver="ebs"),
    )
    pvc = PersistentVolumeClaim(
        ObjectMeta(name="probe-claim"),
        PVCSpec(request=1 << 30, volume_name="probe-pv"),
    )
    node_table, _ = build_node_table(nodes)
    pod_table, _ = build_pod_table([pod])
    extra = build_constraint_tables(
        [pod], nodes, [], pod_capacity=pod_table.capacity,
        node_capacity=node_table.capacity, pvcs=[pvc], pvs=[pv],
    )
    return pod_table, node_table, extra


def _perturb(node_table, extra):
    """Every committed-state plane, substantially changed: resources near
    the allocatable ceiling, the pod's own port claimed, every volume
    mounted read-write, family counts at the cap."""
    import jax.numpy as jnp

    half = {
        "req_cpu": node_table.alloc_cpu // 2 + 300,
        "req_mem": node_table.alloc_mem // 2 + 300,
        "req_eph": node_table.alloc_eph // 2 + 300,
        "req_pods": jnp.maximum(node_table.alloc_pods - 0, 2),
        "nzreq_cpu": node_table.alloc_cpu // 2 + 300,
        "nzreq_mem": node_table.alloc_mem // 2 + 300,
        "used_port": node_table.used_port.at[:, 0].set(8080),
        "num_used_ports": jnp.ones_like(node_table.num_used_ports),
    }
    nodes_p = dataclasses.replace(node_table, **half)
    extra_p = dataclasses.replace(
        extra,
        vol_any=jnp.ones_like(extra.vol_any),
        vol_rw=jnp.ones_like(extra.vol_rw),
        node_vols_fam=extra.node_vols_fam + 39,
    )
    return nodes_p, extra_p


def verify_static_classification(
    static_filters: Sequence[Any],
    static_scores: Sequence[Any],
    ctx: Any,
) -> None:
    """Raise TypeError naming any plugin classified round-invariant whose
    batch kernels are sensitive to committed-state planes."""
    import jax

    device = _probe_device()

    def run(pl, kind, n, e):
        needs = getattr(pl, "needs_extra", False)
        if kind == "filter":
            fn = (lambda p, nn, ee: pl.batch_filter(ctx, p, nn, ee)) if needs \
                else (lambda p, nn, ee: pl.batch_filter(ctx, p, nn))
        else:
            aux = (
                pl.batch_pre_score(ctx, pods, n)
                if callable(getattr(pl, "batch_pre_score", None))
                else {}
            )
            fn = (lambda p, nn, ee: pl.batch_score(ctx, p, nn, aux, ee)) if needs \
                else (lambda p, nn, ee: pl.batch_score(ctx, p, nn, aux))
        return np.asarray(jax.jit(fn)(pods, n, e))

    # ONE device scope for the builds, the perturbation and the probes: the
    # probe tables are uncommitted, so an eager op outside it would run on
    # the default device (the accelerator) instead
    with jax.default_device(device):
        pods, nodes, extra = _probe_tables()
        nodes_p, extra_p = _perturb(nodes, extra)
        for kind, chain in (
            ("filter", static_filters), ("score", static_scores)
        ):
            for pl in chain:
                base = run(pl, kind, nodes, extra)
                pert = run(pl, kind, nodes_p, extra_p)
                if not np.array_equal(base, pert):
                    raise TypeError(
                        f"plugin {pl.name()}: batch_{kind} output changes "
                        "when committed-state planes change, but the plugin "
                        "is classified round-invariant "
                        "(reads_committed_state is False).  Set "
                        "`reads_committed_state = True` on the plugin class "
                        "so the repair loop re-evaluates it every round."
                    )
