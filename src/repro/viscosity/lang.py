"""Viscosity: single-description, dual-lowering op layer (paper §III-B).

The paper's Viscosity ADL lowers one description of each sub-accelerator to
both Verilog (hardware) and C (software fallback), guaranteeing logical
equivalence.  The TPU-native equivalent implemented here:

  * the **software** lowering is the pure-jnp reference (``ref``) — compiled
    by XLA, runs on any backend, including quarantined/degraded devices;
  * the **hardware** lowering is the Pallas TPU kernel (``kernel``) —
    hand-tiled for VMEM/MXU (``target='pallas'``), with ``'interpret'``
    executing the same kernel body in Python for CPU validation;
  * equivalence between the two lowerings is a *contract* (`tol`), enforced
    by property tests and checked online by the fault detector's canaries.

An OpSpec also carries the paper's valid/ready notion: ``valid(out)`` is a
cheap predicate over outputs (e.g. "finite") used by detectors.

Routing is static per compilation: a ``route`` selects the lowering at
trace time, exactly mirroring the paper's per-sub-accelerator queue
(re)configuration — changing a route is a reconfiguration (recompile),
not a redesign.  A route is one of
  * a target string (HW / SW / INTERPRET),
  * a ``core.routing.RoutingPlan`` (the unified routing IR) — the op looks
    up its own stage entry (duck-typed via ``target_for`` so this module
    stays dependency-free), or
  * a ``core.routing.ResidentRoute`` handle (duck-typed via ``select``) —
    the hot-spare lowering: both paths resident behind ``lax.cond``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax

# Routes (per-stage state in a FaultSignature).
HW = "hw"              # optimized path (Pallas kernel on TPU; fused XLA here)
SW = "sw"              # software fallback: the jnp oracle
INTERPRET = "interpret"  # kernel body, interpreter mode (CPU validation)

# The DEGRADED route family (partial degradation, paper §III-A; permanent-
# fault remapping a la arxiv 1802.04657): intermediate rungs between the
# optimized path and the full SW oracle, available once detection has
# localized a lane map for the stage (``viscosity.lanefault``).
DEGRADED_REMAP = "degraded_remap"      # HW full width; oracle heals dead lanes
DEGRADED_REDUCED = "degraded_reduced"  # kernel shrunk to surviving lanes
DEGRADED_TARGETS = (DEGRADED_REMAP, DEGRADED_REDUCED)


@dataclass(frozen=True)
class OpSpec:
    """One op described once; lowered to hardware and software paths."""
    name: str
    ref: Callable[..., Any]                       # the single source of truth
    kernel: Optional[Callable[..., Any]] = None   # pallas path (same signature)
    interpret: Optional[Callable[..., Any]] = None
    valid: Optional[Callable[[Any], Any]] = None  # validity predicate on outputs
    tol: float = 2e-2                             # hw-vs-sw allclose contract (bf16)
    flops: Optional[Callable[..., int]] = None    # analytic flop model (roofline)
    # Reduced-width support (DEGRADED_REDUCED): (args, kw, keep_lanes) ->
    # (args, kw) with the lane-axis operands sliced to the surviving lanes;
    # the kernel then derives its output width from the sliced operand.
    lane_slicer: Optional[Callable[..., Any]] = None
    # Canary inputs: (*standard-normal draws) -> (*args in the op's
    # operating domain), for ops whose math is only stable there (the SSD
    # scan needs dt > 0 and A < 0, as the model feeds it).
    canary_domain: Optional[Callable[..., Any]] = None

    def lower(self, target) -> Callable[..., Any]:
        if hasattr(target, "target_for"):   # RoutingPlan: my stage's entry
            target = target.target_for(self.name)
        if hasattr(target, "select"):       # ResidentRoute: runtime cond
            return target.select(self)
        if target == SW or self.kernel is None:
            return self.ref
        if target == HW:
            return self.kernel
        if target == INTERPRET:
            return self.interpret or self.kernel
        if target in DEGRADED_TARGETS:      # lane-mapped partial degradation
            from repro.viscosity import lanefault
            return lanefault.lower_degraded(self, target)
        raise ValueError(f"unknown lowering target {target!r} for op {self.name}")

    def __call__(self, *args, route=SW, **kw):
        return self.lower(route)(*args, **kw)


class Registry:
    def __init__(self):
        self._ops: Dict[str, OpSpec] = {}

    def register(self, spec: OpSpec) -> OpSpec:
        if spec.name in self._ops:
            raise ValueError(f"duplicate viscosity op {spec.name!r}")
        self._ops[spec.name] = spec
        return spec

    def get(self, name: str) -> OpSpec:
        return self._ops[name]

    def names(self):
        return sorted(self._ops)

    def __contains__(self, name):
        return name in self._ops


REGISTRY = Registry()


def defop(name: str, *, ref, kernel=None, interpret=None, valid=None,
          tol: float = 2e-2, flops=None, lane_slicer=None,
          canary_domain=None) -> OpSpec:
    """Declare an op once; both lowerings become available framework-wide."""
    return REGISTRY.register(OpSpec(name=name, ref=ref, kernel=kernel,
                                    interpret=interpret, valid=valid,
                                    tol=tol, flops=flops,
                                    lane_slicer=lane_slicer,
                                    canary_domain=canary_domain))


def finite_valid(out) -> jax.Array:
    """Default validity predicate: every leaf is finite (paper's `valid`)."""
    import jax.numpy as jnp
    leaves = jax.tree_util.tree_leaves(out)
    ok = jnp.array(True)
    for leaf in leaves:
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating):
            ok = ok & jnp.all(jnp.isfinite(leaf))
    return ok
