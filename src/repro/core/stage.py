"""Stage: the Oobleck sub-accelerator abstraction (paper §III-A).

A Stage wraps one step of ``f = f_n ∘ … ∘ f_1`` with the two interfaces the
paper prescribes:
  * the *fast path* (``hw``): the optimized lowering — a Pallas kernel or a
    fused XLA computation;
  * the *software-visible path* (``sw``): the jnp oracle — logically
    equivalent (a Viscosity contract), runnable anywhere.

``ports`` are the latency-insensitive interface (activation specs); the
runtime uses them for canary generation and checkpoint hand-off.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.viscosity.lang import HW, INTERPRET, SW, OpSpec


@dataclass
class Stage:
    name: str
    spec: Optional[OpSpec] = None            # viscosity op (preferred)
    hw: Optional[Callable] = None            # explicit pair (case studies)
    sw: Optional[Callable] = None
    ports: Tuple[jax.ShapeDtypeStruct, ...] = ()
    tol: float = 2e-2

    def __post_init__(self):
        if self.spec is not None:
            self.hw = self.hw or (lambda *a, **k: self.spec(*a, route=HW, **k))
            self.sw = self.sw or (lambda *a, **k: self.spec(*a, route=SW, **k))
        assert self.sw is not None, f"stage {self.name} needs a software path"
        if self.hw is None:
            self.hw = self.sw   # pure-sw stage (no optimized lowering)

    def run(self, *args, route=HW, **kw):
        """Run one stage under a route: a target string or a RoutingPlan
        (the stage resolves its own entry — the single lookup point that
        replaced the per-layer string shims)."""
        if hasattr(route, "target_for"):
            route = route.target_for(self.name)
        if route == INTERPRET and self.spec is not None:
            return self.spec(*args, route=INTERPRET, **kw)
        fn = self.hw if route == HW else self.sw
        return fn(*args, **kw)

    def canary_inputs(self, seed: int = 0):
        """Deterministic inputs drawn from the port specs, mapped into the
        op's operating domain when its spec declares one."""
        key = jax.random.PRNGKey(seed)
        outs = []
        for i, sds in enumerate(self.ports):
            k = jax.random.fold_in(key, i)
            if jnp.issubdtype(sds.dtype, jnp.floating):
                outs.append(jax.random.normal(k, sds.shape, sds.dtype))
            else:
                outs.append(jax.random.randint(k, sds.shape, 0, 128
                                               ).astype(sds.dtype))
        if self.spec is not None and self.spec.canary_domain is not None:
            return tuple(self.spec.canary_domain(*outs))
        return tuple(outs)
