"""Production meshes (assignment spec) + the fleet-health mesh view.

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state.  Shapes: single pod = (16, 16) ("data","model");
multi-pod = (2, 16, 16) ("pod","data","model") — 2 pods x 256 chips.

``FleetMeshView`` is the fleet layer's device view: a ``FleetPlan``'s
explicit health mask (serving / quarantined / idle-spare) applied to the
process's physical devices, from which health-masked submeshes are built —
the mesh only ever contains devices that are actually taking traffic.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Sequence, Tuple

import jax


def _mesh(shape: Tuple[int, ...], axes: Tuple[str, ...], devices=None):
    n = math.prod(shape)
    devices = list(jax.devices() if devices is None else devices)
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)}: short "
            f"{n - len(devices)} device(s) — the "
            "dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count"
            " before importing jax (see launch/dryrun.py)")
    return jax.make_mesh(shape, axes, devices=devices[:n],
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """Arbitrary mesh (tests use small ones, e.g. (2, 4))."""
    return _mesh(tuple(shape), tuple(axes))


# ------------------------------------------------------- fleet health view
@dataclass(frozen=True)
class FleetMeshView:
    """A fleet's health state projected onto this process's devices.

    ``mask[i]`` is True iff logical device ``i`` is serving traffic;
    quarantined devices and idle spares are carried explicitly (never
    silently dropped), so schedulers can reason about capacity and
    recovery, and ``submesh`` only ever builds meshes over serving
    hardware.
    """

    mask: Tuple[bool, ...]
    quarantined: Tuple[int, ...] = ()
    idle_spares: Tuple[int, ...] = ()

    @staticmethod
    def from_plan(fleet_plan) -> "FleetMeshView":
        """Project a FleetPlan's device table onto the mesh layer."""
        return FleetMeshView(
            mask=tuple(fleet_plan.device_mask()),
            quarantined=tuple(fleet_plan.quarantined),
            idle_spares=tuple(fleet_plan.pool.free()))

    @property
    def n_devices(self) -> int:
        return len(self.mask)

    def serving(self) -> Tuple[int, ...]:
        return tuple(i for i, ok in enumerate(self.mask) if ok)

    def serving_devices(self, devices=None) -> List[jax.Device]:
        """The physical devices behind the serving logical indices; the
        view must fit the device list (loud error otherwise).

        ``devices`` defaults to ``jax.devices()`` — under an initialized
        ``jax.distributed`` runtime that is the *global* device list, so
        logical fleet index i maps to global device i across hosts (the
        per-host slice lives on ``launch.distributed.HostView``)."""
        devices = list(jax.devices() if devices is None else devices)
        if self.n_devices > len(devices):
            raise RuntimeError(
                f"fleet view covers {self.n_devices} devices, process has "
                f"{len(devices)}: short {self.n_devices - len(devices)} "
                "device(s)")
        return [devices[i] for i in self.serving()]

    def submesh(self, axes: Sequence[str] = ("data",), *,
                model: int = 1, devices=None):
        """Health-masked mesh over the serving devices only.

        1-D by default (pure data parallel); ``model > 1`` folds the
        serving devices into a (data, model) grid — serving count must be
        divisible, and the error names the shortfall."""
        devs = self.serving_devices(devices)
        n = len(devs)
        if model > 1:
            if n % model:
                raise RuntimeError(
                    f"{n} serving device(s) do not fold into model={model} "
                    f"groups: short {model - n % model} device(s) (or "
                    f"quarantine {n % model} more)")
            return _mesh((n // model, model), tuple(axes), devices=devs)
        return _mesh((n,), tuple(axes), devices=devs)


# ------------------------------------------------------ per-chip peaks
@dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float      # FLOP/s per chip
    hbm_bw: float          # bytes/s per chip
    ici_link_bw: float     # bytes/s per ICI link


# Published peaks keyed by ``jax.Device.device_kind`` (a TPU v5e reports
# "TPU v5 lite").  Source: Google Cloud TPU documentation, "TPU v5e":
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip
# (four links of 50 GB/s).
CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, hbm_bw=819e9,
                             ici_link_bw=50e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The peaks of one chip kind; an unknown kind is an error, never a
    default (a roofline against another chip's peaks is a wrong number)."""
    if device_kind not in CHIP_PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(CHIP_PEAKS)}")
    return CHIP_PEAKS[device_kind]
