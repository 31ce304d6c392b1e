"""Device abstraction: Place over JAX devices.

Capability parity with the reference's ``platform::Place`` variant
(reference: paddle/fluid/platform/place.h:26,37,52,81) and
``DeviceContextPool`` (reference: platform/device_context.h:408).

On TPU there are no user-managed streams or handles — PJRT owns them — so a
Place is a thin, hashable handle resolving to a ``jax.Device``. The pool
analog is :func:`device_pool`, a cached view of all local devices.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional

import jax

from .enforce import enforce, not_found


@dataclasses.dataclass(frozen=True)
class Place:
    """A logical device handle: ``kind`` in {"cpu", "tpu"} plus ordinal."""

    kind: str
    ordinal: int = 0

    def device(self) -> jax.Device:
        devs = _devices_of_kind(self.kind)
        if self.ordinal >= len(devs):
            not_found(f"no {self.kind} device with ordinal {self.ordinal} "
                      f"(found {len(devs)})")
        return devs[self.ordinal]

    def __repr__(self) -> str:  # mirrors Place printing, e.g. TPUPlace(0)
        return f"{self.kind.upper()}Place({self.ordinal})"


def CPUPlace(ordinal: int = 0) -> Place:
    return Place("cpu", ordinal)


def TPUPlace(ordinal: int = 0) -> Place:
    return Place("tpu", ordinal)


@functools.lru_cache(maxsize=None)
def _devices_of_kind(kind: str) -> tuple:
    if kind == "cpu":
        try:
            return tuple(jax.devices("cpu"))
        except RuntimeError:
            return tuple()
    # "tpu": the accelerator devices. With none attached every CPU device
    # plays TPU — kept ONLY for the CPU test suite, whose 8 virtual
    # devices stand in for chips behind TPUPlace(i)/cuda_places();
    # chip_smoke.py and the measurement paths never resolve devices
    # through places (they read jax.devices() and fail off-TPU).
    devs = [d for d in jax.devices() if d.platform != "cpu"]
    if not devs:
        devs = list(jax.devices())
    return tuple(devs)


def device_pool(kind: Optional[str] = None) -> List[Place]:
    """All local places of ``kind`` (default: accelerator if present else cpu).

    DeviceContextPool analog (reference: platform/device_context.h:408).
    """
    if kind is None:
        kind = "tpu" if is_compiled_with_tpu() else "cpu"
    return [Place(kind, i) for i in range(len(_devices_of_kind(kind)))]


def is_compiled_with_tpu() -> bool:
    """True when a non-CPU accelerator backend is live (CUDA-availability analog,
    reference: pybind.cc is_compiled_with_cuda)."""
    return any(d.platform != "cpu" for d in jax.devices())


def default_place() -> Place:
    return TPUPlace(0) if is_compiled_with_tpu() else CPUPlace(0)


def device_count(kind: Optional[str] = None) -> int:
    return len(device_pool(kind))


def set_device(place: Place):
    """Make ``place`` the default for uncommitted arrays (InitDevices-adjacent,
    reference: platform/init.h:29)."""
    enforce(place.device() is not None, "invalid place %s", place)
    jax.config.update("jax_default_device", place.device())
    return place
