"""Device management.

Reference parity: ``paddle/fluid/platform/place.h`` (CPUPlace/CUDAPlace/...)
and ``python/paddle/device.py`` (set_device/get_device).  On TPU there is a
single logical device kind per process; `set_device("tpu")`/"cpu" selects the
jax backend used for new tensors.  Multi-chip execution is expressed through
``paddle_tpu.distributed`` meshes, not through per-op device placement.
"""
from __future__ import annotations

import jax

_current_device = None  # lazily resolved


class Place:
    """Device identity (reference: platform/place.h:26-103)."""

    def __init__(self, kind: str, index: int = 0):
        self.kind = kind
        self.index = index

    def __repr__(self):
        return f"Place({self.kind}:{self.index})"

    def __eq__(self, other):
        return (isinstance(other, Place) and self.kind == other.kind
                and self.index == other.index)

    def is_tpu_place(self):
        return self.kind == "tpu"

    def is_cpu_place(self):
        return self.kind == "cpu"


def CPUPlace():
    return Place("cpu", 0)


def TPUPlace(idx: int = 0):
    return Place("tpu", idx)


def _default_kind() -> str:
    # a backend that fails to initialize (a chip held by another
    # process, a missing driver) raises here: it must never read as a
    # quiet CPU run
    return "cpu" if jax.default_backend() == "cpu" else "tpu"


def set_device(device: str):
    """paddle.set_device — 'tpu', 'tpu:0', 'cpu'."""
    global _current_device
    kind, _, idx = device.partition(":")
    kind = {"gpu": "tpu", "xpu": "tpu", "tpu": "tpu", "cpu": "cpu"}.get(kind)
    if kind is None:
        raise ValueError("unknown device %r (use 'tpu' or 'cpu')" % device)
    _current_device = Place(kind, int(idx) if idx else 0)
    return _current_device


def get_device() -> str:
    p = current_place()
    return f"{p.kind}:{p.index}"


def current_place() -> Place:
    global _current_device
    if _current_device is None:
        _current_device = Place(_default_kind(), 0)
    return _current_device


def jax_device(place: Place | None = None):
    """Resolve a Place to a concrete jax device object."""
    place = place or current_place()
    if place.kind == "cpu":
        devs = jax.devices("cpu")
    else:
        devs = jax.devices()
    if not 0 <= place.index < len(devs):
        raise ValueError(
            f"{place.kind}:{place.index} does not exist: this host has "
            f"{len(devs)} {devs[0].platform} device(s)")
    return devs[place.index]


def device_count() -> int:
    return len(jax.devices())


def is_compiled_with_cuda() -> bool:  # API-compat shim
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True
