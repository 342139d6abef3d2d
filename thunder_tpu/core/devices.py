"""Devices for the trace IR.

Reference parity: thunder/core/devices.py (`Device:84`, `DeviceType:14`). The
reference knows CPU/CUDA; this build is TPU-first: device types are CPU and
TPU, and a ``Device`` resolves to a concrete ``jax.Device``. Multi-device
placement is expressed through shardings (see thunder_tpu/parallel), not
through per-tensor device indices, so ``index`` mostly matters for CPU test
meshes.
"""

from __future__ import annotations

import enum
from typing import Any, NamedTuple, Optional


class DeviceType(enum.Enum):
    CPU = enum.auto()
    TPU = enum.auto()
    # Recognized for frontend compatibility when importing CUDA-targeted
    # programs; mapped to the accelerator (TPU) at trace time.
    CUDA = enum.auto()


_devicetype_names = {DeviceType.CPU: "cpu", DeviceType.TPU: "tpu", DeviceType.CUDA: "cuda"}
_name_to_devicetype = {v: k for k, v in _devicetype_names.items()}


def devicetype_string(dt: DeviceType) -> str:
    return _devicetype_names[dt]


class Device:
    def __init__(self, string_or_type: Any = None, index: Optional[int] = None):
        if string_or_type is None:
            string_or_type = default_accelerator_type()
        if isinstance(string_or_type, Device):
            self.devicetype = string_or_type.devicetype
            self.index = string_or_type.index if index is None else index
            return
        if isinstance(string_or_type, DeviceType):
            self.devicetype = string_or_type
            self.index = 0 if index is None else index
            return
        if isinstance(string_or_type, str):
            name, _, idx = string_or_type.partition(":")
            devicetype = _name_to_devicetype.get(name)
            if devicetype is None:
                raise ValueError(f"Unknown device string {string_or_type!r}")
            self.devicetype = devicetype
            self.index = int(idx) if idx else (0 if index is None else index)
            return
        raise ValueError(f"Cannot construct Device from {string_or_type!r}")

    @property
    def type(self) -> str:
        return devicetype_string(self.devicetype)

    def __repr__(self) -> str:
        return f'devices.Device("{self.type}:{self.index}")'

    def __str__(self) -> str:
        return f"{self.type}:{self.index}"

    def __hash__(self) -> int:
        return hash((self.devicetype, self.index))

    def __eq__(self, other: Any) -> bool:
        if not isinstance(other, Device):
            return NotImplemented
        return self.devicetype == other.devicetype and self.index == other.index

    # -- jax resolution ------------------------------------------------------

    def jax_device(self):
        """Resolve to a concrete jax.Device (canonicalizing CUDA→accelerator)."""
        import jax

        if self.devicetype == DeviceType.CPU:
            return jax.devices("cpu")[self.index]
        devs = jax.devices()
        return devs[self.index % len(devs)]


def default_accelerator_type() -> DeviceType:
    import jax

    try:
        plat = jax.default_backend()
    except Exception:
        plat = "cpu"
    return DeviceType.CPU if plat == "cpu" else DeviceType.TPU


def to_device(x: Any) -> Optional[Device]:
    if x is None:
        return None
    if isinstance(x, Device):
        return x
    if isinstance(x, (str, DeviceType)):
        return Device(x)
    # torch.device / jax.Device duck-typing
    plat = getattr(x, "platform", None)
    if plat is not None:  # jax.Device
        name = "cpu" if plat == "cpu" else "tpu"
        return Device(name, getattr(x, "id", 0))
    typ = getattr(x, "type", None)
    if typ is not None:  # torch.device
        return Device(typ, getattr(x, "index", None) or 0)
    raise ValueError(f"Cannot convert {x!r} to a Device")


cpu = Device("cpu")


# -- what the chip has -------------------------------------------------------


class TpuSpec(NamedTuple):
    peak_bf16_tflops: float  # per chip
    vmem_bytes: int  # per core


# The one table of the program, a row per TPU generation. Peaks: Google Cloud
# TPU documentation, the "TPU v4", "TPU v5e", "TPU v5p" and "TPU v6e"
# system-architecture pages. VMEM: what jax's pltpu.get_tpu_info reports (for
# the default device alone). perfbench/peaks.json is the benchmark's own table;
# tests/test_chip_smoke.py holds the two equal.
TPU_SPECS = {
    "v4": TpuSpec(275.0, 16 * 1024 * 1024),
    "v5e": TpuSpec(197.0, 128 * 1024 * 1024),
    "v5p": TpuSpec(459.0, 64 * 1024 * 1024),
    "v6e": TpuSpec(918.0, 128 * 1024 * 1024),
}

# jax's ``device_kind`` (lower-cased, spaces dropped) -> generation; the
# first match wins, so "v5lite" is tested before "v5".
_DEVICE_KINDS = (("v5lite", "v5e"), ("v5e", "v5e"), ("v6lite", "v6e"), ("v6e", "v6e"),
                 ("v5p", "v5p"), ("v5", "v5p"), ("v4", "v4"))


def tpu_generation(device_kind: Optional[str] = None) -> str:
    """Generation name ("v5e", ...) of ``device_kind`` (default: the first
    device jax reports). A device that is not in the table raises: a peak
    that is guessed makes every utilization wrong."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    kind = device_kind.lower().replace(" ", "")
    if "tpu" in kind:
        for needle, gen in _DEVICE_KINDS:
            if needle in kind:
                return gen
    raise ValueError(
        f"no peak is recorded for device_kind {device_kind!r}; known TPU generations: "
        f"{sorted(TPU_SPECS)} (add the chip to thunder_tpu/core/devices.py)"
    )


def peak_tflops(device_kind: Optional[str] = None) -> float:
    return TPU_SPECS[tpu_generation(device_kind)].peak_bf16_tflops


def device_description() -> dict:
    """The device as jax reports it — what every result line names."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
