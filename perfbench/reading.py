"""What one run knows, handed to every per-layer reader, and the breakdown of
the traced window. A reader is ``perfbench/layer_metrics/<metric>.py`` with one
function ``read(reading)``; it returns the number, or ``None`` when what it
reads is not there, and the harness then leaves the metric out of the line."""

from __future__ import annotations

import dataclasses
import importlib
import re
import statistics

from perfbench import kernel_families, xplane


@dataclasses.dataclass
class Reading:
    cell: object  # manifest.Cell
    spans: dict  # host-clock seconds the harness took around the program's layers
    counters: dict  # counts: the program's and jax.monitoring's
    window: object  # window.WindowResult of the untraced window
    tokens_per_s: float | None
    flops_per_token: float
    peaks: dict | None  # perfbench/peaks.json for this device_kind; None off the TPU
    trace: xplane.Trace | None = None
    traced_units: int = 0

    def per_unit_ms(self, seconds_of_device) -> float | None:
        """Mean over the traced devices of ``seconds_of_device(d)``, per unit of
        work (step or call), in ms."""
        if self.trace is None or not self.trace.devices or not self.traced_units:
            return None
        per_device = [seconds_of_device(d) for d in self.trace.devices]
        return 1e3 * statistics.fmean(per_device) / self.traced_units


def read_metric(name: str, reading: Reading):
    return importlib.import_module(f"perfbench.layer_metrics.{name}").read(reading)


# -----------------------------------------------------------------------------
# Breakdown
# -----------------------------------------------------------------------------

_NUMBERED = re.compile(r"[.\d]+$")
LABEL_LIMIT = 96


def _short_shape(shape: str) -> str:
    """``(bf16[8,4],bf16[8,4],u32[],u32[])`` -> ``(bf16[8,4]x2,u32[]x2)``."""
    if not shape.startswith("("):
        return shape
    parts = re.findall(r"[a-z]\w*\[[\d,]*\]", shape)
    runs: list[list] = []
    for part in parts:
        if runs and runs[-1][0] == part:
            runs[-1][1] += 1
        else:
            runs.append([part, 1])
    return "(" + ",".join(p if n == 1 else f"{p}x{n}" for p, n in runs) + ")"


def operation_label(event_name: str) -> str:
    """A name that stays the same from layer to layer and run to run: the
    kernel family; a collective's opcode (``async-collective-start`` and
    ``-done``, the issue and the wait, for the v5e's custom fusions); for the
    rest the instruction's name without its number, with its result shape."""
    if xplane.is_mosaic_kernel(event_name):
        family = kernel_families.match(event_name)
        return family[0] if family else "kernel " + _NUMBERED.sub("", xplane.instruction(event_name)[0])
    name, op, shape = xplane.instruction(event_name)
    if name.startswith((xplane.ASYNC_START, xplane.ASYNC_DONE)):
        return _NUMBERED.sub("", name)
    if xplane.is_collective(event_name):
        return re.sub(r"-(start|done)$", "", op)
    label = f"{_NUMBERED.sub('', name)} {_short_shape(shape)}".strip()
    return label if len(label) <= LABEL_LIMIT else label[: LABEL_LIMIT - 3] + "..."


def kernel_family_table(reading: Reading) -> dict:
    """{family: {"ms", "least_ms", "roofline_pct", "bound"}} per unit of work,
    summed over the traced devices' Mosaic calls whose family is known."""
    rows: dict[str, dict] = {}
    if reading.trace is None or not reading.trace.devices or reading.peaks is None:
        return rows
    from perfbench import flops

    scale = 1e3 / (len(reading.trace.devices) * reading.traced_units)
    for device in reading.trace.devices:
        for ev, own in xplane.self_seconds(device.ops):
            hit = kernel_families.match(ev.name) if xplane.is_mosaic_kernel(ev.name) else None
            if hit is None:
                continue
            least, bound = flops.least_seconds(hit[1], hit[2], reading.peaks)
            row = rows.setdefault(hit[0], {"ms": 0.0, "least_ms": 0.0, "bound": bound})
            row["ms"] += own * scale
            row["least_ms"] += least * scale
    for row in rows.values():
        row["roofline_pct"] = 100.0 * row["least_ms"] / row["ms"] if row["ms"] else None
    return rows


def breakdown(reading: Reading, top: int = 10) -> dict:
    """``device_ops``: seconds a unit of work, mean over the devices, of the
    operations that took most. ``idle_gaps``: idle seconds of the busiest
    device over the traced window by what the host was doing."""
    if not reading.trace.devices:  # a trace of a CPU has no device plane
        return {"device_ops": [], "idle_gaps": []}
    sums: dict[str, float] = {}
    scale = 1.0 / (len(reading.trace.devices) * reading.traced_units)
    for device in reading.trace.devices:
        for ev, own in xplane.self_seconds(device.ops):
            label = operation_label(ev.name)
            sums[label] = sums.get(label, 0.0) + own * scale
    ops = sorted(sums.items(), key=lambda kv: -kv[1])[:top]
    gaps = xplane.idle_gaps_by_host_span(reading.trace)[:top]
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": [[k, v] for k, v in gaps]}
