"""Shared by the readers of a region's device time. The device trace names an
event by its instruction's text, which carries no metadata (looked at on the
v5e, PR 33), so the region of the model's code an instruction lies in
(``thunder_tpu.core.trace.region``, a ``jax.named_scope`` in the generated
program) is read from the compiled program's own text, where every instruction
has ``metadata={op_name="jit(..)/<region>/<primitive>"}``: the job hands out
``counters["region_of_instruction"]``, a function that returns {instruction
name: region}. A fusion carries the ``op_name`` of one of the instructions it
fused; where that names no region, the regions of its fused computation's
instructions decide, the commonest winning. A program without such regions, or
a job that keeps no such counter, reads as nothing."""

import collections
import re

from perfbench import xplane

REGIONS = ("attn.sparse.select", "attn.sparse.attend", "attn.linear")
_LINE = re.compile(r"^\s*(?:ROOT )?%?(?P<name>[\w.\-]+) = .*$")
_OP_NAME = re.compile(r'op_name="(?P<op>[^"]*)"')
_CALLS = re.compile(r"\bcalls=%?(?P<callee>[\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?(?P<name>[\w.\-]+) (?:\([^)]*\) -> .*)?\{\s*$")


def _region_in(op_name: str, regions=REGIONS):
    parts = op_name.split("/")
    return next((r for r in regions if r in parts), None)


def of_instructions(hlo_text: str, regions=REGIONS) -> dict:
    """{instruction name: region} for the instructions of an optimized HLO
    module's text that lie in one of ``regions``."""
    own: dict[str, str] = {}          # instruction -> region by its own op_name
    calls: dict[str, str] = {}        # instruction -> the computation it calls
    inside: dict[str, collections.Counter] = {}  # computation -> regions of its instructions
    computation = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            computation = head.group("name")
            continue
        m = _LINE.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        found = _region_in(op.group("op"), regions) if op else None
        if found:
            own[m.group("name")] = found
            inside.setdefault(computation, collections.Counter())[found] += 1
        callee = _CALLS.search(line)
        if callee:
            calls[m.group("name")] = callee.group("callee")
    out = dict(own)
    for name, callee in calls.items():
        if name not in out and inside.get(callee):
            out[name] = inside[callee].most_common(1)[0][0]
    return out


def device_seconds(reading, prefix: str):
    """device -> seconds of the instructions whose region starts with
    ``prefix``, or ``None`` where the run knows of no region."""
    lookup = reading.counters.get("region_of_instruction")
    region_of = lookup() if callable(lookup) else None
    if not region_of:
        return None
    return lambda device: sum(own for ev, own in xplane.self_seconds(device.ops)
                              if region_of.get(xplane.instruction(ev.name)[0], "").startswith(prefix))


def region_ms(reading, prefix: str):
    seconds = device_seconds(reading, prefix)
    return reading.per_unit_ms(seconds) if seconds else None


def roofline(reading, prefix: str, mixer: str):
    """Least time over measured time, in %, of the region's instructions for
    the work the equations require (``counters["mixer_work"]``)."""
    from perfbench import flops

    ms, work = region_ms(reading, prefix), reading.counters.get("mixer_work")
    if not ms or not work or reading.peaks is None:
        return None
    return 100.0 * 1e3 * flops.least_seconds(*work[mixer], reading.peaks)[0] / ms
