"""The trace reduction on a trace recorded on the four-chip v5e
(``perfbench/fixtures/``): busy union, idle share, kernel, XLA and collective
sums, the exposed part of the collectives, and the breakdown's names, against
numbers taken from the file by other means: a sweep over interval end points
written here, plain sums over regular expressions, and counts read by eye
from a dump of the file (PR 22)."""

import os
import re
import types

import pytest
from pb_helpers import REPO

from perfbench import peaks, reading, xplane

FIXTURE = os.path.join(REPO, "perfbench", "fixtures", "fsdp4_stand_in.xplane.pb")
NS = 1e-9
STEPS = 3


@pytest.fixture(scope="module")
def trace():
    return xplane.load(FIXTURE)


@pytest.fixture(scope="module")
def raw():
    """{device ordinal: [(name, start ns, end ns)]} of the XLA Ops, and the
    window of each device, read without perfbench.xplane."""
    from jax.profiler import ProfileData

    ops, window = {}, {}
    for plane in ProfileData.from_file(FIXTURE).planes:
        if plane.name.startswith("/device:TPU:"):
            n = int(plane.name.rsplit(":", 1)[1])
            lines = {l.name: [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in l.events]
                     for l in plane.lines}
            ops[n] = lines["XLA Ops"]
            window[n] = (min(s for _, s, _ in lines["XLA Modules"]), max(e for _, _, e in lines["XLA Modules"]))
    return ops, window


def swept(inside, outside=()):
    """Nanoseconds covered by some interval of ``inside`` and by none of ``outside``."""
    points = sorted([(s, 0, 1) for s, _ in inside] + [(e, 0, -1) for _, e in inside]
                    + [(s, 1, 1) for s, _ in outside] + [(e, 1, -1) for _, e in outside])
    depth, covered, last = [0, 0], 0.0, None
    for t, which, step in points:
        if depth[0] > 0 and depth[1] == 0:
            covered += t - last
        depth[which] += step
        last = t
    return covered


def is_kernel(name):
    return bool(re.match(r"%\S+ = .*? custom-call\(", name, re.S)) and 'custom_call_target="tpu_custom_call"' in name


def is_collective(name):
    return bool(re.match(r"%async-collective-(start|done)", name)
                or re.search(r"[})] (all-gather|all-reduce)\(", name))


def test_what_the_file_holds(trace):
    assert [d.ordinal for d in trace.devices] == [0, 1, 2, 3]
    assert all(len(d.ops) == 1140 and len(d.programs) == STEPS for d in trace.devices)
    assert [len(d.in_flight) for d in trace.devices] == [312, 0, 0, 0]  # the v5e fills that line on one chip only
    names = [s.name for s in trace.host_spans]
    assert names.count("perfbench.unit") == names.count("perfbench.call") == names.count("perfbench.wait") == STEPS


def test_busy_union_and_idle_share(trace, raw):
    ops, window = raw
    for d in trace.devices:
        lo, hi = window[d.ordinal]
        assert xplane.window_of(d) == pytest.approx((lo * NS, hi * NS))
        want = swept([(max(s, lo), min(e, hi)) for _, s, e in ops[d.ordinal]])
        assert xplane.total(xplane.busy_intervals(d)) == pytest.approx(want * NS, rel=1e-9)
    # device 0 by the numbers of the dump: busy 615,762 ns of a 6,109,948 ns window
    assert xplane.total(xplane.busy_intervals(trace.devices[0])) == pytest.approx(615_762 * NS)
    busy, window_s = xplane.busy_and_window(trace)
    assert busy == pytest.approx((615_762 + 610_412 + 611_226 + 607_808) / 4 * NS)
    assert window_s == pytest.approx((6_109_948 + 6_130_695 + 6_117_802 + 6_288_178) / 4 * NS)
    r = types.SimpleNamespace(trace=trace)
    from perfbench.layer_metrics import device_idle_share

    # a stand-in this small waits for its host nine tenths of the time
    assert device_idle_share.read(r) == pytest.approx(100 * (1 - busy / window_s)) == pytest.approx(90.08, abs=0.01)


def test_kernel_xla_and_collective_sums(trace, raw):
    ops, _ = raw
    for d in trace.devices:
        mine = ops[d.ordinal]
        assert sum(is_kernel(n) for n, _, _ in mine) == 42  # 14 claimed symbols a step, 3 steps
        assert sum(is_collective(n) for n, _, _ in mine) == 114
        got = xplane.class_seconds(d)
        want_kernel = sum(e - s for n, s, e in mine if is_kernel(n))
        want_coll = sum(e - s for n, s, e in mine if is_collective(n))
        assert got["kernel"] == pytest.approx(want_kernel * NS, rel=1e-9)
        assert got["collective"] == pytest.approx(want_coll * NS, rel=1e-9)
        # nothing nests in this trace, so the classes add up to the sum of all durations
        assert sum(got.values()) == pytest.approx(sum(e - s for _, s, e in mine) * NS, rel=1e-9)
    assert xplane.class_seconds(trace.devices[0]) == pytest.approx(
        {"kernel": 27_860 * NS, "collective": 260_237 * NS, "xla": 327_665 * NS})


def test_collectives_in_flight_and_their_exposed_part(trace, raw):
    ops, window = raw
    for d in trace.devices:
        mine, (lo, hi) = ops[d.ordinal], window[d.ordinal]
        spans = [(s, e) for n, s, e in mine if is_collective(n)]
        started = {}
        for n, s, e in mine:  # start.<k> pairs with the done.<k> that follows it
            m = re.match(r"%async-collective-(start|done)(\S*) = ", n)
            if m and m.group(1) == "start":
                started[m.group(2)] = s
            elif m and m.group(2) in started:
                spans.append((started.pop(m.group(2)), e))
        compute = [(s, e) for n, s, e in mine if not is_collective(n)]
        got = xplane.collective_and_exposed(d)
        assert got[0] == pytest.approx(swept(spans) * NS, rel=1e-9)
        assert got[1] == pytest.approx(swept(spans, compute) * NS, rel=1e-9)
        assert 0 < got[1] < got[0]
    assert xplane.collective_and_exposed(trace.devices[0]) == pytest.approx((404_844 * NS, 260_650 * NS))
    assert xplane.busiest(trace).ordinal == 0


def test_compute_fusions_that_carry_a_collective(trace, raw):
    """The matmuls XLA overlapped with a gather are part of the ring themselves
    (``calls=%async_collective_fusion``): counted as ``xla``, summed apart too."""
    ops, _ = raw
    for d in trace.devices:
        mine = [(n, s, e) for n, s, e in ops[d.ordinal] if re.search(r"calls=%async_collective_fusion\b", n)]
        assert len(mine) == 96 and all(n.startswith("%fusion") and not is_collective(n) for n, _, _ in mine)
        assert xplane.fused_with_collective_seconds(d) == pytest.approx(sum(e - s for _, s, e in mine) * NS, rel=1e-9)
    assert xplane.fused_with_collective_seconds(trace.devices[0]) == pytest.approx(103_115 * NS)
    assert xplane.fused_with_collective_seconds(trace.devices[0]) < xplane.class_seconds(trace.devices[0])["xla"]


def test_readers_and_breakdown(trace):
    r = reading.Reading(cell=None, spans={}, counters={}, window=None, tokens_per_s=None, flops_per_token=0.0,
                        peaks=peaks.peaks_for("TPU v5 lite"), trace=trace, traced_units=STEPS)
    assert reading.read_metric("kernels_ms", r) == pytest.approx((27_860 + 27_880 + 27_880 + 27_876) / 4 / STEPS * 1e-6)
    assert reading.read_metric("xla_ms", r) == pytest.approx((327_665 + 326_849 + 326_398 + 326_220) / 4 / STEPS * 1e-6)
    assert reading.read_metric("collective_ms", r) == pytest.approx(404_844 / STEPS * 1e-6)
    assert reading.read_metric("collective_exposed_ms", r) == pytest.approx(260_650 / STEPS * 1e-6)
    assert reading.read_metric("collective_fused_ms", r) == pytest.approx(
        (103_115 + 104_220 + 102_696 + 104_108) / 4 / STEPS * 1e-6)
    # every Mosaic call of the stand-in belongs to a known family, under shard_map too
    families = reading.kernel_family_table(r)
    assert set(families) == {"flash_fwd", "flash_bwd", "cross_entropy_fwd", "cross_entropy_bwd", "rope"}
    assert sum(f["ms"] for f in families.values()) == pytest.approx(reading.read_metric("kernels_ms", r))
    assert 0 < reading.read_metric("kernels_roofline", r) < 100

    b = reading.breakdown(r)
    labels = [name for name, _ in b["device_ops"]]
    assert len(labels) == 10 and b["device_ops"] == sorted(b["device_ops"], key=lambda kv: -kv[1])
    assert labels[1:5] == ["async-collective-done", "async-collective-start", "all-gather", "all-reduce"]
    # the compute the compiler overlapped with a gather, its semaphores folded: short enough to read
    assert "fusion (bf16[512,256],bf16[128,256],bf16[512,256]x2,s32[2],u32[]x13)" in labels
    assert all(len(name) <= reading.LABEL_LIMIT for name in labels)
    # idle, by what the host was doing: this stand-in waits on the host's call into the step
    assert b["idle_gaps"][0][0] == "perfbench.call, between programs"
    assert sum(s for _, s in b["idle_gaps"]) == pytest.approx((6_109_948 - 615_762) * NS, rel=1e-6)


def test_a_trace_with_no_device_plane_reads_as_nothing():
    r = reading.Reading(cell=None, spans={}, counters={}, window=None, tokens_per_s=None, flops_per_token=0.0,
                        peaks=None, trace=xplane.Trace(devices=[], host_spans=[]), traced_units=3)
    for name in ("kernels_ms", "kernels_roofline", "xla_ms", "collective_ms", "collective_exposed_ms",
                 "collective_fused_ms", "device_idle_share", "mfu"):
        assert reading.read_metric(name, r) is None
    assert reading.breakdown(r) == {"device_ops": [], "idle_gaps": []}
