"""The interval arithmetic and the instruction parsing under the trace
reduction, on cases small enough to work by hand."""

import pytest

from perfbench import xplane
from perfbench.xplane import DeviceTrace, Event, Trace


def test_union_merges_touching_and_nested():
    assert xplane.union([(5, 6), (0, 2), (1, 3), (3, 4), (2.5, 2.75), (9, 9)]) == [(0, 4), (5, 6)]
    assert xplane.total(xplane.union([(0, 2), (1, 3), (10, 11)])) == 4


def test_subtract_and_clip():
    a = [(0, 10), (20, 30)]
    b = [(-5, 1), (2, 3), (9, 21), (29, 40)]
    assert xplane.subtract(a, b) == [(1, 2), (3, 9), (21, 29)]
    assert xplane.subtract(a, []) == a
    assert xplane.subtract([(0, 1)], [(0, 1)]) == []
    assert xplane.clip([(0, 4), (5, 6), (8, 12)], 3, 9) == [(3, 4), (5, 6), (8, 9)]


def test_self_seconds_takes_children_out_of_their_parent():
    # a while of 10 s holding a 3 s and a 4 s body instruction, the second holding 1 s of its own child
    events = [Event("while", 0, 10), Event("a", 1, 4), Event("b", 5, 9), Event("c", 6, 7), Event("after", 10, 12)]
    own = {e.name: s for e, s in xplane.self_seconds(events)}
    assert own == {"while": 3, "a": 3, "b": 3, "c": 1, "after": 2}
    assert sum(own.values()) == xplane.total(xplane.union((e.start, e.end) for e in events))


FUSION = ("%fusion.72 = (bf16[1024]{0:T(1024)(128)(2,1)}, f32[4,2048]{1,0:T(4,128)}) fusion(bf16[4,2048,1024]{2,1,0} "
          "%custom-call.16, f32[4,2048]{1,0} %copy-done.37), kind=kOutput, calls=%fused_computation.70")
KERNEL = ('%step.3 = f32[8192,128]{1,0:T(8,128)S(1)} custom-call(f32[8192,50304]{1,0:T(8,128)} %bitcast.26, '
          's32[8192,128]{1,0} %copy-done.21), custom_call_target="tpu_custom_call", operand_layout_constraints={}')
LAYOUT_CALL = '%custom-call.6 = bf16[50304,1024]{1,0} custom-call(bf16[50304,1024]{1,0} %p), custom_call_target="AssumeGatherIndicesInBound"'
GATHER = "%all-gather-start.4 = (bf16[1024,4096]{1,0}, bf16[4096,4096]{1,0}) all-gather-start(bf16[1024,4096]{1,0} %p), dimensions={0}"
GATHER_DONE = "%all-gather-done.4 = bf16[4096,4096]{1,0} all-gather-done((bf16[1024,4096]{1,0}, bf16[4096,4096]{1,0}) %all-gather-start.4)"
REDUCE = "%all-reduce.7 = f32[4096]{0} all-reduce(f32[4096]{0} %x), replica_groups={{0,1,2,3}}, to_apply=%add"


def test_instruction_text_is_parsed():
    assert xplane.instruction(FUSION) == ("fusion.72", "fusion", "(bf16[1024],f32[4,2048])")
    assert xplane.instruction(KERNEL) == ("step.3", "custom-call", "f32[8192,128]")
    assert xplane.instruction(GATHER)[:2] == ("all-gather-start.4", "all-gather-start")
    assert xplane.instruction("jit_step(123)") == ("jit_step(123)", "", "")


def test_classes():
    assert xplane.is_mosaic_kernel(KERNEL)
    # an operand called %custom-call.16 does not make a fusion a kernel, nor does another target
    assert not xplane.is_mosaic_kernel(FUSION) and not xplane.is_mosaic_kernel(LAYOUT_CALL)
    assert all(xplane.is_collective(t) for t in (GATHER, GATHER_DONE, REDUCE))
    assert not xplane.is_collective(FUSION) and not xplane.is_collective(KERNEL)


def device():
    """A step by hand, 0 to 10 s: a fusion, a gather issued and left in flight
    over a kernel, a wait for it, an idle second, a synchronous reduce."""
    ops = [Event(FUSION, 0, 2), Event(GATHER, 2, 2.5), Event(KERNEL, 2.5, 5), Event(GATHER_DONE, 5, 6),
           Event(REDUCE, 7, 9), Event(FUSION, 9, 10)]
    in_flight = [Event(GATHER, 2, 6)]
    return DeviceTrace(ordinal=0, ops=ops, in_flight=in_flight, programs=[Event("jit_step(1)", 0, 10)])


def test_a_step_by_hand():
    d = device()
    assert xplane.window_of(d) == (0, 10)
    assert xplane.total(xplane.busy_intervals(d)) == 9  # everything but 6..7
    assert xplane.class_seconds(d) == {"kernel": 2.5, "collective": 0.5 + 1 + 2, "xla": 3}
    # a collective is running or in flight 2..6 and 7..9; compute covers 2.5..5 of that
    assert xplane.collective_and_exposed(d) == (6, 3.5)
    trace = Trace(devices=[d], host_spans=[Event("perfbench.unit", 0, 10), Event("perfbench.wait", 4, 10)])
    assert xplane.busy_and_window(trace) == (9, 10)
    assert xplane.idle_gaps_by_host_span(trace) == [("perfbench.wait, inside a program", 1)]


def test_a_device_on_which_nothing_ran_is_an_error():
    with pytest.raises(ValueError, match="no operation ran"):
        xplane.window_of(DeviceTrace(ordinal=2, ops=[], in_flight=[], programs=[]))
