"""The families of the calls that read heads out of a head-major array (PR 30):
the rope family's cost on the heads a call reads and writes, told from the rope
family by the first operand's shape, and adding them moves no call that had a
family."""

import pytest
from test_pb_flops import KERNELS, _TAIL

from perfbench import flops, kernel_families, peaks

# Instruction texts as the v5e's trace names them in pythia-410m.fwd's program (PR 30).
RESULT = "%computation.4 = bf16[8,16,2048,64]{3,2,1,0:T(8,128)(2,1)} custom-call("
TABLES = ", bf16[2048,128]{1,0:T(8,128)(2,1)S(1)} %copy-done.3, bf16[2048,128]{1,0:T(8,128)(2,1)S(1)} %copy-done.1)" + _TAIL
PAIRS = "bf16[8,24,2048,128]{3,2,1,0:T(8,128)(2,1)} %fusion.2"   # two heads of 64 a lane group
SINGLE = "bf16[8,48,2048,64]{3,2,1,0:T(8,128)(2,1)} %fusion.2"   # a head a group
Q_OR_K = RESULT + PAIRS + TABLES
Q_OR_K_SINGLE = RESULT + SINGLE + TABLES.replace("2048,128]", "2048,64]")
GQA_K = (RESULT.replace("bf16[8,16,2048,64]", "bf16[1,8,4096,128]") + SINGLE.replace("bf16[8,48,2048,64]", "bf16[1,48,4096,128]")
         + TABLES.replace("2048,128]", "4096,128]"))
V = RESULT + PAIRS + ")" + _TAIL
EVERY_HEAD = RESULT + "bf16[8,16,2048,64]{3,2,1,0:T(8,128)(2,1)} %fusion.2" + TABLES.replace("2048,128]", "2048,64]")


@pytest.mark.parametrize("text,x,cos", [(Q_OR_K, [8, 16, 2048, 64], [2048, 128]), (Q_OR_K_SINGLE, [8, 16, 2048, 64], [2048, 64]),
                                        (GQA_K, [1, 8, 4096, 128], [4096, 128])], ids=["pythia-q-or-k", "a-head-a-group", "gqa-k"])
def test_the_rope_call_has_a_family_and_the_rope_familys_cost_on_the_heads_it_reads(text, x, cos):
    hit = kernel_families.match(text)
    assert hit is not None and hit[0] == "rope_heads"
    assert hit[1:] == flops.rope(x, cos)


def test_the_call_that_only_splits_has_a_family_of_its_own_and_no_tables():
    hit = kernel_families.match(V)
    assert hit is not None and hit[0] == "split_heads"
    assert hit[2] == 2 * 16_777_216 * 2 + 4  # v read and written once in bf16


def test_cost_by_hand_at_pythia():
    # 8 * 16 * 2048 * 64 elements read and written in bf16, two (2048, 128) tables read: 68.2 MB, 83.2 us at the HBM peak
    ops, nbytes = flops.rope([8, 16, 2048, 64], [2048, 128])
    assert (ops, nbytes) == (3.0 * 16_777_216, 2 * 16_777_216 * 2 + 2 * 262_144 * 2)
    seconds, bound = flops.least_seconds(ops, nbytes, peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory" and seconds == pytest.approx(83.22e-6, rel=1e-3)


def test_a_call_on_every_head_stays_the_rope_familys():
    assert kernel_families.match(EVERY_HEAD)[0] == "rope"


@pytest.mark.parametrize("family", sorted(KERNELS))
def test_no_call_that_had_a_family_changes_it(family):
    assert kernel_families.match(KERNELS[family])[0] == family
    for new in ("rope_heads", "split_heads"):
        pattern = next(p for name, p, _ in kernel_families._families() if name == new)
        assert pattern.search(KERNELS[family]) is None


@pytest.mark.parametrize("text", [
    Q_OR_K.replace("bf16[8,24,2048,128]", "bf16[4,24,2048,128]"),  # another batch
    Q_OR_K.replace("bf16[8,24,2048,128]", "bf16[8,24,1024,128]"),  # another sequence
    Q_OR_K.replace("%copy-done.1)", "%copy-done.1, bf16[8]{0} %extra)"),  # a fourth operand
    Q_OR_K.replace("= bf16[8,16", "= f32[8,16"),
    V.replace("bf16[8,24,2048,128]", "bf16[8,24,2048]"),
], ids=["batch", "sequence", "operands", "dtype", "rank"])
def test_what_is_not_such_a_call_matches_nothing(text):
    assert kernel_families.match(text) is None
