"""Kernels of the main path compiled at real widths for a described v5e, from
a host with no chip: what Mosaic's compiler refuses (a slice off the tiling,
too much VMEM) it refuses here, at no chip time. Interpret mode, which every
other test of the kernels runs in, cannot show that.

One process at a time may load the TPU's library, so the topology is described
inside a fixture (never at import) and every such test lives in this file."""

import re
from types import SimpleNamespace

import pytest


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu from describing a chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip can be written to the persistent cache and never read back.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def describe_chip(one_chip):
    """Another generation's chip, by topology name (after ``one_chip``, which turned the persistent cache off)."""
    import functools

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    @functools.cache
    def describe(topology_name):
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name=topology_name)
        except Exception as e:  # noqa: BLE001
            pytest.skip(f"no {topology_name} topology can be described here: {e}")
        return SingleDeviceSharding(topo.devices[0])

    return describe


ROPE_SHAPES = [
    # dtype, (B, H, T, hs), n
    ("bfloat16", (8, 16, 2048, 64), 16),    # pythia-410m.fwd's q and k
    ("bfloat16", (4, 32, 2048, 80), 32),    # phi-2
    ("bfloat16", (1, 8, 4096, 128), 64),
    ("bfloat16", (2, 4, 2048, 256), 64),    # the widest row the checker takes
    ("float32", (2, 4, 2048, 128), 32),
    ("bfloat16", (2, 4, 8, 64), 16),        # the shortest block
    ("bfloat16", (1, 32, 4096, 128), 128),  # mistral-7b.train's q: full rotary
    ("bfloat16", (2, 64, 4096, 192), 64),   # a.x-k1.fwd's q: the rope part first, 64 of 192 lanes
    ("bfloat16", (2, 1, 4096, 64), 64),     # a.x-k1.fwd's one rope key for all heads
    ("bfloat16", (2, 32, 4096, 64), 64),    # lfm2-8b-a1b.fwd's normed q: full rotary on heads of 64
    ("bfloat16", (2, 8, 4096, 64), 64),     # and its k, a head for four query heads
    ("bfloat16", (1, 32, 32768, 128), 128), # minicpm-sala.fwd-t32k's linear layers' q and k: 32,768 positions
]


def _rope_claim_and_lowering(monkeypatch, one_chip, dtype, shape, n):
    """(what the checker says of the shapes, the rope call lowered for the described chip)."""
    import jax
    import jax.numpy as jnp

    from thunder_tpu.core import dtypes
    from thunder_tpu.executors import pallasex

    tables = (shape[-2], n)
    proxy = lambda s: SimpleNamespace(shape=s, dtype=getattr(dtypes, dtype))
    sds = lambda s: jax.ShapeDtypeStruct(s, getattr(jnp, dtype), sharding=one_chip)
    monkeypatch.setattr(pallasex, "_interpret", lambda: False)
    return (pallasex._rope_checker(proxy(shape), proxy(tables), proxy(tables)),
            jax.jit(pallasex._rope_impl, donate_argnums=0).lower(sds(shape), sds(tables), sds(tables)))


@pytest.mark.parametrize("dtype,shape,n", ROPE_SHAPES, ids=[f"{d}-{s[-1]}-{n}-T{s[-2]}" for d, s, n in ROPE_SHAPES])
def test_rope_kernel_compiles_for_v5e(one_chip, monkeypatch, dtype, shape, n):
    """Every shape the rope checker takes compiles, in place where the rotary is partial."""
    claimed, lowered = _rope_claim_and_lowering(monkeypatch, one_chip, dtype, shape, n)
    assert claimed
    text = lowered.compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert ("output_to_operand_aliasing" in text) == (n != shape[-1])


@pytest.mark.parametrize("dtype,hs", [("float32", 256), ("float16", 64)])
def test_rope_checker_declines_what_does_not_compile(one_chip, monkeypatch, dtype, hs):
    """Partial rotary in float32 at 256 lanes runs out of VMEM and float16 has no
    matmul on the v5e: the checker says no, because nothing falls back at run time."""
    claimed, lowered = _rope_claim_and_lowering(monkeypatch, one_chip, dtype, (8, 16, 2048, hs), 16)
    assert not claimed
    with pytest.raises(Exception):  # noqa: B017, PT011 - Mosaic's own error, whatever its type
        lowered.compile()


CE_SHAPES = [
    # rows, vocabulary, dtype
    (8192, 50304, "bfloat16"),   # pythia-410m.train, the logits as the head wrote them
    (4096, 32000, "bfloat16"),   # mistral-7b.train, and a chip of mistral-7b.fsdp4
    (8192, 50304, "float32"),    # a program that computes its logits in float32
    (4096, 32000, "float32"),
    (4096, 163840, "bfloat16"),  # ROADMAP R3's vocabulary
]


def _ce_claim_and_lowering(monkeypatch, one_chip, backward, rows, vocab, dtype):
    """(what the checker says of the shapes, the kernel's call lowered for the described chip)."""
    import jax
    import jax.numpy as jnp

    from thunder_tpu.core import dtypes
    from thunder_tpu.executors import pallasex

    monkeypatch.setattr(pallasex, "_interpret", lambda: False)
    monkeypatch.setattr(pallasex, "_device_kind", lambda: next(iter(one_chip.device_set)).device_kind)
    logits = SimpleNamespace(shape=(rows, vocab), dtype=getattr(dtypes, dtype))
    target = SimpleNamespace(shape=(rows,), dtype=dtypes.int32)
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    args = (sds((rows, vocab), getattr(jnp, dtype)), sds((rows,), jnp.int32))
    # A function of its own each time: jax keeps a traced call by function and shapes, and the block is the device's.
    if backward:
        return (pallasex._ce_bwd_checker(None, logits, target),
                jax.jit(lambda *a: pallasex._ce_bwd_impl(*a)).lower(sds((), jnp.float32), *args))
    return pallasex._ce_checker(logits, target), jax.jit(lambda *a: pallasex._ce_impl(*a)).lower(*args)


CE_IDS = [f"{d}-{n}x{v}" for n, v, d in CE_SHAPES]


@pytest.mark.parametrize("rows,vocab,dtype", CE_SHAPES, ids=CE_IDS)
def test_cross_entropy_forward_kernel_compiles_for_v5e(one_chip, monkeypatch, rows, vocab, dtype):
    claimed, lowered = _ce_claim_and_lowering(monkeypatch, one_chip, False, rows, vocab, dtype)
    assert claimed
    assert lowered.compile().as_text().count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("rows,vocab,dtype", CE_SHAPES, ids=CE_IDS)
def test_cross_entropy_backward_kernel_compiles_for_v5e(one_chip, monkeypatch, rows, vocab, dtype):
    """The gradient comes out in the logits' dtype, from one call."""
    claimed, lowered = _ce_claim_and_lowering(monkeypatch, one_chip, True, rows, vocab, dtype)
    assert claimed
    compiled = lowered.compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1
    assert str(compiled.out_info.dtype) == dtype and compiled.out_info.shape == (rows, vocab)


@pytest.mark.parametrize("backward", [False, True], ids=["forward", "backward"])
def test_cross_entropy_checker_declines_float16_which_does_not_compile(one_chip, monkeypatch, backward):
    """Mosaic loads no float16 vector on the v5e: the checkers say no, so the
    upcast before the loss stays in a float16 program."""
    claimed, lowered = _ce_claim_and_lowering(monkeypatch, one_chip, backward, 4096, 32000, "float16")
    assert not claimed
    with pytest.raises(Exception):  # noqa: B017, PT011 - Mosaic's own error, whatever its type
        lowered.compile()


OTHER_CHIPS = [
    # topology, the VMEM a call asks for there in MiB, the row blocks at (8192, 50304) in bfloat16 and float32
    ("v4:2x2x1", 16, (16, 8)),    # 16 MiB a core: the default scope, which the kernels lived in before
    ("v5p:2x2x1", 32, (32, 16)),  # 64 MiB
    ("v6e:2x2", 64, (64, 32)),    # 128 MiB, as the v5e
]


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("topology,limit_mib,blocks", OTHER_CHIPS, ids=[t.split(":")[0] for t, _, _ in OTHER_CHIPS])
def test_cross_entropy_kernels_compile_for_other_generations(describe_chip, monkeypatch, topology, limit_mib, blocks, dtype):
    """The block follows the VMEM of the device's generation: what the checkers
    claim at pythia's shapes compiles there, forward and backward."""
    from thunder_tpu.executors import pallasex

    chip = describe_chip(topology)
    for backward in (False, True):
        claimed, lowered = _ce_claim_and_lowering(monkeypatch, chip, backward, 8192, 50304, dtype)
        assert claimed
        assert lowered.compile().as_text().count('custom_call_target="tpu_custom_call"') == 1
    assert pallasex._ce_vmem_limit() == limit_mib * 1024 * 1024
    assert pallasex._ce_block_n(8192, 50304, 2 if dtype == "bfloat16" else 4) == blocks[dtype == "float32"]


def test_cross_entropy_checker_declines_where_the_generation_has_no_room(describe_chip, monkeypatch):
    """A vocabulary of 163840 in 16 rows of bfloat16 does not fit the v4's 16 MiB
    twice over: the checkers say no there, and yes on the v5e (above)."""
    for backward in (False, True):
        with pytest.raises(ValueError, match="unclaimable"):
            _ce_claim_and_lowering(monkeypatch, describe_chip("v4:2x2x1"), backward, 4096, 163840, "bfloat16")
    from thunder_tpu.core import dtypes
    from thunder_tpu.executors import pallasex

    logits = SimpleNamespace(shape=(4096, 163840), dtype=dtypes.bfloat16)
    target = SimpleNamespace(shape=(4096,), dtype=dtypes.int32)
    assert not pallasex._ce_checker(logits, target) and not pallasex._ce_bwd_checker(None, logits, target)


def test_the_v5e_block_does_not_compile_for_a_v4(describe_chip, monkeypatch):
    """Why the budget is the device's: the backward on 64 rows of pythia's
    vocabulary under a 64 MiB scope, right for the v5e, runs out of the v4's VMEM."""
    import jax
    import jax.numpy as jnp

    from thunder_tpu.executors import pallasex

    monkeypatch.setattr(pallasex, "_interpret", lambda: False)
    monkeypatch.setattr(pallasex, "_device_kind", lambda: "TPU v5 lite")
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=describe_chip("v4:2x2x1"))
    lowered = jax.jit(lambda *a: pallasex._ce_bwd_impl(*a)).lower(
        sds((), jnp.float32), sds((8192, 50304), jnp.bfloat16), sds((8192,), jnp.int32))
    with pytest.raises(Exception, match="vmem"):  # noqa: PT011 - Mosaic's own error, whatever its type
        lowered.compile()


MLA_SHAPES = [
    # (B, H, T), d_qk, d_v
    ((2, 64, 4096), 192, 128),  # a.x-k1.fwd: latent attention's prefill call
    ((1, 8, 2048), 192, 128),
    ((1, 4, 1024), 128, 64),
]


@pytest.mark.parametrize("bht,d_qk,d_v", MLA_SHAPES, ids=[f"{d}-{v}-T{s[-1]}" for s, d, v in MLA_SHAPES])
def test_attention_with_narrower_value_heads_compiles_for_v5e(one_chip, monkeypatch, bht, d_qk, d_v):
    """The flash executor claims a call whose value heads are narrower than its
    query and key heads, and splash compiles it for the v5e with the output at
    the value width: no lane is padded to make the widths equal."""
    import jax
    import jax.numpy as jnp

    from thunder_tpu.core import dtypes
    from thunder_tpu.executors import flashex

    monkeypatch.setattr(flashex, "_interpret", lambda: False)
    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    proxy = lambda d: SimpleNamespace(shape=(*bht, d), dtype=dtypes.bfloat16)
    assert flashex._sdpa_checker(proxy(d_qk), proxy(d_qk), proxy(d_v), is_causal=True, scale=0.1)
    sds = lambda d: jax.ShapeDtypeStruct((*bht, d), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(lambda q, k, v: flashex._sdpa_impl(q, k, v, is_causal=True, scale=0.1)).lower(
        sds(d_qk), sds(d_qk), sds(d_v)).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1
    assert compiled.out_info.shape == (*bht, d_v)


def test_grouped_query_attention_at_head_64_compiles_for_v5e(one_chip, monkeypatch):
    """lfm2-8b-a1b.fwd's attention call: 32 query heads of 64 on 8 key-value heads
    at T=4096, which no other cell asks of splash (pythia has heads of 64 and a
    key head each, mistral four query heads a key head at 128)."""
    import jax
    import jax.numpy as jnp

    from thunder_tpu.core import dtypes
    from thunder_tpu.executors import flashex

    monkeypatch.setattr(flashex, "_interpret", lambda: False)
    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    proxy = lambda h: SimpleNamespace(shape=(2, h, 4096, 64), dtype=dtypes.bfloat16)
    assert flashex._sdpa_checker(proxy(32), proxy(8), proxy(8), is_causal=True, enable_gqa=True)
    sds = lambda h: jax.ShapeDtypeStruct((2, h, 4096, 64), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(lambda q, k, v: flashex._sdpa_impl(q, k, v, is_causal=True, enable_gqa=True)).lower(
        sds(32), sds(8), sds(8)).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1
    assert compiled.out_info.shape == (2, 32, 4096, 64)


def _arrays_written(text):
    """(instruction, shape) of what the entry computation and a conditional's
    branches write: the instructions of every computation that is not the body
    of a fusion, which never reaches memory."""
    made = []
    for body in re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \([^\n]*\) -> [^\n]*\{\n)", text):
        if not body.lstrip().startswith("%fused_computation"):
            made += re.findall(r"^\s+(?:ROOT )?%([\w\-]+?)[.\d]* = \(?(\w+\[[\d,]*\])", body, re.M)
    return made


def test_grouped_matmul_compiles_for_v5e_as_a_kernel_that_walks_the_groups(one_chip):
    """The routed experts' grouped matmul at a.x-k1.fwd's worst-case buffer (8 rows
    a token, 12 held experts): XLA's ragged dot is a Mosaic call on the v5e, with
    the group sizes an operand, not a dense product masked afterwards."""
    import jax
    import jax.numpy as jnp

    from thunder_tpu.executors import jaxex

    sds = lambda s, d=jnp.bfloat16: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    for (k, n) in ((7168, 2048), (2048, 7168)):
        text = jax.jit(jaxex._grouped_mm).lower(sds((65536, k)), sds((12, k, n)), sds((12,), jnp.int32)).compile().as_text()
        assert "ragged-dot" in text and 'custom_call_target="tpu_custom_call"' in text


def test_claimed_routed_experts_compile_for_v5e_with_the_short_buffer_and_the_worst_case(one_chip, monkeypatch):
    """a.x-k1.fwd's expert layer as the pallas executor claims it: 8192 tokens, 8
    choices among 192, 12 held experts of 7168 x 2048. Three megablox calls on
    the short buffer (8192 rows, twice what an even router sends here) in each of
    two branches, chosen by one conditional on the count of rows routed here:
    once over the buffer, or, since PR 40, in passes over it, a loop; the worst
    case's 65536 rows are no array's any more."""
    import jax
    import jax.numpy as jnp

    from thunder_tpu.core import dtypes
    from thunder_tpu.executors import pallasex

    monkeypatch.setattr(pallasex, "_interpret", lambda: False)
    shapes = {"x": ((8192, 7168), "bfloat16"), "top_i": ((8192, 8), "int32"), "top_w": ((8192, 8), "float32"),
              "w_gate": ((12, 7168, 2048), "bfloat16"), "w_up": ((12, 7168, 2048), "bfloat16"),
              "w_down": ((12, 2048, 7168), "bfloat16")}
    assert pallasex._moe_experts_checker(*(SimpleNamespace(shape=s, dtype=getattr(dtypes, d)) for s, d in shapes.values()))
    assert pallasex.expert_buffer_rows(8192, 8, 12, 192) == 8192 and pallasex.expert_buffer_passes(65536, 8192, 8, 12, 192) == 8
    sds = [jax.ShapeDtypeStruct(s, getattr(jnp, d), sharding=one_chip) for s, d in shapes.values()]
    compiled = jax.jit(lambda *a: pallasex._moe_experts_impl(*a, 0, 192)).lower(*sds).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 6 and " conditional(" in text and " while(" in text
    assert "bf16[8192,7168]" in text and "[65536,7168]" not in text and "[65536,2048]" not in text and "bf16[16384,7168]" not in text
    # The way back of the one pass: 8 gathers of (8192, 7168) that one fusion masks (a select, on bf16), weighs and
    # sums; no array stacked, no fill after a gather; the only float32 of (N, C) is the passes' running sum.
    written = _arrays_written(text)
    assert "f32[65536," not in text and "f32[8,8192,7168]" not in text
    assert not [made for made in written if made[1] in ("bf16[8,8192,7168]", "bf16[8192,8,7168]")]
    assert "broadcast_select_fusion" not in dict(written)
    assert written.count(("add_convert_fusion", "bf16[8192,7168]")) == 1  # the one pass's sum; the passes' is a loop's


def test_claimed_routed_experts_of_longcat_go_over_their_buffer_in_passes_and_hold_no_worst_case(one_chip, monkeypatch):
    """longcat-flash-omni.fwd-t16k's routed layer as the pallas executor claims
    it: 16,384 tokens, 12 choices among 768 outputs, 16 held experts of 6144 x
    2048. The worst case is 196,608 rows (8 GB of temporaries, ISSUE 40); the
    buffer is 8,192, gone over once or in a loop of passes, and nothing in the
    compiled program has the worst case's rows."""
    import jax
    import jax.numpy as jnp

    from perfbench.run import executable_needs
    from thunder_tpu.core import dtypes
    from thunder_tpu.executors import pallasex

    monkeypatch.setattr(pallasex, "_interpret", lambda: False)
    shapes = {"x": ((16384, 6144), "bfloat16"), "top_i": ((16384, 12), "int32"), "top_w": ((16384, 12), "float32"),
              "w_gate": ((16, 6144, 2048), "bfloat16"), "w_up": ((16, 6144, 2048), "bfloat16"),
              "w_down": ((16, 2048, 6144), "bfloat16")}
    assert pallasex._moe_experts_checker(*(SimpleNamespace(shape=s, dtype=getattr(dtypes, d)) for s, d in shapes.values()))
    assert pallasex.expert_buffer_rows(16384, 12, 16, 768) == 8192
    sds = [jax.ShapeDtypeStruct(s, getattr(jnp, d), sharding=one_chip) for s, d in shapes.values()]
    compiled = jax.jit(lambda *a: pallasex._moe_experts_impl(*a, 0, 768)).lower(*sds).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 6 and " conditional(" in text and text.count(" while(") >= 2
    assert "bf16[8192,6144]" in text and "[196608,6144]" not in text and "[196608,2048]" not in text
    needs, sizes = executable_needs(compiled)
    assert sizes["temp_size_in_bytes"] < 4.0e9  # the k gathers of (N, C) of the one pass, 2.4 GB, are the most of it


def test_the_cell_of_longcat_compiles_for_v5e_with_no_array_of_the_worst_cases_rows(one_chip, monkeypatch):
    """longcat-flash-omni.fwd-t16k's program at depth 1, as the cell's job lowers
    it (the dispatcher's pass applied): 7 claimed kernels' calls and the routed
    layer's two branches; no array of 196,608 rows among its instructions; the
    regions the cell's readers ask for are in its text."""
    from perfbench import manifest
    from perfbench.jobs import forward_scmoe
    from perfbench.layer_metrics import _regions
    from perfbench.run import executable_needs
    from thunder_tpu.executors import flashex, pallasex

    monkeypatch.setattr(pallasex, "_interpret", lambda: False)
    monkeypatch.setattr(flashex, "_interpret", lambda: False)
    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    cell = manifest.load_cell("longcat-flash-omni.fwd-t16k")
    keys = manifest.published(cell)
    keys.update(num_hidden_layers=1)
    topo = SimpleNamespace(devices=[next(iter(one_chip.device_set))])
    compiled = forward_scmoe.lower_for(cell, keys, cell.traffic["batch"], cell.traffic["seq"], topo).compile()
    text = compiled.as_text()
    assert "196608,6144]" not in text and "196608,2048]" not in text and "bf16[8192,6144]" in text
    assert text.count('custom_call_target="tpu_custom_call"') == 2 * 3 + 2 * 3  # q's rope, k's rope, attention a sublayer; gmm thrice a branch
    assert " conditional(" in text and " while(" in text
    needs, sizes = executable_needs(compiled)
    assert sizes["temp_size_in_bytes"] < 5.0e9 and needs < 8.0e9
    found = _regions.of_instructions(forward_scmoe.forward_window_moe.an_instruction_a_line(text), forward_scmoe.REGIONS)
    assert set(found.values()) == set(forward_scmoe.REGIONS)


def test_claimed_routed_experts_compile_for_v5e_on_one_buffer_where_every_expert_is_held(one_chip, monkeypatch):
    """lfm2-8b-a1b.fwd's expert layer as the pallas executor claims it: 8192
    tokens, 4 choices among 32 experts of 2048 x 1792, all held: one buffer of
    32,768 rows, three megablox calls, no conditional; the tiles along the
    experts' width are 896, which divides 1792. Called as a layer calls it,
    (B, T, C) around it and the residual and the next norm's statistics behind:
    there a sum left open to the caller's fusions kept four float32 converts of
    (8192, 2048) standing alone."""
    import jax
    import jax.numpy as jnp

    from thunder_tpu.core import dtypes
    from thunder_tpu.executors import pallasex

    monkeypatch.setattr(pallasex, "_interpret", lambda: False)
    shapes = {"x": ((8192, 2048), "bfloat16"), "top_i": ((8192, 4), "int32"), "top_w": ((8192, 4), "float32"),
              "w_gate": ((32, 2048, 1792), "bfloat16"), "w_up": ((32, 2048, 1792), "bfloat16"),
              "w_down": ((32, 1792, 2048), "bfloat16")}
    assert pallasex._moe_experts_checker(*(SimpleNamespace(shape=s, dtype=getattr(dtypes, d)) for s, d in shapes.values()))
    assert (pallasex._gmm_tile(1792), pallasex._gmm_tile(2048)) == (896, 1024)
    sds = [jax.ShapeDtypeStruct(s, getattr(jnp, d), sharding=one_chip) for s, d in shapes.values()]

    def layer(x, *routing_and_weights):
        stream = x.reshape(2, 4096, 2048)
        stream = stream + pallasex._moe_experts_impl(x, *routing_and_weights, 0, 32).reshape(stream.shape)
        return stream, jnp.mean(jnp.square(stream.astype(jnp.float32)), -1)

    compiled = jax.jit(layer).lower(*sds).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3 and " conditional(" not in text
    assert "bf16[32768,2048]" in text and "bf16[32768,1792]" in text
    # Each row moves once each way: no float32 copy of the buffer, stacked or not, no relayout to (N, k, C), no fill
    # after a gather; four gathers of (8192, 2048) come back and one fusion reads them.
    written = _arrays_written(text)
    assert not [made for made in written if made[1].startswith("f32[") and made[1].endswith(",2048]")]
    assert not [made for made in written if made[1].endswith("[8192,4,2048]")]
    assert "broadcast_select_fusion" not in dict(written)
    assert written.count(("fusion", "bf16[8192,2048]")) == 4 and written.count(("fusion", "bf16[32768,2048]")) == 1
    assert written.count(("add_convert_fusion", "bf16[8192,2048]")) == 1


ROPE_HEADS_SHAPES = [
    # (B, lane groups of the array, T, lanes), n (0: no rope), first, heads read, scale, heads a lane group, normed
    ((8, 24, 2048, 128), 16, 0, 16, 0.125, 2, False),   # pythia-410m.fwd's q out of the packed projection: two heads of 64 a group
    ((8, 24, 2048, 128), 16, 16, 16, 1.0, 2, False),    # and its k
    ((8, 24, 2048, 128), 64, 0, 16, 0.125, 2, False),   # full rotary on heads of 64 (tinyllama's)
    ((8, 48, 2048, 64), 16, 0, 16, 0.125, 1, False),    # a head of 64 a group, where the counts of heads are odd
    ((8, 48, 2048, 64), 16, 16, 16, 1.0, 1, False),
    ((1, 48, 4096, 128), 128, 0, 32, 128 ** -0.5, 1, False),  # 32 query and 8 key heads of 128, full rotary
    ((1, 48, 4096, 128), 128, 32, 8, 1.0, 1, False),
    ((2, 64, 4096, 192), 64, 0, 64, 192 ** -0.5 * 1.3466 ** 2, 1, False),  # a.x-k1.fwd's q: every head, so in place
    # normed heads (PR 39). trinity-mini.fwd-t32k: 32 query and 4 key heads of 128 at 32,768 positions, a window
    # layer's q and k (normed, roped, q scaled) and the global layer's (normed, no rope)
    ((1, 40, 32768, 128), 128, 0, 32, 128 ** -0.5, 1, True),
    ((1, 40, 32768, 128), 128, 32, 4, 1.0, 1, True),
    ((1, 40, 32768, 128), 0, 0, 32, 128 ** -0.5, 1, True),
    ((1, 40, 32768, 128), 0, 32, 4, 1.0, 1, True),
    # lfm2-8b-a1b.fwd: 32 query and 8 key heads of 64, two a lane group, each half normed by itself
    ((2, 24, 4096, 128), 64, 0, 32, 0.125, 2, True),
    ((2, 24, 4096, 128), 64, 32, 8, 1.0, 2, True),
    ((2, 24, 4096, 128), 16, 0, 32, 0.125, 2, True),    # and under a partial rotary
]


def _heads_call_compiled(monkeypatch, one_chip, call, checker, shape, tables, donate):
    import jax
    import jax.numpy as jnp

    from thunder_tpu.core import dtypes
    from thunder_tpu.executors import pallasex

    proxy = lambda s: SimpleNamespace(shape=s, dtype=dtypes.bfloat16)
    sds = lambda s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
    monkeypatch.setattr(pallasex, "_interpret", lambda: False)
    assert checker(proxy(shape), *(proxy(t) for t in tables))
    compiled = jax.jit(call, donate_argnums=(0,) if donate else ()).lower(sds(shape), *(sds(t) for t in tables)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and " slice(" not in text  # found by the block index
    assert ("output_to_operand_aliasing" in text) == donate
    return compiled


@pytest.mark.parametrize("shape,n,first,heads,scale,split,normed", ROPE_HEADS_SHAPES,
                         ids=[f"{s[-1] // k}-{n}-heads{f}to{f + h}of{s[1] * k}{'-normed' * nd}" for s, n, f, h, _, k, nd in ROPE_HEADS_SHAPES])
def test_rope_on_some_heads_of_a_head_major_array_compiles_for_v5e(one_chip, monkeypatch, shape, n, first, heads, scale, split, normed):
    """``apply_rope_heads`` as transforms/attention_layout.py writes it: one call
    that reads its heads by the block index and writes them scaled, each head
    to a (T, hs) of its own where a group's lanes hold several; normed first
    where the model norms its heads, and not rotated where the layer has no rope."""
    from thunder_tpu.executors import pallasex

    tables = [(shape[-2], n)] * 2 if n else []
    weight = [(shape[-1] // split,)] if normed else []

    def operands(rest):
        cos, sin = rest[:2] if n else (None, None)
        return (cos, sin, first, heads, scale, split), (dict(norm_weight=rest[-1], eps=1e-5) if normed else {})

    def call(x, *rest):
        args, norm = operands(rest)
        return pallasex._rope_heads_impl(x, *args, **norm)

    def checker(x, *rest):
        args, norm = operands(rest)
        return pallasex._rope_heads_checker(x, *args, **norm)

    compiled = _heads_call_compiled(monkeypatch, one_chip, call, checker, shape, tables + weight,
                                    donate=(heads, split) == (shape[1], 1))
    assert compiled.out_info.shape == (shape[0], heads, shape[2], shape[3] // split)
    text = compiled.as_text()
    # one pass: nothing but the kernel touches an array of the heads' size, in float32 or otherwise
    assert "f32[" + ",".join(map(str, shape[:1] + (heads,) + shape[2:3])) not in text
    assert not [line for line in text.splitlines() if " fusion(" in line and f"{shape[2]},{shape[3] // split}]" in line.split(" fusion(")[0]]


def test_split_heads_compiles_for_v5e(one_chip, monkeypatch):
    """pythia-410m.fwd's v: heads 32 to 48 of the 24 groups of two."""
    from thunder_tpu.executors import pallasex

    compiled = _heads_call_compiled(
        monkeypatch, one_chip, lambda x: pallasex._split_heads_impl(x, 32, 16, 2),
        lambda x: pallasex._split_heads_checker(x, 32, 16, 2), (8, 24, 2048, 128), [], donate=False)
    assert compiled.out_info.shape == (8, 16, 2048, 64)


def test_heads_checkers_decline_what_would_not_compile_or_is_not_there():
    from thunder_tpu.core import dtypes
    from thunder_tpu.executors import pallasex

    proxy = lambda s, d=dtypes.bfloat16: SimpleNamespace(shape=s, dtype=d)
    x, t = proxy((8, 24, 2048, 128)), proxy((2048, 16))
    assert pallasex._rope_heads_checker(x, t, t, 16, 16, 1.0, 2) and pallasex._split_heads_checker(x, 32, 16, 2)
    assert not pallasex._split_heads_checker(x, 33, 15, 2)         # a group is read whole
    assert not pallasex._split_heads_checker(x, 32, 18, 2)         # beyond the array's 48 heads
    assert not pallasex._rope_heads_checker(proxy((8, 24, 2048, 128), dtypes.float16), t, t, 0, 16, 1.0, 2)
    assert not pallasex._rope_heads_checker(x, proxy((2048, 16), dtypes.float32), proxy((2048, 16), dtypes.float32), 0, 16, 1.0, 2)
    assert pallasex.heads_per_lane_group(64, 16, 16) == 2 and pallasex.heads_per_lane_group(32, 8, 4) == 4
    assert pallasex.heads_per_lane_group(64, 15, 5) == pallasex.heads_per_lane_group(128, 32, 8) == 1
    assert pallasex.heads_per_lane_group(96, 8, 8) == pallasex.heads_per_lane_group(192, 64) == 1


def test_forward_of_pythia_has_no_layout_copy_in_front_of_attention_on_the_v5e(one_chip, monkeypatch):
    """What the gain of transforms/attention_layout.py rests on is the TPU
    compiler's: a dot whose own output has the head dimension is written
    head-major with no copy. pythia-410m.fwd's program at depth 2, as the cell
    runs it, compiled for the described chip: no copy or scaling of q, k or v
    is left inside a layer, and the executable needs no more than as written."""
    import jax
    import jax.numpy as jnp

    from perfbench import manifest
    from perfbench.jobs import gpt_model
    from perfbench.run import executable_needs
    from thunder_tpu.api import trace_program
    from thunder_tpu.executors import flashex, pallasex
    from thunder_tpu.executors.passes import transform_for_execution
    from thunder_tpu.extend import resolve_executors
    from thunder_tpu.models import gpt
    from thunder_tpu.transforms.attention_layout import FOLDED_TAG, fold_attention_layouts
    from thunder_tpu.transforms.common import dce

    monkeypatch.setattr(pallasex, "_interpret", lambda: False)
    monkeypatch.setattr(flashex, "_interpret", lambda: False)
    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    cell = manifest.load_cell("pythia-410m.fwd")
    keys = manifest.published(cell)
    keys.update(num_hidden_layers=2, reduced=[*keys["reduced"], "num_hidden_layers"])
    cfg = gpt_model.gpt_config(keys)
    shapes = gpt_model.param_shapes(cfg)
    tokens = jax.ShapeDtypeStruct((cell.traffic["batch"], cell.traffic["seq"]), jnp.int32)
    flat = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip) for a in jax.tree_util.tree_leaves((shapes, tokens))]

    def compiled(folded: bool):
        _, trc = trace_program(lambda p, i: gpt.forward(p, i, cfg), (shapes, tokens), {})
        trc = dce(trc)
        if folded:
            trc = fold_attention_layouts(trc, resolve_executors(None))
            assert trc.tags[FOLDED_TAG] == 2
        return jax.jit(transform_for_execution(trc, resolve_executors(None)).python_callable()).lower(*flat).compile()

    def layout_instructions(text):
        """Copies, slices, and scalings by a broadcast constant, that write an array of q's, k's or v's size."""
        made = re.findall(r"^\s*%((?:copy|slice|broadcast_multiply_fusion)[.\d]*) = (bf16\[[\d,]+\])", text[text.index("ENTRY"):], re.M)
        return [name for name, shape in made if shape in ("bf16[8,2048,1024]", "bf16[8,16,2048,64]", "bf16[128,2048,64]")]

    written, folded = compiled(False), compiled(True)
    # as written: 3 slices, 2 head transposes of q and k, q's scaling, a layer; one copy of the embedding outside
    assert len(layout_instructions(written.as_text())) == 2 * 6 + 1
    assert len(layout_instructions(folded.as_text())) == 1
    assert executable_needs(folded)[0] <= executable_needs(written)[0]


def test_forward_of_trinity_norms_its_heads_in_one_pass_on_the_v5e(one_chip, monkeypatch):
    """trinity-mini.fwd-t32k's program at depth 4 (three window layers with rope and the global one without, every
    head normed), as the dispatcher rewrites it, compiled for the described chip. Token-major the heads' norm costs
    each layer a float32 q with the tokens in the lanes, a float32 relayout of it, the mean square, the norm's pass
    and q's scaling, and the same on k (PERF.md, PR 39): none of them is left, the projection is written head-major
    by its own dot, and two calls a layer norm, rope and scale; no family of the benchmark's takes them for its own."""
    import jax
    import jax.numpy as jnp

    from perfbench import kernel_families, manifest
    from perfbench.jobs import gpt_model
    from thunder_tpu.api import trace_program
    from thunder_tpu.executors import flashex, pallasex
    from thunder_tpu.executors.passes import transform_for_execution
    from thunder_tpu.extend import resolve_executors
    from thunder_tpu.models import gpt
    from thunder_tpu.transforms.attention_layout import FOLDED_TAG, fold_attention_layouts
    from thunder_tpu.transforms.common import dce

    monkeypatch.setattr(pallasex, "_interpret", lambda: False)
    monkeypatch.setattr(flashex, "_interpret", lambda: False)
    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    cell = manifest.load_cell("trinity-mini.fwd-t32k")
    keys = manifest.published(cell)
    keys.update(num_hidden_layers=4)
    cfg = gpt_model.gpt_config(keys)
    assert [cfg.layer_mixer(i) for i in range(4)] == ["sliding_attention"] * 3 + ["full_attention"]
    shapes = gpt_model.param_shapes(cfg)
    tokens = jax.ShapeDtypeStruct((cell.traffic["batch"], cell.traffic["seq"]), jnp.int32)
    flat = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip) for a in jax.tree_util.tree_leaves((shapes, tokens))]
    _, trc = trace_program(lambda p, i: gpt.forward(p, i, cfg, last=cell.traffic["last"]), (shapes, tokens), {})
    trc = fold_attention_layouts(dce(trc), resolve_executors(None))
    assert trc.tags[FOLDED_TAG] == 4
    extrace = transform_for_execution(trc, resolve_executors(None))
    assert gpt_model.kernels_claimed(extrace) == 4 * 3 + 2  # q, k and the attention call a layer; two dispatches
    text = jax.jit(extrace.python_callable()).lower(*flat).compile().as_text()
    for gone in ("f32[1,32768,32,128]", "f32[1,32768,4096]", "f32[32,32768]", "f32[1,32768,4,128]", "f32[1,32768,512]", "f32[4,32768]"):
        assert gone not in text, gone
    written = _arrays_written(text)
    assert ("broadcast_multiply_fusion", "bf16[32,32768,128]") not in written          # q's scaling rides on the call
    assert written.count(("convolution_bitcast_fusion", "bf16[1,40,32768,128]")) == 4  # the dot writes head-major itself
    mosaic = re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text, re.M)
    calls = [call for call in (_as_the_trace_names_it(text, name + " ") for name in mosaic) if "custom-call(bf16[1,40,32768,128]" in call]
    assert len(calls) == 8
    # x, the norm's weight, then the tables where the layer ropes
    operands = sorted(re.findall(r"(\w+\[[\d,]*\])\S* %", call.split("custom-call(")[1].split("), custom_call_target")[0]) for call in calls)
    assert operands == [["bf16[1,40,32768,128]", "f32[1,128]"]] * 2 + [["bf16[1,40,32768,128]", "f32[1,128]", "bf16[32768,128]", "bf16[32768,128]"]] * 6


def test_forward_of_minicpm_sala_hands_linear_attention_its_heads_with_no_copy_on_the_v5e(one_chip, monkeypatch):
    """minicpm-sala.fwd-t32k's program at depth 2 (the sparse layer and the first linear one) at the cell's 32,768
    positions, as the dispatcher rewrites it since PR 41, compiled for the described chip. Token-major a linear layer
    pays, on q and on k, a float32 copy turned head-major, a slice converted to float32 and the norm's own pass, and
    a copy of v (PERF.md, PR 41): none is left. The packed projection is written head-major by its dot, two calls
    norm and rope out of it, and ``attn.linear``'s first instructions read their outputs as they lie. The sparse
    layer's site, whose consumer the pass does not know, keeps what it had."""
    import jax
    import jax.numpy as jnp

    from perfbench import manifest
    from perfbench.jobs import gpt_model
    from thunder_tpu.api import trace_program
    from thunder_tpu.executors import flashex, pallasex
    from thunder_tpu.executors.passes import transform_for_execution
    from thunder_tpu.extend import resolve_executors
    from thunder_tpu.models import gpt
    from thunder_tpu.transforms.attention_layout import FOLDED_TAG, fold_attention_layouts
    from thunder_tpu.transforms.common import dce

    monkeypatch.setattr(pallasex, "_interpret", lambda: False)
    monkeypatch.setattr(flashex, "_interpret", lambda: False)
    monkeypatch.setattr(pallasex, "_device_kind", lambda: next(iter(one_chip.device_set)).device_kind)
    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    cell = manifest.load_cell("minicpm-sala.fwd-t32k")
    keys = manifest.published(cell)
    keys.update(num_hidden_layers=2)
    cfg = gpt_model.gpt_config(keys)
    assert [cfg.layer_mixer(i) for i in range(2)] == ["sparse_attention", "linear_attention"]
    shapes = gpt_model.param_shapes(cfg)
    tokens = jax.ShapeDtypeStruct((cell.traffic["batch"], cell.traffic["seq"]), jnp.int32)
    flat = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip) for a in jax.tree_util.tree_leaves((shapes, tokens))]

    def compiled(folded: bool):
        _, trc = trace_program(lambda p, i: gpt.forward(p, i, cfg, last=cell.traffic["last"]), (shapes, tokens), {})
        trc = dce(trc)
        if folded:
            trc = fold_attention_layouts(trc, resolve_executors(None))
            assert trc.tags[FOLDED_TAG] == 1
        extrace = transform_for_execution(trc, resolve_executors(None))
        assert gpt_model.kernels_claimed(extrace) == 3  # the attention over the chosen blocks; q's and k's rope, or norm and rope
        text = jax.jit(extrace.python_callable()).lower(*flat).compile().as_text()
        return text, _arrays_written(text)

    text, written = compiled(False)
    # as written: the sparse layer's q and both of the linear layer's, float32 and turned; v's copies; the norms' passes
    assert written.count(("copy", "f32[1,32768,32,128]")) == 3 and written.count(("copy", "bf16[1,32,32768,128]")) == 2
    assert written.count(("fusion", "bf16[32,32768,128]")) == 2 and written.count(("convolution_bitcast_fusion", "bf16[1,32768,12288]")) == 1
    text, written = compiled(True)
    assert written.count(("copy", "f32[1,32768,32,128]")) == 1 and written.count(("copy", "bf16[1,32,32768,128]")) == 1
    assert written.count(("fusion", "bf16[32,32768,128]")) == 0
    assert written.count(("convolution_bitcast_fusion", "bf16[1,96,32768,128]")) == 1
    assert written.count(("convolution_bitcast_fusion", "bf16[1,32768,12288]")) == 0
    # the two head calls: x, the norm's weight, the tables; what reads them is a bitcast into linear attention's chunks
    entry = text[text.index("ENTRY"):]
    heads = re.findall(r"^\s*(%[\w.\-]+) = bf16\[1,32,32768,128\]\S* custom-call\((%[\w.\-]+), [^\n]*tpu_custom_call", entry, re.M)
    assert len(heads) == 2 and len({packed for _, packed in heads}) == 1
    for name, _ in heads:
        readers = re.findall(r"^\s*(?:ROOT )?%[\w.\-]+ = \S+ ([\w\-]+)\([^\n]*" + re.escape(name) + r"[,)]", entry, re.M)
        assert readers == ["bitcast"], (name, readers)


def _as_the_trace_names_it(text, name_prefix):
    """The instruction whose name starts with ``name_prefix``, its operands with their shapes: the compiled text
    leaves an operand's shape to its own line, the device trace's event names (``kernel_families.match``'s input) do not."""
    shapes = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\S+) ", text, re.M))
    line = next(l.strip() for l in text.splitlines() if l.strip().startswith(name_prefix))
    head, operands, tail = re.match(r"(.*? custom-call\()([^)]*)(\).*)", line).groups()
    operands = re.sub(r"/\*.*?\*/", "", operands)  # "/*index=5*/" before every fifth operand
    return head + ", ".join(f"{shapes[o]} {o}" for o in operands.split(", ")) + tail


def _sparse_attend_compiled(monkeypatch, one_chip, shape, groups, ids, dtype="bfloat16"):
    import jax
    import jax.numpy as jnp

    from thunder_tpu.core import dtypes
    from thunder_tpu.executors import pallasex

    monkeypatch.setattr(pallasex, "_interpret", lambda: False)
    monkeypatch.setattr(pallasex, "_device_kind", lambda: next(iter(one_chip.device_set)).device_kind)
    B, H, T, d = shape
    shapes = [(shape, dtype), ((B, groups, T, d), dtype), ((B, groups, T, d), dtype), ((B, groups, T, ids), "int32")]
    claimed = pallasex._sparse_attend_checker(*(SimpleNamespace(shape=s, dtype=getattr(dtypes, d_)) for s, d_ in shapes), block_size=64)
    sds = [jax.ShapeDtypeStruct(s, getattr(jnp, d_), sharding=one_chip) for s, d_ in shapes]
    return claimed, jax.jit(lambda *a: pallasex._sparse_attend_impl(*a, block_size=64)).lower(*sds)


def test_the_attention_over_the_chosen_blocks_compiles_for_v5e_at_the_cells_shapes(one_chip, monkeypatch):
    """``minicpm-sala.fwd-t32k``'s sparse layer: 32 query heads on 2 key-value heads of 128 at 32,768 positions, 64
    ids a query. One Mosaic call within the VMEM it asks for (k and v of a key-value head whole, 8 MB each), which no
    family of the benchmark's takes for its own (the rope family tells a call by its shapes)."""
    from perfbench import kernel_families
    from thunder_tpu.executors import pallasex

    claimed, lowered = _sparse_attend_compiled(monkeypatch, one_chip, (1, 32, 32768, 128), 2, 64)
    assert claimed
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and "%sparse_attend_fwd" in text
    assert compiled.out_info.shape == (1, 32, 32768, 128)
    call = next(line for line in text.splitlines() if line.strip().startswith("%sparse_attend_fwd"))
    scoped = int(re.search(r'"scoped_memory_configs":\[\{"memory_space":"1","offset":"0","size":"(\d+)"', call).group(1))
    assert scoped == pallasex._ce_vmem_limit() == 64 * 1024 * 1024
    assert pallasex._sparse_attend_vmem(32768, 16, 128, 2) <= 3 * scoped // 4
    named = _as_the_trace_names_it(text, "%sparse_attend_fwd")
    operands = re.findall(r"(\w+)\[[\d,]*\]\S* %", named.split("custom-call(")[1].split("), custom_call_target")[0])
    assert operands == ["s32", "bf16", "bf16", "bf16", "s32"]  # the flags lead: no (x, cos, sin) of the rope family
    assert kernel_families.match(named) is None


SPARSE_ATTEND_SHAPES = {
    # (B, H, T, d), key-value heads, dtype, whether the checker claims it
    "float32": ((1, 32, 8192, 128), 2, "float32", True),
    "a-key-head-a-query-head": ((2, 4, 4096, 128), 4, "bfloat16", True),
    "heads-of-256": ((1, 8, 4096, 256), 2, "bfloat16", True),
    "too-long-for-the-VMEM": ((1, 32, 131072, 128), 2, "bfloat16", False),
    "float32-too-long-for-the-VMEM": ((1, 32, 32768, 128), 2, "float32", False),
    "eight-sequences": ((8, 32, 32768, 128), 2, "bfloat16", True),
    "more-flags-than-the-SMEM-holds": ((4, 64, 16384, 128), 64, "bfloat16", False),  # 262,144: out of SMEM by 1.1 K
}


@pytest.mark.parametrize("shape,groups,dtype,claims", SPARSE_ATTEND_SHAPES.values(), ids=SPARSE_ATTEND_SHAPES)
def test_the_attend_kernels_checker_claims_what_compiles_for_v5e(one_chip, monkeypatch, shape, groups, dtype, claims):
    """float32, a key-value head a query head and wider heads compile; keys and values that the v5e's VMEM does not
    hold twice over, and more flags than a call may keep in SMEM, are ``jaxex``'s loops'."""
    claimed, lowered = _sparse_attend_compiled(monkeypatch, one_chip, shape, groups, 64, dtype)
    assert claimed == claims
    if claims:
        assert lowered.compile().as_text().count('custom_call_target="tpu_custom_call"') == 1


def test_the_attend_kernels_checker_declines_where_the_generation_has_no_room(describe_chip, monkeypatch):
    """A v4 core has 16 MiB: the cell's keys and values do not fit it, 2,048 positions do and compile there."""
    from thunder_tpu.executors import pallasex

    chip = describe_chip("v4:2x2x1")
    assert not _sparse_attend_compiled(monkeypatch, chip, (1, 32, 32768, 128), 2, 64)[0]
    claimed, lowered = _sparse_attend_compiled(monkeypatch, chip, (1, 32, 2048, 128), 2, 64)
    assert claimed and pallasex._ce_vmem_limit() == 16 * 1024 * 1024
    assert lowered.compile().as_text().count('custom_call_target="tpu_custom_call"') == 1


def test_block_sparse_attention_over_three_spans_compiles_for_v5e_as_loops_and_a_kernel_with_no_square_of_the_sequence(one_chip, monkeypatch):
    """``minicpm-sala.fwd-t32k``'s sparse layer at three eighths of its length (12,288 positions, three spans; 32 query
    heads on 2 key-value heads of 128): the selection is ``jaxex``'s, a ``while`` a span and inside it one for the turns
    that take a query's 31 free blocks, with no ``sort`` left of the one a query that ``lax.top_k`` was (PR 36); the
    attention over the chosen blocks is one Mosaic call of ``pallas``, and nothing the compiled program holds has the
    sequence twice among its dimensions or the sequence beside its 767 pooled keys: no score reaches HBM. The
    benchmark's readers find the call in the region it was written in and in no kernel family."""
    import jax
    import jax.numpy as jnp

    import thunder_tpu.torch as ttorch
    from perfbench import kernel_families
    from perfbench.layer_metrics import _regions
    from thunder_tpu.api import trace_program
    from thunder_tpu.executors import pallasex
    from thunder_tpu.executors.passes import transform_for_execution
    from thunder_tpu.extend import resolve_executors
    from thunder_tpu.transforms.common import dce

    monkeypatch.setattr(pallasex, "_interpret", lambda: False)
    monkeypatch.setattr(pallasex, "_device_kind", lambda: next(iter(one_chip.device_set)).device_kind)
    T = 12288
    shapes = [jax.ShapeDtypeStruct((1, heads, T, 128), jnp.bfloat16, sharding=one_chip) for heads in (32, 2, 2)]
    _, comp = trace_program(lambda q, k, v: ttorch.sparse_block_attention(
        q, k, v, kernel_size=32, kernel_stride=16, block_size=64, topk=64, init_blocks=1, local_blocks=32), shapes, {})
    claimed = transform_for_execution(dce(comp), resolve_executors(None))
    owners = [(b.sym.name, b.sym.executor.name) for b in claimed.bound_symbols if b.sym.name.startswith("sparse_block")]
    assert owners == [("sparse_block_select", "jax"), ("sparse_block_attend", "pallas")]
    text = jax.jit(claimed.python_callable()).lower(*shapes).compile().as_text()
    assert text.count(" while(") == 6 and text.count('custom_call_target="tpu_custom_call"') == 1
    assert " sort(" not in text
    dims = [[int(d) for d in m.split(",") if d] for m in re.findall(r"(?:pred|[subf]\d+|bf16)\[([\d,]*)\]", text)]
    assert not [d for d in dims if d.count(T) >= 2 or (T in d and 767 in d)]
    assert not [d for d in dims if d[:2] == [2, 4096] and d[-1] >= 4096]  # the spans' scores, (2, 4096, keys), are gone
    call = next(l.split(" = ")[0].strip().lstrip("%") for l in text.splitlines() if 'custom_call_target="tpu_custom_call"' in l)
    regions = _regions.of_instructions(text)
    assert call.startswith("sparse_attend_fwd") and regions[call] == "attn.sparse.attend"
    assert "attn.sparse.select" in regions.values()
    assert kernel_families.match(_as_the_trace_names_it(text, "%" + call)) is None


WINDOW_SHAPES = [
    # (B, H, T, d), key-value heads, window, key tiles a query tile visits
    ((1, 32, 32768, 128), 4, 2048, 3),  # trinity-mini.fwd-t32k's window layers
    ((1, 32, 16384, 128), 8, 4096, 5),  # mistral-7b's declared window on a sequence past it
    ((2, 8, 4096, 64), 8, 1000, 2),
]


@pytest.mark.parametrize("shape,groups,window,steps", WINDOW_SHAPES, ids=[f"T{s[2]}-W{w}-d{s[3]}" for s, _, w, _ in WINDOW_SHAPES])
def test_attention_within_a_window_compiles_for_v5e_and_the_benchmark_tells_it_from_a_causal_call(one_chip, monkeypatch, shape,
                                                                                               groups, window, steps):
    """``flash`` claims ``torch.window_attention`` where ``pallas`` has not (here by splash's own entry point, whatever
    the shapes) and splash compiles it for the v5e under its local mask: one Mosaic call whose first operand, the mask's
    table, has the key tiles a query tile visits as its last dimension, by which
    the benchmark's family ``attn_window_fwd`` takes it; the causal call at the
    same shapes keeps every key tile there and stays ``flash_fwd``'s. Nothing has
    the sequence twice among its dimensions."""
    import jax
    import jax.numpy as jnp

    from perfbench import flops, flops_window_moe, kernel_families
    from thunder_tpu.core import dtypes
    from thunder_tpu.executors import flashex

    monkeypatch.setattr(flashex, "_interpret", lambda: False)
    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    B, H, T, d = shape
    proxy = lambda h: SimpleNamespace(shape=(B, h, T, d), dtype=dtypes.bfloat16)
    assert flashex._window_checker(proxy(H), proxy(groups), proxy(groups), window=window)
    sds = lambda h: jax.ShapeDtypeStruct((B, h, T, d), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(lambda q, k, v: flashex._window_impl(q, k, v, window=window)).lower(sds(H), sds(groups), sds(groups)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and compiled.out_info.shape == shape
    assert not re.search(rf"\[[\d,]*{T},[\d,]*{T}[\d,]*\]", text)
    tiles = T // flashex._fit_block(T)
    named = _as_the_trace_names_it(text, "%splash_mha_fwd")
    assert f"custom-call(s8[1,{tiles},{steps}]" in named
    hit = kernel_families.match(named)
    assert hit[0] == "attn_window_fwd" and hit[1:] == flops_window_moe.attn_window_fwd([B, H, T, d], [1, tiles, steps])
    assert hit[1] >= flops_window_moe.attention(T, B * H, B * groups, d, window)[0]  # the upper end of what the table allows
    assert hit[1] <= 4.0 * d * B * H * flashex.splash_window_tiles(T, window)  # and never more than the tiles visited
    causal = jax.jit(lambda q, k, v: flashex._sdpa_impl(q, k, v, is_causal=True, enable_gqa=True)).lower(
        sds(H), sds(groups), sds(groups)).compile()
    named = _as_the_trace_names_it(causal.as_text(), "%splash_mha_fwd")
    assert f"custom-call(s8[1,{tiles},{tiles}]" in named
    assert kernel_families.match(named) == ("flash_fwd", *flops.flash_fwd([B, H, T, d]))


OWN_WINDOW_SHAPES = {
    # (B, H, T, d), key-value heads, window
    "trinity-mini.fwd-t32k": ((1, 32, 32768, 128), 4, 2048),
    "a-key-head-a-query-head-heads-of-256": ((2, 4, 8192, 256), 4, 1000),
    "mistral-7b-past-its-window": ((1, 32, 16384, 128), 8, 4096),
    "131072-positions": ((1, 32, 131072, 128), 4, 2048),  # a step holds its window's span, whatever the length
}


@pytest.mark.parametrize("shape,groups,window", OWN_WINDOW_SHAPES.values(), ids=OWN_WINDOW_SHAPES)
def test_the_own_window_kernel_compiles_for_v5e_and_expands_nothing(one_chip, monkeypatch, shape, groups, window):
    """``pallas`` stands in front of ``flash`` and takes ``torch.window_attention`` on bf16 heads of 128 (PR 42): one
    Mosaic call, ``window_attend_fwd``, on k and v as the symbol hands them, (B, G, T, d): nothing in front of it has
    them at the query heads' count, nothing has the sequence twice among its dimensions, the call lives in the default
    scope of VMEM within what the checker reckoned (a step holds its window's span of k and v, not the sequence), keeps
    the symbol's region, and is no family's of the benchmark's (``attn_window_fwd`` is splash's table; the rope family
    tells a call by its shapes). Its two results that nothing writes are q's shape: where k and v expanded stood."""
    import jax
    import jax.numpy as jnp

    import thunder_tpu.torch as ttorch
    from perfbench import kernel_families
    from perfbench.layer_metrics import _regions
    from thunder_tpu.api import trace_program
    from thunder_tpu.core.trace import region
    from thunder_tpu.executors import flashex, pallasex
    from thunder_tpu.executors.passes import transform_for_execution
    from thunder_tpu.extend import resolve_executors
    from thunder_tpu.transforms.common import dce

    monkeypatch.setattr(pallasex, "_interpret", lambda: False)
    monkeypatch.setattr(flashex, "_interpret", lambda: False)
    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    B, H, T, d = shape
    shapes = [jax.ShapeDtypeStruct((B, h, T, d), jnp.bfloat16, sharding=one_chip) for h in (H, groups, groups)]

    def program(q, k, v):
        with region("attn.window"):
            return ttorch.window_attention(q, k, v, window=window, scale=1.0)

    _, comp = trace_program(program, shapes, {})
    claimed = transform_for_execution(dce(comp), resolve_executors(None))
    assert [(b.sym.name, b.sym.executor.name) for b in claimed.bound_symbols if b.sym.name == "window_attention"] == [
        ("window_attention", "pallas")]
    compiled = jax.jit(claimed.python_callable()).lower(*shapes).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and jax.tree_util.tree_leaves(compiled.out_info)[0].shape == shape
    assert not re.search(rf"\[[\d,]*{T},[\d,]*{T}[\d,]*\]", text)
    if groups != H:
        front = text.split("ENTRY")[1].split("%window_attend_fwd")[0].splitlines()[1:]
        wide = [line for line in front if f" = bf16[{B},{H},{T},{d}]" in line]
        assert len(wide) == 1 and " parameter(0)" in wide[0]  # q as it came, and no k or v at its heads' count
        assert " broadcast(" not in text.split("ENTRY")[1]
    call = next(line for line in text.splitlines() if line.strip().startswith("%window_attend_fwd"))
    assert '"scoped_memory_configs":[]' in call  # it asks for no VMEM beyond the default scope, and uses less than it reckoned
    used = int(re.search(r'"used_scoped_memory_configs":\[\{"memory_space":"1","offset":"0","size":"(\d+)"', call).group(1))
    assert used <= pallasex._window_attend_vmem(T, window, H // groups, d, 2) <= 3 * pallasex._SCOPED_VMEM_DEFAULT // 4
    named = _as_the_trace_names_it(text, "%window_attend_fwd")
    operands = re.findall(r"(\w+\[[\d,]*\])\S* %", named.split("custom-call(")[1].split("), custom_call_target")[0])
    assert operands == [f"bf16[{B},{groups},{H // groups},{T},{d}]", f"bf16[{B},{groups},{T},{d}]", f"bf16[{B},{groups},{T},{d}]"]
    assert kernel_families.match(named) is None
    results = re.findall(r"bf16\[[\d,]*\]", call.split(" = ")[1].split(" custom-call(")[0])
    assert results == [f"bf16[{B},{groups},{H // groups},{T},{d}]"] * 3
    name = call.split(" = ")[0].strip().lstrip("%")
    assert _regions.of_instructions(text, ("attn.window", "attn.full"))[name] == "attn.window"


SSM_SCAN_SHAPES = {
    # (B, T, H, P), groups, state, chunk
    "granite-4.0-h-micro.fwd-t16k": ((1, 16384, 64, 64), 1, 128, 256),
    "eight-groups-of-16-heads": ((1, 4096, 128, 64), 8, 128, 256),
    "two-heads-a-group-a-state-of-64-a-batch-of-2": ((2, 512, 4, 64), 2, 64, 128),
    "a-chunk-of-512": ((1, 2048, 32, 64), 1, 128, 512),
}


@pytest.mark.parametrize("shape,groups,state,chunk", SSM_SCAN_SHAPES.values(), ids=SSM_SCAN_SHAPES)
def test_the_state_space_scan_compiles_for_v5e_as_one_call_that_reads_token_major(one_chip, monkeypatch, shape, groups, state, chunk):
    """``pallas`` takes ``torch.ssm_scan`` on bf16 heads of 64 (PR 45): one Mosaic call, ``ssm_scan_fwd``, on x seen as
    (B, T, H P) and dt, B and C as the symbol hands them, token-major; nothing of the decomposition is left (no array
    with a chunk twice among its dimensions, no float32 array of x's size), the call uses no more VMEM than the checker
    reckoned and asks for the generation's half where three quarters of the default scope are short, keeps the
    symbol's region, and is no family's of the benchmark's."""
    import jax
    import jax.numpy as jnp

    import thunder_tpu.torch as ttorch
    from perfbench import kernel_families
    from perfbench.layer_metrics import _regions
    from thunder_tpu.api import trace_program
    from thunder_tpu.core.trace import region
    from thunder_tpu.executors import flashex, pallasex
    from thunder_tpu.executors.passes import transform_for_execution
    from thunder_tpu.extend import resolve_executors
    from thunder_tpu.transforms.common import dce

    monkeypatch.setattr(pallasex, "_interpret", lambda: False)
    monkeypatch.setattr(pallasex, "_device_kind", lambda: next(iter(one_chip.device_set)).device_kind)
    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    (B, T, H, P), f32 = shape, jnp.float32
    like = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    shapes = [like(shape), like((B, T, H), f32), like((H,), f32), like((B, T, groups, state)), like((B, T, groups, state)), like((H,), f32)]

    def program(x, dt, A, Bm, Cm, D):
        with region("ssm.scan"):
            return ttorch.ssm_scan(x, dt, A, Bm, Cm, D, chunk=chunk)

    _, comp = trace_program(program, shapes, {})
    claimed = transform_for_execution(dce(comp), resolve_executors(None))
    assert [(b.sym.name, b.sym.executor.name) for b in claimed.bound_symbols if b.sym.name == "ssm_scan"] == [("ssm_scan", "pallas")]
    with jax.enable_x64(True):  # as the dispatcher's runtime has it: nothing in the kernel may widen an index to 64 bits
        compiled = jax.jit(claimed.python_callable()).lower(*shapes).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and jax.tree_util.tree_leaves(compiled.out_info)[0].shape == shape
    assert not re.search(rf"\[[\d,]*{chunk},[\d,]*{chunk}[\d,]*\]", text) and not re.search(rf"f32\[[\d,]*{T},[\d,]*{H * P}\]|f32\[[\d,]*{H},{P}\]", text)
    call = next(line for line in text.splitlines() if line.strip().startswith("%ssm_scan_fwd"))
    needed = pallasex._ssm_scan_vmem(chunk, H, state, groups, 2)
    asked = re.search(r'"scoped_memory_configs":\[\{"memory_space":"1","offset":"\d+","size":"(\d+)"', call)
    assert (int(asked.group(1)) if asked else 16 * 2 ** 20) == pallasex._ssm_scan_scope(needed) == (
        16 * 2 ** 20 if needed <= 12 * 2 ** 20 else 64 * 2 ** 20)
    used = int(re.search(r'"used_scoped_memory_configs":\[\{"memory_space":"1","offset":"0","size":"(\d+)"', call).group(1))
    assert used <= needed
    named = _as_the_trace_names_it(text, "%ssm_scan_fwd")
    operands = re.findall(r"(\w+\[[\d,]*\])\S* %", named.split("custom-call(")[1].split("), custom_call_target")[0])
    assert operands == [f"bf16[{B},{T},{H * P}]", f"f32[{B},{T},{H}]", f"f32[1,{H}]", f"bf16[{B},{T},{groups * state}]",
                        f"bf16[{B},{T},{groups * state}]", f"f32[{H}]"]
    assert kernel_families.match(named) is None
    name = call.split(" = ")[0].strip().lstrip("%")
    assert _regions.of_instructions(text, ("ssm.conv", "ssm.scan", "ssm.gate_norm"))[name] == "ssm.scan"


def test_the_cell_of_granite_compiles_for_v5e_with_the_scan_in_its_region(one_chip, monkeypatch):
    """granite-4.0-h-micro.fwd-t16k's program at depth 2 (two Mamba-2 layers, 16,384 positions), as the cell's job
    lowers it: a Mosaic call a layer, each in region ``ssm.scan`` where ``ssm_scan_ms`` and ``ssm_scan_roofline`` look for
    the region's instructions, and beside it in that region only what makes dt and A; no float32 array of the
    activation's size is left anywhere in the layer."""
    from perfbench import manifest
    from perfbench.jobs import forward_ssm
    from perfbench.layer_metrics import _regions
    from thunder_tpu.executors import flashex, pallasex

    monkeypatch.setattr(pallasex, "_interpret", lambda: False)
    monkeypatch.setattr(flashex, "_interpret", lambda: False)
    monkeypatch.setattr(pallasex, "_device_kind", lambda: next(iter(one_chip.device_set)).device_kind)
    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    cell = manifest.load_cell("granite-4.0-h-micro.fwd-t16k")
    keys = manifest.published(cell)
    keys.update(num_hidden_layers=2, reduced=[*keys["reduced"], "num_hidden_layers"])
    topo = SimpleNamespace(devices=[next(iter(one_chip.device_set))])
    text = forward_ssm.lower_for(cell, keys, cell.traffic["batch"], cell.traffic["seq"], topo).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    found = _regions.of_instructions(forward_ssm.forward_window_moe.an_instruction_a_line(text), forward_ssm.REGIONS)
    scans = sorted(name for name, where in found.items() if where == "ssm.scan" and name.startswith("ssm_scan_fwd"))
    assert len(scans) == 2 and set(found.values()) == {"ssm.conv", "ssm.scan", "ssm.gate_norm"}
    entry = text.split("ENTRY")[1]
    assert not re.search(r" = f32\[[\d,]*16384,4096\]| = f32\[[\d,]*64,256,64,64\]", entry)  # the decomposition's arrays


SSM_SCAN_PACKED_SHAPES = {
    # (B, T), heads, groups, state, chunk: the packed array is (B, T, 64 heads + 2 groups * state)
    "granite-4.0-h-micro.fwd-t16k": ((1, 16384), 64, 1, 128, 256),       # blocks of 4096, 128, 128 at 0, 32, 33
    "eight-groups-of-16-heads": ((1, 4096), 128, 8, 128, 256),           # blocks of 8192, 1024, 1024 at 0, 8, 9
    "two-groups-on-a-state-of-64-a-batch-of-2": ((2, 512), 4, 2, 64, 128),
}


@pytest.mark.parametrize("bt,heads,groups,state,chunk", SSM_SCAN_PACKED_SHAPES.values(), ids=SSM_SCAN_PACKED_SHAPES)
def test_the_packed_state_space_scan_compiles_for_v5e_and_reads_one_array_by_block_index(one_chip, monkeypatch, bt, heads, groups,
                                                                                         state, chunk):
    """``pallas`` takes ``torch.ssm_scan_packed`` (PR 46): the same Mosaic call, ``ssm_scan_fwd``, whose first, fourth
    and fifth operand are one array, the convolution's ``[x | B | C]`` as it lies; nothing is cut out of it in front of
    the call (no ``slice``, no copy), the call asks for the VMEM the three-array call asks for, and it keeps its region."""
    import jax
    import jax.numpy as jnp

    import thunder_tpu.torch as ttorch
    from perfbench.layer_metrics import _regions
    from thunder_tpu.api import trace_program
    from thunder_tpu.core.trace import region
    from thunder_tpu.executors import pallasex
    from thunder_tpu.executors.passes import transform_for_execution
    from thunder_tpu.extend import resolve_executors
    from thunder_tpu.transforms.common import dce

    monkeypatch.setattr(pallasex, "_interpret", lambda: False)
    monkeypatch.setattr(pallasex, "_device_kind", lambda: next(iter(one_chip.device_set)).device_kind)
    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    (B, T), W, f32 = bt, heads * 64 + 2 * groups * state, jnp.float32
    like = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    shapes = [like((B, T, W)), like((B, T, heads), f32), like((heads,), f32), like((heads,), f32)]

    def program(xbc, dt, A, D):
        with region("ssm.scan"):
            return ttorch.ssm_scan_packed(xbc, dt, A, D, heads=heads, groups=groups, state=state, chunk=chunk)

    _, comp = trace_program(program, shapes, {})
    claimed = transform_for_execution(dce(comp), resolve_executors(None))
    assert [(b.sym.name, b.sym.executor.name) for b in claimed.bound_symbols if "ssm_scan" in b.sym.name] == [("ssm_scan_packed", "pallas")]
    with jax.enable_x64(True):
        compiled = jax.jit(claimed.python_callable()).lower(*shapes).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and jax.tree_util.tree_leaves(compiled.out_info)[0].shape == (B, T, heads, 64)
    entry = text.split("ENTRY")[1]
    assert " slice(" not in entry and " fusion(" not in entry
    call = next(line for line in text.splitlines() if line.strip().startswith("%ssm_scan_fwd"))
    asked = re.search(r'"scoped_memory_configs":\[\{"memory_space":"1","offset":"\d+","size":"(\d+)"', call)
    assert (int(asked.group(1)) if asked else 16 * 2 ** 20) == pallasex._ssm_scan_scope(pallasex._ssm_scan_vmem(chunk, heads, state, groups, 2))
    named = _as_the_trace_names_it(text, "%ssm_scan_fwd")
    operands = re.findall(r"(\w+\[[\d,]*\])\S* (%[\w.\-]+)", named.split("custom-call(")[1].split("), custom_call_target")[0])
    assert [shape for shape, _ in operands] == [f"bf16[{B},{T},{W}]", f"f32[{B},{T},{heads}]", f"f32[1,{heads}]", f"bf16[{B},{T},{W}]",
                                                f"bf16[{B},{T},{W}]", f"f32[{heads}]"]
    # one array, three times, and as the program was handed it
    assert operands[0][1] == operands[3][1] == operands[4][1] and re.search(rf"{re.escape(operands[0][1])} = \S+ parameter\(0\)", entry)
    name = call.split(" = ")[0].strip().lstrip("%")
    assert _regions.of_instructions(text, ("ssm.conv", "ssm.scan", "ssm.gate_norm"))[name] == "ssm.scan"


def test_the_cell_of_granite_as_the_dispatcher_rewrites_it_cuts_nothing_out_in_front_of_its_scans(one_chip, monkeypatch):
    """granite-4.0-h-micro.fwd-t16k's program at depth 2 through ``pipeline.compile_trace``, which is what the
    dispatcher does to a trace (the job's ``lower_for`` leaves the rewrites out and compiles the program as written:
    the test above). As written each layer holds two standalone slices, ``bf16[1,16384,4352]`` out of ``in_proj``'s
    result for the convolution and ``bf16[1,16384,4096]`` out of the convolution's for the scan (PERF.md, PR 46: 30.8
    ms of the cell's 782.7 ms call); rewritten it holds neither, B and C are no results of their own, each scan's first,
    fourth and fifth operand are the convolution's one result, and the convolution's fusion reads ``in_proj``'s."""
    import jax
    import jax.numpy as jnp

    from perfbench import manifest
    from perfbench.jobs import forward_ssm, gpt_model
    from perfbench.layer_metrics import _regions
    from thunder_tpu import pipeline
    from thunder_tpu.api import trace_program
    from thunder_tpu.executors import flashex, pallasex
    from thunder_tpu.extend import resolve_executors
    from thunder_tpu.models import gpt

    monkeypatch.setattr(pallasex, "_interpret", lambda: False)
    monkeypatch.setattr(flashex, "_interpret", lambda: False)
    monkeypatch.setattr(pallasex, "_device_kind", lambda: next(iter(one_chip.device_set)).device_kind)
    monkeypatch.setenv("THUNDER_FLASH_FORCE", "1")
    cell = manifest.load_cell("granite-4.0-h-micro.fwd-t16k")
    keys = manifest.published(cell)
    keys.update(num_hidden_layers=2, reduced=[*keys["reduced"], "num_hidden_layers"])
    cfg = gpt_model.gpt_config(keys)
    assert [cfg.layer_mixer(i) for i in range(2)] == ["mamba"] * 2
    shapes = gpt_model.param_shapes(cfg)
    tokens = jax.ShapeDtypeStruct((cell.traffic["batch"], cell.traffic["seq"]), jnp.int32)
    flat = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip) for a in jax.tree_util.tree_leaves((shapes, tokens))]
    _, trc = trace_program(lambda p, i: gpt.forward(p, i, cfg, last=cell.traffic["last"]), (shapes, tokens), {})
    compiled = pipeline.compile_trace(pipeline.clean(trc)[-1], resolve_executors(None))
    assert compiled.extras["transforms"]["ssm_layouts_folded"] == 2 and gpt_model.kernels_claimed(compiled.claimed) == 2
    text = jax.jit(compiled.claimed.python_callable()).lower(*flat).compile().as_text()
    entry = text.split("ENTRY")[1]
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert not re.search(r" = bf16\[1,16384,(4096|4352|128)\]\S* slice\(", entry)
    written = _arrays_written(text)
    assert written.count(("multiply_convert_fusion", "bf16[1,16384,4352]")) == 2, written
    assert not [w for w in written if w[1] == "bf16[1,16384,128]"]
    found = _regions.of_instructions(forward_ssm.forward_window_moe.an_instruction_a_line(text), forward_ssm.REGIONS)
    scans = sorted(name for name, where in found.items() if where == "ssm.scan" and name.startswith("ssm_scan_fwd"))
    assert len(scans) == 2
    for name in scans:
        named = _as_the_trace_names_it(entry, f"%{name} ")
        operands = re.findall(r"(\w+\[[\d,]*\])\S* (%[\w.\-]+)", named.split("custom-call(")[1].split("), custom_call_target")[0])
        assert [shape for shape, _ in operands][::3] == ["bf16[1,16384,4352]"] * 2 and operands[0] == operands[3] == operands[4]
        conv = next(line for line in entry.splitlines() if line.strip().startswith(operands[0][1] + " = "))
        assert found[operands[0][1].lstrip("%")] == "ssm.conv" and "fusion(%convolution_bitcast_fusion" in conv
