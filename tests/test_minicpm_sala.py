"""MiniCPM-SALA's blocks at test size on the CPU, float32, seeded weights:
block-sparse attention whose blocks each query chooses by scoring pooled keys,
linear attention with a decay a head, each with its own head layout and rope
setting, output gates and an output norm, the MiniCPM family's scalings, and a
head on the last positions. Against the plain reference
(``perfbench/reference/minicpm_sala.py``), which knows nothing of the program,
and against loops over single queries written here."""

import dataclasses
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

import thunder_tpu
import thunder_tpu.torch as ttorch
from thunder_tpu.core import dtypes
from thunder_tpu.models import gpt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

with open(os.path.join(REPO, "perfbench", "configs", "minicpm-sala.json"), encoding="utf-8") as _f:
    _FILE = json.load(_f)
# The stand-in (``--rehearse``'s sizes): sparse, linear, linear, linear; 256 wide, heads of 64, 4 query heads on
# one key-value head in the sparse layer; blocks of 16 keys, 6 a query, 3 of them forced; dense under 64 positions.
KEYS = {**_FILE, **_FILE["stand_in"]}
SPARSE = dict(kernel_size=8, kernel_stride=4, block_size=16, topk=6, init_blocks=1, local_blocks=2)
HP = {**SPARSE, "matmul_inputs": None}
T = 256


def built(keys=KEYS, seed=5):
    """(the program's config, its parameters, the same numbers stacked for the reference)."""
    import jax

    from perfbench import weights
    from perfbench.jobs import gpt_model

    cfg = gpt_model.gpt_config(keys, rehearse=True)
    shapes = jax.eval_shape(lambda: gpt.init_params(cfg, dtype=dtypes.float32, device_init=True))
    return cfg, weights.make_system_weights(shapes, seed), weights.make_reference_weights(shapes, seed)


def batch(t=T, seed=0, b=1):
    return np.random.RandomState(seed).randint(0, KEYS["vocab_size"], (b, t)).astype(np.int32)


def qkv(t, heads=4, groups=1, d=16, seed=0):
    rng = np.random.RandomState(seed)
    return (rng.randn(1, heads, t, d).astype(np.float32), rng.randn(1, groups, t, d).astype(np.float32),
            rng.randn(1, groups, t, d).astype(np.float32))


def rel(got, want):
    return float(np.linalg.norm(np.asarray(got, np.float64) - want) / np.linalg.norm(want))


# -----------------------------------------------------------------------------
# The model
# -----------------------------------------------------------------------------


def test_the_registry_lists_the_model_at_its_published_sizes():
    """Every published key of the configuration file is the registry's: the
    benchmark lays only the cut in depth over the entry, the layer pattern it
    runs is the first 10 of the published list, and the decay and the residual
    scale keep the published depth."""
    from perfbench import manifest
    from perfbench.jobs import forward_sparse_linear, gpt_model

    cell = manifest.load_cell("minicpm-sala.fwd-t32k")
    cfg = gpt_model.gpt_config(manifest.published(cell))
    listed = gpt.name_to_config("MiniCPM-SALA")
    assert cfg == dataclasses.replace(listed, n_layer=10)
    assert (listed.n_layer, listed.n_embd, listed.n_head, listed.query_groups, listed.head_size) == (32, 4096, 32, 2, 128)
    assert (listed.linear_heads, listed.linear_groups, listed.intermediate_size) == (32, 32, 16384)
    assert (listed.padded_vocab_size, listed.block_size, listed.tie_embeddings) == (73448, 524288, False)
    assert listed.layer_types == tuple(forward_sparse_linear.MIXERS[m] for m in _FILE["mixer_types"])
    assert [listed.layer_types.count(k) for k in ("sparse_attention", "linear_attention")] == [8, 24]
    assert [i for i, k in enumerate(listed.layer_types) if k == "sparse_attention"] == [0, 9, 16, 17, 22, 29, 30, 31]
    assert [cfg.layer_mixer(i) for i in range(10)] == ["sparse_attention"] + ["linear_attention"] * 8 + ["sparse_attention"]
    assert (listed.embedding_scale, listed.logit_divisor) == (_FILE["scale_emb"], 4096 / _FILE["dim_model_base"])
    assert listed.residual_scale == pytest.approx(_FILE["scale_depth"] / 32 ** 0.5)
    assert (listed.attn_rope, listed.linear_rope, listed.qk_norm) == (False, True, True)
    sparse = {f: getattr(listed, g) for f, g in forward_sparse_linear.SPARSE_FIELDS.items()}
    assert sparse == _FILE["sparse_config"]
    # the decay of the cut model is the published model's: layer 9 of 32, not of 10
    assert cfg.linear_decay(1) == listed.linear_decay(1) and len(cfg.linear_decay(1)) == 32
    assert cfg.linear_decay(1)[0] == pytest.approx(2 ** (-8 / 32) * (1 - 1 / 31 + 1e-5))
    assert cfg.linear_decay(8)[31] == pytest.approx(2 ** -8 * (1 - 8 / 31 + 1e-5))
    # every default is yesterday's program: no scaling, roped attention, no gate
    plain = gpt.name_to_config("mistral-7b")
    assert (plain.embedding_scale, plain.residual_scale, plain.logit_divisor, plain.attn_rope, plain.attn_output_gate) \
        == (1.0, 1.0, 1.0, True, False)


def test_the_parameter_tree_has_each_kinds_leaves_at_each_kinds_head_layout():
    import jax

    cfg = dataclasses.replace(gpt.name_to_config("MiniCPM-SALA"), n_layer=2)
    shapes = jax.eval_shape(lambda: gpt.init_params(cfg, device_init=True))
    sparse, linear = shapes["blocks"][0]["sparse_attn"], shapes["blocks"][1]["linear_attn"]
    assert sparse["qkv_w"].shape == ((32 + 2 * 2) * 128, 4096) and linear["qkv_w"].shape == (3 * 32 * 128, 4096)
    assert sorted(sparse) == ["gate_w", "k_norm", "proj_w", "q_norm", "qkv_w"]
    assert sorted(linear) == ["gate_w", "k_norm", "out_norm", "proj_w", "q_norm", "qkv_w"]
    assert linear["out_norm"]["weight"].shape == (4096,) and sparse["q_norm"]["weight"].shape == (128,)
    assert shapes["lm_head_w"].shape == shapes["wte"].shape == (73448, 4096)
    count = lambda tree: sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(tree))
    assert count(shapes["blocks"][0]) == pytest.approx(253.8e6, rel=1e-3)   # ISSUE 33's reckoning
    assert count(shapes["blocks"][1]) == pytest.approx(285.2e6, rel=1e-3)


@pytest.mark.parametrize("t", [T, 200, 48], ids=["sparse-256", "sparse-200", "dense-under-64"])
def test_forward_through_jit_agrees_with_the_reference(t):
    import jax.numpy as jnp

    from perfbench.reference import minicpm_sala

    cfg, params, stacked = built()
    idx = batch(t, b=2)
    jfn = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))
    got, want = np.asarray(jfn(params, idx)), np.asarray(minicpm_sala.forward(stacked, jnp.asarray(idx), KEYS))
    assert got.shape == (2, t, KEYS["vocab_size"])
    assert rel(got, want) < 2e-5
    names = [b.sym.name for b in thunder_tpu.last_traces(jfn)[-1].bound_symbols]
    assert ("topk" in names) == (t >= 64)  # under dense_len no block is chosen: plain causal attention


def test_forward_last_is_the_last_rows_of_forward():
    cfg, params, _ = built()
    idx = batch(b=2)
    whole = np.asarray(thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))(params, idx))
    last = np.asarray(thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg, last=24))(params, idx))
    assert last.shape == (2, 24, KEYS["vocab_size"])
    np.testing.assert_allclose(last, whole[:, -24:], rtol=1e-5, atol=1e-6)
    tiny = gpt.name_to_config("llama-tiny")  # and on a model without any of this
    p = gpt.init_params(tiny, dtype=dtypes.float32)
    ids = np.random.RandomState(1).randint(0, 96, (2, 32)).astype(np.int32)
    np.testing.assert_allclose(np.asarray(thunder_tpu.jit(lambda p, i: gpt.forward(p, i, tiny, last=5))(p, ids)),
                               np.asarray(thunder_tpu.jit(lambda p, i: gpt.forward(p, i, tiny))(p, ids))[:, -5:],
                               rtol=1e-5, atol=1e-6)


def test_the_models_regions_are_named_in_the_generated_program_and_in_the_hlo():
    import jax

    from perfbench.layer_metrics import _regions

    cfg, params, _ = built()
    idx = batch()
    jfn = thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg))
    jfn(params, idx)
    run = thunder_tpu.last_traces(jfn)[-1]
    opened = [line.strip() for line in run.python().splitlines() if line.strip().startswith("with __region(")]
    norm = ["with __region('attn.qk_norm'):"]
    # since PR 41 a linear layer's k and q are normed and roped out of the head-major projection by a call each, v's
    # split (in no region) between them; the sparse layer's norms stand as written, side by side
    assert opened == norm + ["with __region('attn.sparse.select'):", "with __region('attn.sparse.attend'):"] \
        + (norm * 2 + ["with __region('attn.linear'):"]) * 3
    compiled = jax.jit(run.python_callable()).lower(*jax.tree_util.tree_leaves((params, idx))).compile()
    found = _regions.of_instructions(compiled.as_text())
    assert set(found.values()) == set(_regions.REGIONS)


def test_sparse_selection_counts_are_the_blocks_each_tile_of_queries_chose():
    cfg, params, _ = built()
    idx = batch(b=2)
    counts = np.asarray(thunder_tpu.jit(lambda p, i: gpt.sparse_selection_counts(p, i, cfg))(params, idx))
    assert counts.shape == (1, 2, 1, T // gpt.SPARSE_TILE)  # sparse layers, batch, key-value heads, tiles
    # a tile of 128 queries spans 8 blocks of 16: its queries' own, and of the 8 before them what they chose
    assert (counts[..., 0] == 8).all() and (counts[..., 1] > 8 + 1).all() and (counts <= T // 16).all()
    q, k, _ = qkv(T, d=64)
    ids = np.asarray(thunder_tpu.jit(lambda q, k: ttorch.sparse_block_select(q, k, **SPARSE))(q, k))
    want = [len(set(ids[0, 0, t0:t0 + 128].ravel()) - {-1}) for t0 in (0, 128)]
    got = np.asarray(thunder_tpu.jit(lambda i: gpt._tile_union(i, T // 16))(ids))
    assert got[0, 0].tolist() == want


# -----------------------------------------------------------------------------
# The composites against loops over single queries
# -----------------------------------------------------------------------------


def select_by_loops(q, k, kernel_size, kernel_stride, block_size, topk, init_blocks, local_blocks):
    """Steps 1 to 5 a query at a time, numpy float64: q (H, T, d), k (G, T, d) -> ids (G, T, topk), -1 padded."""
    H, T_, d = q.shape
    G = k.shape[0]
    q, k = q.astype(np.float64), k.astype(np.float64)
    pools = (T_ - kernel_size) // kernel_stride + 1
    pooled = np.stack([k[:, kernel_stride * j:kernel_stride * j + kernel_size].mean(1) for j in range(pools)], 1)
    out = -np.ones((G, T_, topk), np.int64)
    for g in range(G):
        for t in range(T_):
            past = [j for j in range(pools) if kernel_stride * j + kernel_size <= t + 1]
            P = np.zeros(pools)
            for h in range(g * H // G, (g + 1) * H // G):
                if past:
                    s = pooled[g, past] @ q[h, t] / np.sqrt(d)
                    e = np.exp(s - s.max())
                    P[past] += e / e.sum()
            own = t // block_size
            score = []
            for b in range(own + 1):
                over = [j for j in range(pools) if kernel_stride * j < block_size * (b + 1)
                        and kernel_stride * j + kernel_size > block_size * b]
                forced = b < init_blocks or b > own - local_blocks
                score.append(np.inf if forced else max((P[j] for j in over), default=0.0))
            order = sorted(range(own + 1), key=lambda b: (-score[b], b))[:topk]  # the lower block on a tie
            out[g, t, :len(order)] = order
    return out


def attend_by_loops(q, k, v, ids, block_size):
    H, T_, d = q.shape
    G = k.shape[0]
    out = np.zeros((H, T_, d))
    for h in range(H):
        g = h * G // H
        for t in range(T_):
            keys = [s for s in range(t + 1) if s // block_size in set(ids[g, t].tolist())]
            s = k[g, keys].astype(np.float64) @ q[h, t] / np.sqrt(d)
            e = np.exp(s - s.max())
            out[h, t] = (e / e.sum()) @ v[g, keys]
    return out


@pytest.mark.parametrize("t", [40, 100, 131], ids=lambda t: f"T{t}")
@pytest.mark.parametrize("chunks", [(1024, 256), (48, 24)], ids=["one-chunk", "chunks-of-48-and-24"])
def test_sparse_block_attention_against_loops_over_single_queries(t, chunks):
    """Under and over ``topk`` blocks, at a T no chunk divides, at two chunk sizes: the same ids, the same output."""
    q, k, v = qkv(t, heads=4, groups=2)
    select, attend = chunks
    ids = np.asarray(thunder_tpu.jit(lambda q, k: ttorch.sparse_block_select(q, k, query_chunk=select, **SPARSE))(q, k))
    want = select_by_loops(q[0], k[0], **SPARSE)
    assert ids.shape == (1, 2, t, 6) and ids.dtype == np.int32
    chosen = lambda a: [[sorted(set(row.tolist()) - {-1}) for row in head] for head in a]
    assert chosen(ids[0]) == chosen(want)
    np.testing.assert_array_equal(ids[0], want)  # and in the same order: best first, the lower block on a tie
    out = np.asarray(thunder_tpu.jit(lambda q, k, v, i: ttorch.sparse_block_attend(
        q, k, v, i, block_size=16, query_chunk=attend))(q, k, v, ids))
    np.testing.assert_allclose(out[0], attend_by_loops(q[0], k[0], v[0], want, 16), rtol=2e-4, atol=2e-5)
    whole = np.asarray(thunder_tpu.jit(lambda q, k, v: ttorch.sparse_block_attention(q, k, v, **SPARSE))(q, k, v))
    np.testing.assert_allclose(whole, out, rtol=1e-5, atol=1e-6)


def test_the_reference_chooses_and_attends_as_the_loops_do():
    import jax.numpy as jnp

    from perfbench.reference import minicpm_sala

    q, k, v = qkv(131, heads=4, groups=2)
    out, ids = minicpm_sala.sparse_attention(jnp.asarray(q[0]), jnp.asarray(k[0]), jnp.asarray(v[0]), HP)
    want = select_by_loops(q[0], k[0], **SPARSE)
    np.testing.assert_array_equal(np.asarray(ids), want)
    np.testing.assert_allclose(np.asarray(out), attend_by_loops(q[0], k[0], v[0], want, 16), rtol=2e-4, atol=2e-5)


def _sparse_owners(jfn):
    """{symbol: executor} of the sparse halves a compiled function's last trace holds."""
    return {b.sym.name: b.sym.executor.name for b in thunder_tpu.last_traces(jfn)[-1].bound_symbols
            if b.sym.name.startswith("sparse_block")}


def test_on_whole_spans_the_xla_executor_runs_the_same_passes_as_loops(monkeypatch):
    """``jaxex`` claims both halves where the sequence is two or more whole spans
    and the caller leaves the chunking open, and gives the decomposition's ids
    and output; anything else is the decomposition's."""
    from thunder_tpu.executors import jaxex

    q, k, v = qkv(T, heads=4, groups=2)
    for name, value in (("SPARSE_LOOP_SPAN", 64), ("SPARSE_LOOP_SELECT", 32), ("SPARSE_LOOP_ATTEND", 16)):
        monkeypatch.setattr(jaxex, name, value)
    whole = thunder_tpu.jit(lambda q, k, v: ttorch.sparse_block_attention(q, k, v, **SPARSE))
    out = np.asarray(whole(q, k, v))
    assert _sparse_owners(whole) == {"sparse_block_select": "jax", "sparse_block_attend": "jax"}
    ids = np.asarray(thunder_tpu.jit(lambda q, k: ttorch.sparse_block_select(q, k, **SPARSE))(q, k))
    unrolled = np.asarray(thunder_tpu.jit(lambda q, k: ttorch.sparse_block_select(q, k, query_chunk=48, **SPARSE))(q, k))
    np.testing.assert_array_equal(ids, unrolled)
    want = np.asarray(thunder_tpu.jit(lambda q, k, v, i: ttorch.sparse_block_attend(
        q, k, v, i, block_size=16, query_chunk=48))(q, k, v, unrolled))
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
    # not whole spans, a single span, or a chunking the caller chose: the decomposition
    assert not jaxex._sparse_loop_checker(q[:, :, :200], k[:, :, :200])
    assert not jaxex._sparse_loop_checker(q[:, :, :64], k[:, :, :64])
    assert jaxex._sparse_loop_checker(q, k) and not jaxex._sparse_loop_checker(q, k, query_chunk=64)
    short = thunder_tpu.jit(lambda q, k, v: ttorch.sparse_block_attention(q, k, v, **SPARSE))
    short(q[:, :, :200], k[:, :, :200], v[:, :, :200])
    assert "sparse_block_select" not in [b.sym.name for b in thunder_tpu.last_traces(short)[-1].bound_symbols]


# -----------------------------------------------------------------------------
# The last step of a pass in the loop form: the best blocks without a sort of them all
# -----------------------------------------------------------------------------


def _random_scores(t, seed):
    q, k, _ = qkv(t, heads=4, groups=2, seed=seed)
    return q, k


def _queries_of_zeros(t, seed):
    """Every pooled key in a query's past scores alike: every free block ties, at every query."""
    q, k = _random_scores(t, seed)
    return np.zeros_like(q), k


def _keys_repeated(t, seed):
    """Small whole numbers, so that every product and pooled mean is exact, and every block of keys a copy of one
    of three: whole blocks tie, at the threshold too, in float32 as in the loops' float64."""
    rng = np.random.RandomState(seed)
    q = rng.randint(-2, 3, (1, 4, t, 16)).astype(np.float32)
    base = rng.randint(-2, 3, (1, 2, 3, 16, 16)).astype(np.float32)
    k = base[:, :, rng.randint(0, 3, -(-t // 16))].reshape(1, 2, -1, 16)[:, :, :t]
    return q, np.ascontiguousarray(k)


def _one_pooled_key_takes_all(t, seed):
    """Scores whole multiples of 1,024 apart: each head's softmax is 1 / m on its m best pooled keys and exactly
    0.0 on the rest, in float32 and in float64, so most blocks score 0.0 and the threshold of most rows is 0.0."""
    q, k = _keys_repeated(t, seed)
    return q * 32768.0, k


SELECT_DATA = {"random": _random_scores, "queries-of-zeros": _queries_of_zeros, "keys-repeated": _keys_repeated,
               "threshold-of-zero": _one_pooled_key_takes_all}
# (span, queries a pass, T): the blocks a span's passes see are 4, 8, 12, 16; 3, 6, 9, 12, 15 (under topk, topk itself, and
# counts no power of two divides); 2, 4, 6, 8, 10, 12 (three spans with no more blocks than topk)
SELECT_SIZES = {"spans-of-64": (64, 32, 256), "spans-of-48": (48, 24, 240), "spans-of-32": (32, 16, 192)}


@pytest.mark.parametrize("sizes", SELECT_SIZES)
@pytest.mark.parametrize("data", SELECT_DATA)
def test_the_loop_form_chooses_the_array_the_decomposition_and_the_loops_choose(monkeypatch, data, sizes):
    """``jaxex``'s passes end in a selection that sorts nothing it rejects: the whole (B, G, T, topk) array is the
    decomposition's and the loops', place for place, where scores tie, where the threshold is 0.0, where a query has
    fewer than ``topk`` blocks in its past and where it has exactly ``topk``."""
    from thunder_tpu.executors import jaxex

    span, select, t = SELECT_SIZES[sizes]
    monkeypatch.setattr(jaxex, "SPARSE_LOOP_SPAN", span)
    monkeypatch.setattr(jaxex, "SPARSE_LOOP_SELECT", select)
    q, k = SELECT_DATA[data](t, seed=len(data) + span)
    loops = thunder_tpu.jit(lambda q, k: ttorch.sparse_block_select(q, k, **SPARSE))
    ids = np.asarray(loops(q, k))
    assert _sparse_owners(loops) == {"sparse_block_select": "jax"}
    unrolled = np.asarray(thunder_tpu.jit(lambda q, k: ttorch.sparse_block_select(q, k, query_chunk=40, **SPARSE))(q, k))
    want = select_by_loops(q[0], k[0], **SPARSE)
    assert ids.shape == (1, 2, t, 6) and ids.dtype == np.int32
    np.testing.assert_array_equal(ids, unrolled)
    np.testing.assert_array_equal(ids[0], want)
    # the cases are what they say: -1s in the first blocks and none after, the first span all forced or short
    assert (ids[0, :, :16 * 5] == -1).any() and (ids[0, :, 16 * 5:] >= 0).all()
    if data == "queries-of-zeros":       # the free blocks chosen are the lowest: 1, 2, 3
        assert (ids[0, :, 16 * 6:, 3:] == [1, 2, 3]).all()
    if data == "threshold-of-zero":      # a row that ends in the lowest free blocks in order took them at a score of 0.0
        assert (ids[0, :, 16 * 8:, 4:] == [1, 2]).all(-1).any() or (ids[0, :, 16 * 8:, 3:] == [1, 2, 3]).all(-1).any()


@pytest.mark.parametrize("forced", [(2, 3), (2, 4), (3, 1), (1, 5)], ids=lambda f: f"{f[0]}-first-and-{f[1]}-local")
@pytest.mark.parametrize("data", ["random", "queries-of-zeros"])
def test_the_loop_form_places_other_counts_of_forced_blocks_as_the_loops_do(monkeypatch, data, forced):
    """The loop form writes the forced blocks by their numbers and takes turns for the rest only: with more
    than one first block, with as many forced as ``topk`` (no turn at all), and in spans that hold fewer
    blocks than are forced, the array is still the decomposition's and the loops'."""
    from thunder_tpu.executors import jaxex

    monkeypatch.setattr(jaxex, "SPARSE_LOOP_SPAN", 48)
    monkeypatch.setattr(jaxex, "SPARSE_LOOP_SELECT", 24)
    consts = {**SPARSE, "init_blocks": forced[0], "local_blocks": forced[1]}
    q, k = SELECT_DATA[data](240, seed=sum(forced))
    loops = thunder_tpu.jit(lambda q, k: ttorch.sparse_block_select(q, k, **consts))
    ids = np.asarray(loops(q, k))
    assert _sparse_owners(loops) == {"sparse_block_select": "jax"}
    np.testing.assert_array_equal(ids, np.asarray(thunder_tpu.jit(
        lambda q, k: ttorch.sparse_block_select(q, k, query_chunk=40, **consts))(q, k)))
    np.testing.assert_array_equal(ids[0], select_by_loops(q[0], k[0], **consts))


@pytest.mark.parametrize("nb,k,own0,forced", [(48, 48, 0, (1, 32)), (64, 64, 10, (1, 32)), (200, 64, 150, (1, 32)),
                                              (512, 64, 504, (1, 32)), (16, 6, 0, (1, 2)), (7, 6, 3, (1, 2)),
                                              (6, 6, 2, (1, 2)), (4, 4, 0, (1, 2)), (12, 6, 8, (3, 5))],
                         ids=lambda v: "-".join(map(str, v)) if isinstance(v, tuple) else str(v))
@pytest.mark.parametrize("scores", ["random", "quarters", "zeros"])
def test_the_best_in_turn_are_top_k_s(nb, k, own0, forced, scores):
    """The step alone against ``lax.top_k`` on rows as a pass makes them (+inf on the forced blocks, -inf after
    the query's own, nothing negative else): every id in every place, with ties and with rows short of ``k``."""
    import jax.numpy as jnp
    from jax import lax

    from thunder_tpu.executors import jaxex

    rng = np.random.RandomState(nb + k)
    s = {"random": lambda: rng.rand(2, 3, 64, nb) * 3, "quarters": lambda: np.round(rng.rand(2, 3, 64, nb) * 12) / 4,
         "zeros": lambda: np.zeros((2, 3, 64, nb))}[scores]().astype(np.float32)
    block = 4
    b, own = np.arange(nb)[None, :], ((own0 * block + np.arange(64)) // block)[:, None]
    s = np.where(b > own, -np.inf, np.where((b < forced[0]) | (b > own - forced[1]), np.inf, s)).astype(np.float32)
    best, want = lax.top_k(jnp.asarray(s), k)
    want = np.where(np.asarray(best) > -np.inf, np.asarray(want), -1)
    got = np.asarray(jaxex._best_in_turn(jnp.asarray(s), k))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold (loop bodies, branches, calls)."""
    import jax

    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def _widest_sort(fn, *args):
    """The widest row any ``top_k``, ``approx_top_k`` or ``sort`` equation of ``fn``'s jaxpr orders; 0 with none."""
    import jax

    widest = 0
    for eqn in _equations(jax.make_jaxpr(fn)(*args).jaxpr):
        if eqn.primitive.name in ("top_k", "approx_top_k"):
            widest = max(widest, eqn.invars[0].aval.shape[-1])
        elif eqn.primitive.name == "sort":
            widest = max(widest, eqn.invars[0].aval.shape[eqn.params["dimension"]])
    return widest


def test_the_loop_form_sorts_no_row_wider_than_topk(monkeypatch):
    """The full sort a query (196 of 1,668 ms a call on the chip, PR 36) cannot come back unnoticed on a CPU:
    no ``top_k`` or ``sort`` in the traced loops, their bodies included, orders more than ``topk`` candidates."""
    import jax.numpy as jnp
    from jax import lax

    from thunder_tpu.executors import jaxex

    monkeypatch.setattr(jaxex, "SPARSE_LOOP_SPAN", 64)
    monkeypatch.setattr(jaxex, "SPARSE_LOOP_SELECT", 32)
    q, k = _random_scores(T, 0)
    assert _widest_sort(lambda q, k: jaxex._sparse_block_select_loops(q, k, **SPARSE), q, k) <= SPARSE["topk"]
    # and the walk does see one inside a loop's body: the last step as it was
    before = lambda s: lax.map(lambda row: lax.top_k(row, 6)[1], s)
    assert _widest_sort(before, jnp.zeros((4, 32, 16))) == 16
    assert _widest_sort(lambda s: lax.map(lambda row: lax.sort(row, dimension=0), s), jnp.zeros((4, 32, 16))) == 32


# -----------------------------------------------------------------------------
# The attention over the chosen blocks as a Pallas kernel (interpret mode here)
# -----------------------------------------------------------------------------

KERNEL_TILES = (32, 64)  # queries and keys a tile at test size: 8 query tiles and 4 key tiles of 4 blocks in 256 positions


@pytest.fixture
def pallasex(monkeypatch):
    """The executor's module with the kernel's tiles at test size."""
    from thunder_tpu.executors import pallasex

    monkeypatch.setattr(pallasex, "_SPARSE_ATTEND_TILES", KERNEL_TILES)
    return pallasex


def _attend(**how):
    return thunder_tpu.jit(lambda q, k, v, i: ttorch.sparse_block_attend(q, k, v, i, block_size=16, **how))


def _proxies(*arrays):
    """What a checker sees of float32 arrays and int32 ids."""
    return [SimpleNamespace(shape=a.shape, dtype=dtypes.int32 if a.dtype == np.int32 else dtypes.float32) for a in arrays]


def _ids_selected(q, k, **over):
    """What the selection gives: fewer than ``topk`` blocks (-1) for the first 80 queries."""
    return np.asarray(thunder_tpu.jit(lambda q, k: ttorch.sparse_block_select(q, k, **{**SPARSE, **over}))(q, k))


def _ids_forced_only(q, k):
    return _ids_selected(q, k, topk=SPARSE["init_blocks"] + SPARSE["local_blocks"])


def _ids_disjoint_then_all(q, k):
    """Every query its own block and the one before; the queries of tile 6 (192 to 223) their own and block 1 or block
    9 by turns, so that between them they leave key tile 1 (blocks 4 to 7) out; those of tile 7 every block."""
    G, T_ = k.shape[1], q.shape[2]
    ids = -np.ones((1, G, T_, 16), np.int32)
    for t in range(T_):
        own = t // 16
        chosen = [own, own - 1] if own else [own]
        if 192 <= t < 224:
            chosen = [own, 1 if t % 2 else 9]
        elif t >= 224:
            chosen = list(range(own, -1, -1))
        ids[:, :, t, :len(chosen)] = chosen
    return ids


KERNEL_CASES = [
    # heads, key-value heads, dtype, the ids
    (32, 2, "float32", _ids_selected),          # R = 16: MiniCPM-SALA's grouping
    (32, 2, "bfloat16", _ids_selected),
    (32, 2, "bfloat16", _ids_disjoint_then_all),
    (2, 2, "float32", _ids_selected),           # R = 1
    (2, 2, "bfloat16", _ids_forced_only),
    (2, 2, "float32", _ids_forced_only),
    (2, 2, "float32", _ids_disjoint_then_all),
    (4, 1, "bfloat16", _ids_selected),
]


@pytest.mark.parametrize("heads,groups,dtype,make_ids", KERNEL_CASES,
                         ids=[f"{h}on{g}-{d}-{m.__name__[5:]}" for h, g, d, m in KERNEL_CASES])
def test_the_attend_kernel_against_the_decomposition_and_loops_over_single_queries(pallasex, heads, groups, dtype, make_ids):
    """``pallas`` claims ``sparse_block_attend`` at heads of 128 on whole tiles and gives what the decomposition and
    the loops give, whatever the ids: with -1 among them, the forced blocks alone, a key tile that no query of a query
    tile chose (skipped whole) and a query tile that chose every block."""
    import jax.numpy as jnp

    q, k, v = qkv(T, heads=heads, groups=groups, d=128, seed=heads)
    ids = make_ids(q, k)
    if dtype == "bfloat16":  # the loops read what the kernel reads
        q, k, v = (np.asarray(jnp.asarray(a, jnp.bfloat16)) for a in (q, k, v))
    kernel = _attend()
    got = np.asarray(kernel(q, k, v, ids))
    owners = {b.sym.name: b.sym.executor.name for b in thunder_tpu.last_traces(kernel)[-1].bound_symbols
              if b.sym.name.startswith("sparse_block")}
    assert owners == {"sparse_block_attend": "pallas"}
    assert got.shape == q.shape and got.dtype == q.dtype
    unrolled = np.asarray(_attend(query_chunk=48)(q, k, v, ids)).astype(np.float32)
    loops = attend_by_loops(*(np.asarray(a[0], np.float32) for a in (q, k, v)), ids[0], 16)
    got = got.astype(np.float32)
    if dtype == "float32":
        np.testing.assert_allclose(got[0], loops, rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(got, unrolled, rtol=1e-5, atol=2e-6)
    else:  # the output's own rounding is 2**-9 of values up to 3; the decomposition rounds its scores to bf16 besides
        np.testing.assert_allclose(got[0], loops, rtol=0, atol=2e-2)
        np.testing.assert_allclose(got, unrolled, rtol=0, atol=5e-2)
        assert rel(got[0], loops) <= 1.1 * rel(unrolled[0], loops) + 1e-3  # float32 scores: no further from the loops


def test_visited_key_tiles_against_a_count_by_hand():
    """Of the 20 pairs of a query tile of 32 and a key tile of 64 at or before the causal frontier in 256 positions
    (1 + 1 + 2 + 2 + 3 + 3 + 4 + 4), a key-value head: with every query's own block and the one before, 1, 1, 2, 1, 2, 1
    for the first six query tiles; tile 6 leaves key tile 1 out (3 of 4); tile 7 chose everything (4)."""
    from thunder_tpu.executors import pallasex

    q, k, _ = qkv(T, heads=2, groups=2, d=128)
    ids = _ids_disjoint_then_all(q, k)
    visited, causal = pallasex.visited_key_tiles(ids, 16, *KERNEL_TILES)
    assert (int(visited), int(causal)) == (2 * 15, 2 * 20)
    flags = np.asarray(pallasex._sparse_pair_flags(ids, 16, *KERNEL_TILES))[0, 0]
    assert flags[6].tolist() == [True, False, True, True] and flags[7].all() and flags[0].tolist() == [True, False, False, False]
    # every block up to its own chosen: every causal pair, here at the kernel's own tiles (the default)
    tq, tk = pallasex._SPARSE_ATTEND_TILES
    own = (np.arange(4 * tk, dtype=np.int32) // 64)[None, None, :, None]
    everything = np.minimum(np.arange(4 * tk // 64, dtype=np.int32)[None, None, None, :], own)
    pairs = sum(-(-(i + 1) * tq // tk) for i in range(4 * tk // tq))
    assert [int(x) for x in pallasex.visited_key_tiles(everything, 64)] == [pairs, pairs]
    assert int(pallasex.visited_key_tiles(np.zeros_like(everything), 64)[0]) == 4 * tk // tq  # block 0 alone: the first key tile


def test_the_attend_kernel_is_causal_to_the_bit(pallasex):
    q, k, v = qkv(T, heads=4, groups=2, d=128)
    ids = _ids_selected(q, k)
    k2, v2 = k.copy(), v.copy()
    k2[:, :, 150:] += 1.0
    v2[:, :, 150:] -= 1.0
    kernel = _attend()
    first, second = np.asarray(kernel(q, k, v, ids)), np.asarray(kernel(q, k2, v2, ids))
    np.testing.assert_array_equal(first[:, :, :150], second[:, :, :150])
    assert np.abs(first[:, :, 150:] - second[:, :, 150:]).max() > 0.1


def test_under_a_mesh_the_attend_kernel_runs_a_batch_shard_a_device(pallasex):
    """As the other kernels (``executors/kernel_mesh.py``): two sequences over two devices, each shard a call of its
    own on its own ids, the values those of one call on both; a batch the mesh does not divide is declined."""
    import jax
    from jax.sharding import Mesh

    from thunder_tpu.executors.kernel_mesh import kernel_mesh

    q, k, v = (np.concatenate([a, b]) for a, b in zip(qkv(T, heads=4, groups=2, d=128), qkv(T, heads=4, groups=2, d=128, seed=1)))
    ids = _ids_selected(q, k)
    want = np.asarray(pallasex._sparse_attend_impl(q, k, v, ids, block_size=16))
    with kernel_mesh(Mesh(np.asarray(jax.devices()[:2]), ("dp",)), "dp"):
        assert pallasex._sparse_attend_checker(*_proxies(q, k, v, ids), block_size=16)
        assert not pallasex._sparse_attend_checker(*_proxies(*(a[:1] for a in (q, k, v, ids))), block_size=16)
        got = jax.jit(lambda *a: pallasex._sparse_attend_impl(*a, block_size=16))(q, k, v, ids)
    assert len(got.sharding.device_set) == 2
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("d,t,how", [(64, T, {}), (128, 200, {}), (128, T, {"query_chunk": 64})],
                         ids=["heads-of-64", "a-ragged-length", "a-chunking-the-caller-chose"])
def test_what_the_attend_kernels_checker_declines_is_the_decompositions(pallasex, d, t, how):
    """Heads that are not whole lanes, a length the tiles do not divide, a chunking the caller chose: no executor owns
    the symbol (``jaxex``'s loops want whole spans of 4,096), and the values are the decomposition's."""
    q, k, v = qkv(t, heads=4, groups=2, d=d)
    ids = _ids_selected(q, k)
    assert not pallasex._sparse_attend_checker(*_proxies(q, k, v, ids), block_size=16, **how)
    jfn = _attend(**how)
    got = np.asarray(jfn(q, k, v, ids))
    assert "sparse_block_attend" not in [b.sym.name for b in thunder_tpu.last_traces(jfn)[-1].bound_symbols]
    np.testing.assert_allclose(got[0], attend_by_loops(q[0], k[0], v[0], ids[0], 16), rtol=2e-4, atol=2e-5)
    # and what it takes: heads of 128 on whole tiles, the chunking left open
    assert pallasex._sparse_attend_checker(*_proxies(*qkv(T, heads=4, groups=2, d=128), np.zeros((1, 2, T, 6), np.int32)), block_size=16)


def test_the_forced_blocks_are_always_chosen_and_ties_go_to_the_lower_block():
    q, k, _ = qkv(T, heads=4, groups=1)
    ids = np.asarray(thunder_tpu.jit(lambda q, k: ttorch.sparse_block_select(q, k, **SPARSE))(q, k))[0, 0]
    for t in (0, 15, 16, 47, 48, 100, 255):
        own = t // 16
        assert {0, own, max(own - 1, 0)} <= set(ids[t].tolist())
        assert ids[t].max() <= own and (ids[t] >= 0).sum() == min(6, own + 1)
    # queries of zeros score every pooled key alike: every free block ties, and the lowest are taken
    ids = np.asarray(thunder_tpu.jit(lambda q, k: ttorch.sparse_block_select(q, k, **SPARSE))(np.zeros_like(q), k))[0, 0]
    assert ids[255].tolist() == [0, 14, 15, 1, 2, 3] and ids[100].tolist() == [0, 5, 6, 1, 2, 3]


def test_both_mixers_are_causal():
    q, k, v = qkv(T, heads=4, groups=1)
    q2, k2, v2 = (a.copy() for a in (q, k, v))
    for a in (q2, k2, v2):
        a[:, :, 150:] += 1.0
    sparse = thunder_tpu.jit(lambda q, k, v: ttorch.sparse_block_attention(q, k, v, **SPARSE))
    np.testing.assert_array_equal(np.asarray(sparse(q, k, v))[:, :, :150], np.asarray(sparse(q2, k2, v2))[:, :, :150])
    decay = np.asarray([0.5, 0.1, 0.01, 0.0], np.float32)
    kk, vv, kk2, vv2 = (np.repeat(a, 4, 1) for a in (k, v, k2, v2))
    linear = thunder_tpu.jit(lambda q, k, v, g: ttorch.linear_attention(q, k, v, g, chunk=64))
    np.testing.assert_allclose(np.asarray(linear(q, kk, vv, decay))[:, :, :150],
                               np.asarray(linear(q2, kk2, vv2, decay))[:, :, :150], rtol=1e-5, atol=1e-5)


def linear_by_recurrence(q, k, v, g):
    """``S_t = exp(-g) S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t / sqrt(d)``, float64: (H, T, d)."""
    H, T_, d = q.shape
    out = np.zeros((H, T_, d))
    for h in range(H):
        S = np.zeros((d, d))
        for t in range(T_):
            S = np.exp(-float(g[h])) * S + np.outer(k[h, t], v[h, t]).astype(np.float64)
            out[h, t] = q[h, t].astype(np.float64) @ S / np.sqrt(d)
    return out


@pytest.mark.parametrize("t,chunk", [(100, 256), (100, 32), (131, 48), (256, 64)], ids=lambda x: str(x))
def test_linear_attention_against_the_recurrence(t, chunk):
    """One chunk, whole chunks, and a T no chunk divides; a fast, a slow and no decay."""
    import jax.numpy as jnp

    from perfbench.reference import minicpm_sala

    q, k, v = qkv(t, heads=4, groups=4)
    decay = np.asarray([0.8, 0.1, 0.004, 0.0], np.float32)
    want = linear_by_recurrence(q[0], k[0], v[0], decay)
    got = np.asarray(thunder_tpu.jit(lambda q, k, v, g: ttorch.linear_attention(q, k, v, g, chunk=chunk))(q, k, v, decay))
    assert rel(got[0], want) < 1e-5
    quadratic = minicpm_sala.linear_attention(jnp.asarray(q[0]), jnp.asarray(k[0]), jnp.asarray(v[0]),
                                              jnp.asarray(decay), HP)
    assert rel(np.asarray(quadratic), want) < 1e-5


def test_linear_attention_refuses_grouped_keys():
    q, k, v = qkv(32, heads=4, groups=2)
    with pytest.raises(Exception, match="key heads"):
        thunder_tpu.jit(lambda q, k, v, g: ttorch.linear_attention(q, k, v, g))(q, k, v, np.zeros(4, np.float32))


# -----------------------------------------------------------------------------
# The comparison that decides ``correct``
# -----------------------------------------------------------------------------


def _only_the_forced_blocks(monkeypatch, cfg):
    return dataclasses.replace(cfg, sparse_topk=cfg.sparse_init_blocks + cfg.sparse_window_size // cfg.sparse_block_size)


def _dense_in_place_of_the_chosen_blocks(monkeypatch, cfg):
    return dataclasses.replace(cfg, sparse_dense_len=10 ** 9)


def _the_decay_dropped(monkeypatch, cfg):
    monkeypatch.setattr(gpt.GPTConfig, "linear_decay", lambda self, layer: (0.0,) * self.linear_heads)
    return cfg


def _the_pooling_over_a_blocks_keys_dropped(monkeypatch, cfg):
    """Step 4 without its max over the overlapping pooled keys: a block scores by its first pooled key alone."""
    per = cfg.sparse_block_size // cfg.sparse_kernel_stride
    monkeypatch.setattr(ttorch, "_pooled_to_blocks", lambda P, per_, r, nb: ttorch.pad(
        P, (0, per * nb - P.shape[-1]))[..., ::per] if per * nb >= P.shape[-1] else P[..., :per * nb:per])
    return cfg


def _a_gate_dropped(which):
    def mutate(monkeypatch, cfg):
        return dataclasses.replace(cfg, **{which: False})
    return mutate


def _the_output_norm_dropped(monkeypatch, cfg):
    return dataclasses.replace(cfg, linear_output_norm=False)


def _the_residual_scale_dropped(monkeypatch, cfg):
    return dataclasses.replace(cfg, residual_scale=1.0)


MUTATIONS = {"only-the-forced-blocks": _only_the_forced_blocks,
             "dense-in-place-of-step-6": _dense_in_place_of_the_chosen_blocks,
             "the-decay-dropped": _the_decay_dropped,
             "step-4s-pooling-dropped": _the_pooling_over_a_blocks_keys_dropped,
             "the-sparse-gate-dropped": _a_gate_dropped("attn_output_gate"),
             "the-linear-gate-dropped": _a_gate_dropped("linear_output_gate"),
             "the-output-norm-dropped": _the_output_norm_dropped,
             "the-residual-scale-dropped": _the_residual_scale_dropped}


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_a_mutated_system_fails_the_cells_comparison_at_rehearsal_size(monkeypatch, name):
    """Each departure from the equations fails the comparison the cell's check
    makes (``perfbench/checks_sparse_linear.py``) at the stand-in's sizes,
    where the unmutated system is within a hundredth of the limits."""
    import jax.numpy as jnp

    from perfbench import checks_sparse_linear
    from perfbench.jobs import forward_sparse_linear
    from perfbench.reference import minicpm_sala

    cfg, params, stacked = built()
    params, stacked = forward_sparse_linear.with_mixers_heard(params), forward_sparse_linear.with_mixers_heard(stacked)
    idx = batch()
    last = 64
    want = np.asarray(minicpm_sala.forward(stacked, jnp.asarray(idx), KEYS, last=last))
    clean = np.asarray(thunder_tpu.jit(lambda p, i: gpt.forward(p, i, cfg, last=last))(params, idx))
    sound = checks_sparse_linear.compare_logits(clean, want)
    assert sound["ok"] and sound["logits_rel_l2"] < 1e-2 * sound["logits_rtol"], sound
    mutated = MUTATIONS[name](monkeypatch, cfg)
    got = np.asarray(thunder_tpu.jit(lambda p, i: gpt.forward(p, i, mutated, last=last))(params, idx))
    verdict = checks_sparse_linear.compare_logits(got, want)
    assert not verdict["ok"], verdict
