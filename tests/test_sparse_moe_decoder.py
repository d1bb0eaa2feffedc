"""The ops, layers and model of the sparse-attention mixture-of-experts
decoder (ISSUE 26) on the CPU at small sizes: each new op against
``jax.numpy`` forward and gradient, ``fused_attention`` with grouped heads
and a selection against ``reference_attention`` and against the streamed
kernel (interpreted), the expert layer and its shares against the plain
reference, and the tiny model's training against it."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from op_test import OpTest
from paddle_tpu import compile_cache
from paddle_tpu.ops import moe
from paddle_tpu.ops import sparse_select as ss
from paddle_tpu.ops import attention_xla as fa
from paddle_tpu.ops.pallas import streamed_attention as sa

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, weights                        # noqa: E402

CELL = "keye_vl2_30b_a3b.train_longdoc_8k"


def _rand(shape, seed=0, scale=1.0):
    return (np.random.RandomState(seed).randn(*shape) * scale).astype(
        "float32")


# ---- rms_norm, rotary_embedding, swiglu ------------------------------------

def _np_rms(x, g, eps):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * g


def _np_rotary(x, theta):
    t, d = x.shape[1], x.shape[-1]
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(t)[:, None] * inv[None, :]
    ang = np.concatenate([ang, ang], -1).reshape(
        (1, t) + (1,) * (x.ndim - 3) + (d,))
    half = np.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * np.cos(ang) + half * np.sin(ang)


def _op_case(name):
    t = OpTest()
    if name == "rms_norm":
        x, g = _rand((2, 3, 4, 8), 1), 1.0 + 0.1 * _rand((8,), 2)
        t.op_type, t.attrs = "rms_norm", {"epsilon": 1e-6}
        t.inputs = {"X": x, "Scale": g}
        t.outputs = {"Y": _np_rms(x, g, 1e-6)}
        return t, ["rms_norm__X", "rms_norm__Scale"], "rms_norm__Y"
    if name == "rotary_embedding":
        x = _rand((2, 5, 3, 8), 3)
        t.op_type, t.attrs = "rotary_embedding", {"theta": 1e4}
        t.inputs = {"X": x}
        t.outputs = {"Out": _np_rotary(x, 1e4)}
        return t, ["rotary_embedding__X"], "rotary_embedding__Out"
    x, y = _rand((3, 7), 4), _rand((3, 7), 5)
    t.op_type = "swiglu"
    t.inputs = {"X": x, "Y": y}
    t.outputs = {"Out": x / (1.0 + np.exp(-x)) * y}
    return t, ["swiglu__X", "swiglu__Y"], "swiglu__Out"


@pytest.mark.parametrize("name", ["rms_norm", "rotary_embedding", "swiglu"])
def test_elementwise_op_forward(name):
    _op_case(name)[0].check_output(atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("name", ["rms_norm", "rotary_embedding", "swiglu"])
def test_elementwise_op_gradient(name):
    t, ins, out = _op_case(name)
    t.check_grad(ins, out, max_relative_error=0.01)


# ---- indexer_score, select_topk_keys -----------------------------------------

def test_indexer_score_matches_the_formula_blockwise_or_not(monkeypatch):
    q, k, w = _rand((2, 32, 3, 8), 1), _rand((2, 32, 8), 2), _rand((2, 32, 3), 3)
    want = np.einsum("btjs,btj->bts", np.maximum(
        np.einsum("btjd,bsd->btjs", q, k), 0), w) * 0.25
    np.testing.assert_allclose(ss.index_scores(q, k, w, 0.25), want,
                               rtol=1e-5, atol=1e-5)
    monkeypatch.setattr(ss, "_SCORE_BLOCK", 8)        # four query blocks
    np.testing.assert_allclose(ss.index_scores(q, k, w, 0.25), want,
                               rtol=1e-5, atol=1e-5)


def _np_topk_mask(x, k):
    out = np.zeros(x.shape, bool)
    for b in range(x.shape[0]):
        for t in range(x.shape[1]):
            best = sorted(range(t + 1), key=lambda s: (-x[b, t, s], s))[:k]
            out[b, t, best] = True
    return out


@pytest.mark.parametrize("levels", [5, 1000000])
def test_select_topk_keys_ties_go_to_the_lower_index(levels):
    """Few distinct scores: most rows are cut inside a run of ties."""
    x = np.random.RandomState(0).randint(0, levels, (2, 40, 40)).astype(
        "float32") - levels / 2
    sel = ss.topk_key_mask(jnp.asarray(x), 8)
    want = _np_topk_mask(x, 8)
    assert np.array_equal(np.asarray(sel), want)
    assert np.array_equal(want[:, :8], np.tril(np.ones((40, 40), bool))[:8]
                          [None].repeat(2, 0))      # t < k: every causal key
    packed = ss.pack_key_mask(sel)
    assert packed.shape == (2, 40, 128) and packed.dtype == jnp.int32
    assert np.array_equal(np.asarray(ss.unpack_key_mask(packed, 40)), want)


def test_select_op_reports_the_share_and_has_no_gradient():
    x = _rand((1, 24, 24), 7)
    got = ss._select_compute({"X": [jnp.asarray(x)]},
                             {"k": 6, "causal": True}, None, 0)
    want = _np_topk_mask(x, 6)
    assert np.array_equal(np.asarray(ss.unpack_key_mask(got["Out"], 24)),
                          want)
    assert float(got["Share"][0]) == pytest.approx(want.sum() / (24 * 25 / 2))
    from paddle_tpu.registry import get_op_def
    assert get_op_def("select_topk_keys").grad is None
    assert get_op_def("indexer_score").grad is None


def test_packed_layout_is_one_bit_plane_per_128_keys():
    sel = np.zeros((1, 1, 5000), bool)
    sel[0, 0, [0, 127, 128, 4095, 4096, 4999]] = True
    words = np.asarray(ss.pack_key_mask(jnp.asarray(sel))).view(np.uint32)
    assert words.shape == (1, 1, 256)
    assert words[0, 0, 0] == 1 | (1 << 1)        # keys 0 and 128
    assert words[0, 0, 127] == 1 | (1 << 31)     # keys 127 and 4095
    assert words[0, 0, 128] == 1                 # key 4096
    assert words[0, 0, 128 + 4999 % 128] == 1 << ((4999 - 4096) // 128)


# ---- select_topk_keys: the Pallas body ----------------------------------------

def _levels(shape, levels, seed=0):
    return (np.random.RandomState(seed).randint(0, levels, shape)
            - levels / 2).astype("float32")


def _special(shape):
    """Zeros of both signs, runs of equal negatives, infinities and NaNs of
    both signs — among them the one whose sortable key is 0."""
    r = np.random.RandomState(3)
    pool = np.array([0.0, -0.0, -1.5, -1.5, 2.0, np.inf, -np.inf, np.nan,
                     1e-40, -1e-40], "float32")
    x = pool[r.randint(0, len(pool), shape)]
    x.view(np.uint32)[..., ::7] = 0xFFFFFFFF
    x[:, 8:16] = 0.0                                  # whole rows of ties
    x[:, 16:24] = -3.0
    return x


_KERNEL_CASES = {
    # many ties at the threshold: the index passes run (72 rows: two row
    # blocks, the second a part of one; batch 2)
    "ties_levels_5": (lambda: _levels((2, 72, 256), 5), 8, True),
    "ties_levels_1000000": (lambda: _levels((2, 72, 256), 1000000), 8, True),
    "negative_zero_equal_nan": (lambda: _special((2, 72, 256)), 8, True),
    "not_causal": (lambda: _levels((2, 72, 256), 5), 8, False),
    # one slab; rows 0..99 have fewer candidates than k
    "fewer_candidates_than_k": (lambda: _rand((1, 136, 128), 1), 100, True),
    "k_at_least_tk": (lambda: _rand((1, 136, 128), 2), 128, True),
    "k_at_least_tk_not_causal": (lambda: _rand((1, 8, 384), 2), 500, False),
    # two 4096-key tiles and one slab of a third
    "several_tiles_and_a_part": (lambda: _levels((1, 8, 8320), 50, 4), 5000,
                                 False),
    # a part of one tile, slabs in groups of four, causal past T = Tk
    "rows_past_the_keys": (lambda: _rand((1, 80, 512), 5), 40, True),
}


@pytest.mark.parametrize("case", sorted(_KERNEL_CASES))
def test_topk_kernel_interpreted_equals_the_xla_body_word_for_word(case):
    from paddle_tpu.ops.pallas import topk_select

    make, k, causal = _KERNEL_CASES[case]
    x = jnp.asarray(make())
    assert topk_select.supported(x.shape, x.dtype)
    words, count = topk_select.select_topk_words(x, k, causal, True)
    sel = ss.topk_key_mask(x, k, causal)
    assert words.dtype == jnp.int32
    assert np.array_equal(np.asarray(words), np.asarray(ss.pack_key_mask(sel)))
    assert np.array_equal(np.asarray(count)[..., 0], np.asarray(sel).sum(-1))


def test_topk_kernel_crosses_a_tile_under_causal():
    """Rows past 4096 have candidates in the second 4096-key tile (the
    kernel runs on the whole matrix; its last row blocks are compared)."""
    from paddle_tpu.ops.pallas import topk_select

    x = _levels((1, 4104, 4224), 3000, 6)
    x[..., 4096:] += 5000.0                  # the second tile's keys win
    x = jnp.asarray(x)
    words, _ = topk_select.select_topk_words(x, 1024, True, True)
    want = ss.pack_key_mask(ss.topk_key_mask(x, 1024, True))
    assert words.shape == (1, 4104, 256)
    assert np.array_equal(np.asarray(words[:, 4032:]),
                          np.asarray(want[:, 4032:]))
    assert np.asarray(words[0, 4100, 128:133] == 1).all()


def _bodies_noted(run):
    """(the kernel bodies ops noted while ``run()`` ran, what it returned)."""
    before = dict(compile_cache.stats()["kernel_bodies"])
    out = run()
    after = compile_cache.stats()["kernel_bodies"]
    return {key: n - before.get(key, 0) for key, n in after.items()
            if n != before.get(key, 0)}, out


def _select_body(monkeypatch, x, k, causal, platforms, mesh=None):
    """Trace the op's compute as the CPU executor would; return (kernel
    bodies recorded, outputs)."""
    from paddle_tpu.registry import ComputeContext

    monkeypatch.setattr(ss, "_KERNEL_PLATFORMS", platforms)
    ctx = ComputeContext(key=jax.random.key(0), platform="cpu", mesh=mesh)
    return _bodies_noted(lambda: jax.jit(lambda x: ss._select_compute(
        {"X": [x]}, {"k": k, "causal": causal}, ctx, 0))(x))


@pytest.mark.parametrize("causal", [True, False])
def test_select_op_takes_the_kernel_when_its_rule_says_so(causal,
                                                          monkeypatch):
    """A CPU trace keeps the XLA body and says so; let the CPU platform take
    the kernel (interpreted; the program never does) and the op's words
    and share are the XLA body's."""
    x = jnp.asarray(_levels((2, 72, 256), 7, 8))
    bodies, want = _select_body(monkeypatch, x, 16, causal, ("tpu",))
    assert bodies == {"select_topk_keys:xla": 1}
    bodies, got = _select_body(monkeypatch, x, 16, causal, ("tpu", "cpu"))
    assert bodies == {"select_topk_keys:pallas": 1}
    assert np.array_equal(np.asarray(got["Out"]), np.asarray(want["Out"]))
    assert np.asarray(got["Share"]) == np.asarray(want["Share"])
    assert got["Share"].shape == (1,) and got["Share"].dtype == jnp.float32


@pytest.mark.parametrize("case", ["cell_shape", "mesh", "flag_off",
                                  "odd_tk", "odd_rows", "bf16", "cpu",
                                  "row_over_budget"])
def test_select_kernel_rule_reads_only_what_the_op_observes(case,
                                                            monkeypatch):
    import types

    from paddle_tpu import flags
    from paddle_tpu.parallel.mesh import make_mesh

    tpu = types.SimpleNamespace(platform="tpu", mesh=None)
    cell = (1, 8192, 8192)
    if case == "cell_shape":
        assert ss._kernel_applicable(tpu, cell, jnp.float32)
    elif case == "mesh":
        meshed = types.SimpleNamespace(platform="tpu",
                                       mesh=make_mesh((2, 4), ("dp", "tp")))
        assert not ss._kernel_applicable(meshed, cell, jnp.float32)
    elif case == "flag_off":
        monkeypatch.setitem(flags._FLAGS, "pallas_kernels", False)
        assert not ss._kernel_applicable(tpu, cell, jnp.float32)
    elif case == "odd_tk":
        assert not ss._kernel_applicable(tpu, (1, 8192, 8200), jnp.float32)
        assert not ss._kernel_applicable(tpu, (1, 40, 40), jnp.float32)
    elif case == "odd_rows":
        assert not ss._kernel_applicable(tpu, (1, 100, 128), jnp.float32)
    elif case == "bf16":
        assert not ss._kernel_applicable(tpu, cell, jnp.bfloat16)
    elif case == "cpu":
        cpu = types.SimpleNamespace(platform="cpu", mesh=None)
        assert not ss._kernel_applicable(cpu, cell, jnp.float32)
        assert not ss._kernel_applicable(None, cell, jnp.float32)
    else:
        # eight rows of 131072 keys are 4 MB: over the row block's budget
        assert ss._kernel_applicable(tpu, (1, 8, 65536), jnp.float32)
        assert not ss._kernel_applicable(tpu, (1, 8, 131072), jnp.float32)


# ---- fused_attention: grouped heads, selected keys ---------------------------

def _qkv(b=1, h=4, hk=2, t=256, d=128, seed=0):
    r = np.random.RandomState(seed)
    return (jnp.asarray(r.randn(b, h, t, d).astype("float32")),
            jnp.asarray(r.randn(b, hk, t, d).astype("float32")),
            jnp.asarray(r.randn(b, hk, t, d).astype("float32")))


def _dense_attention(q, k, v, valid):
    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, 1), jnp.repeat(v, g, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(valid, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", jnp.where(valid, p, 0.0), v)


def test_xla_body_with_grouped_heads_and_a_selection():
    q, k, v = _qkv(t=64, d=16)
    scores = jnp.asarray(_rand((1, 64, 64), 9))
    sel = ss.topk_key_mask(scores, 8)
    got = fa.reference_attention(q, k, v, None, None, True, 0.0, None,
                                 ss.pack_key_mask(sel))
    want = _dense_attention(q, k, v, sel[:, None])
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_selection_of_more_keys_than_the_row_holds_is_plain_causal():
    q, k, v = _qkv(t=64, d=16)
    sel = ss.topk_key_mask(jnp.asarray(_rand((1, 64, 64), 9)), 64)   # t < k
    got = fa.reference_attention(q, k, v, None, None, True, 0.0, None,
                                 ss.pack_key_mask(sel))
    want = fa.reference_attention(q, k, v, None, None, True, 0.0, None)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def _low_scoring_keys(t, first, last, seed=11):
    """Indexer scores ``[1, t, t]`` in which keys first..last-1 score so low
    that no query with enough other candidates selects any of them."""
    return jnp.asarray(_rand((1, t, t), seed)).at[:, :, first:last].set(
        -100.0)


# (query heads, key/value heads, T, D, causal, selection[, plain heads a grid
# step]): the selection is None, or (low-scoring keys' range, keys a query
# selects[, a range of query rows that select NO key]); with a number of heads
# a step, the VMEM budget is what that many plain heads take, and the rule has
# to find it
_STREAMED_CASES = {
    # T = 512 is ONE 512 x 512 block pair, two heads a key/value head
    "one_block_selected": (4, 2, 512, 128, True, ((128, 256), 64)),
    "one_block_causal": (4, 2, 512, 128, True, None),
    "one_block_dense": (4, 2, 512, 128, False, None),
    # T = 384 is three by three blocks of 128: pairs the diagonal crosses,
    # pairs below it and pairs above it (skipped) all occur
    "g8_selected": (8, 1, 384, 128, True, ((128, 256), 64)),
    "g8_causal": (8, 1, 384, 128, True, None),
    "g1_selected": (2, 2, 384, 128, True, ((128, 256), 64)),
    "g1_causal": (2, 2, 384, 128, True, None),
    "g4_dense": (8, 2, 384, 128, False, None),
    "noncausal_selected": (4, 2, 384, 128, False, ((128, 256), 64)),
    # from query 192 on no key of the FIRST key block is selected: those
    # rows pass it with no key yet, for every head of the loop in turn
    "first_key_block_empty": (8, 2, 384, 128, True, ((0, 128), 64)),
    # from query 320 on none of the first TWO key blocks: the running maximum
    # stays -1e30 over two pairs and every lane of the running sum 0.0, then
    # both start in the third
    "two_key_blocks_empty": (8, 2, 384, 128, True, ((0, 256), 64)),
    # queries 120..199 select no key at all, in the first two query blocks:
    # out 0.0 and log-sum-exp +1e30 exactly, no gradient from those rows
    "rows_without_keys": (4, 2, 384, 128, True, ((128, 256), 64, (120, 200))),
    "rows_without_keys_plain": (2, 2, 384, 128, False,
                                ((128, 256), 64, (120, 200))),
    # 16 heads a key/value head at D = 256 in float32 are more than the
    # VMEM budget takes a step: 8 a step, two steps a block pair; T = 1024
    # is two by two blocks of 512; T = 768 three by three of 256
    "g16_split_selected": (16, 1, 1024, 256, True, ((512, 640), 256)),
    "blocks_of_256": (4, 1, 768, 128, True, ((256, 512), 128)),
    # plain heads, several a grid step and more than one block of heads a
    # grid (each head its own K/V block, dK and dV of the step): 8 heads by
    # 4, 6 by 3 and by 2; three by three blocks of 128
    "plain8_by4_causal": (8, 8, 384, 128, True, None, 4),
    "plain8_by4_selected": (8, 8, 384, 128, True, ((128, 256), 64), 4),
    "plain8_by4_dense": (8, 8, 384, 128, False, None, 4),
    "plain6_by3_selected": (6, 6, 384, 128, True, ((0, 128), 64), 3),
    "plain6_by3_noncausal_selected": (6, 6, 384, 128, False,
                                      ((128, 256), 64), 3),
    "plain6_by2_causal": (6, 6, 384, 128, True, None, 2),
    # ONE block pair a row of blocks, two blocks of plain heads: the state
    # is started and the lanes of the sums added up in the same grid step
    "plain4_by2_one_block": (4, 4, 256, 128, True, None, 2),
    # output two lane tiles wide under the per-row factor, three by three
    # blocks of 128
    "plain2_d256_causal": (2, 2, 384, 256, True, None),
    "g2_d256_selected": (4, 2, 384, 256, True, ((0, 128), 64)),
}


def _packed_selection(t, selection, causal):
    """(the packed key mask, the query rows that select NO key) of one of
    ``_STREAMED_CASES``' selections — (low-scoring keys' range, keys a query
    selects[, rows without keys]) —, (None, no rows) without one."""
    if selection is None:
        return None, slice(0, 0)
    (first, last), keep, *no_key = selection
    sel = ss.topk_key_mask(_low_scoring_keys(t, first, last), keep, causal)
    assert not bool(sel[0, last + keep:, first:last].any())
    empty = slice(*no_key[0]) if no_key else slice(0, 0)
    return ss.pack_key_mask(sel.at[:, empty].set(False)), empty


@pytest.mark.parametrize("case", sorted(_STREAMED_CASES))
def test_streamed_kernel_interpreted_matches_the_xla_body(case, monkeypatch):
    """Forward and the three gradients against ``reference_attention``.
    With a selection, a range of keys scores so low that queries with
    enough other candidates select none of them: whole key blocks then hold
    no selected key for the later query blocks, and must leave the online
    softmax as it was."""
    h, hk, t, d, causal, selection, *a_step = _STREAMED_CASES[case]
    q, k, v = _qkv(h=h, hk=hk, t=t, d=d)
    packed, empty = _packed_selection(t, selection, causal)
    g, block = h // hk, sa._pick_blocks(t)
    if a_step:
        monkeypatch.setattr(sa, "_VMEM_BUDGET", sa._step_bytes(
            1, block, block, d, 4, kh=a_step[0]))
    assert sa._heads_per_step(g, hk, block, block, d, 4) == (
        (1, 8 if case == "g16_split_selected" else g) if g > 1
        else (a_step[0] if a_step else hk, 1))

    def xla(q, k, v):
        return fa.reference_attention(q, k, v, None, None, causal, 0.0, None,
                                      packed)

    def kernel(q, k, v):
        return sa.streamed_attention(q, k, v, packed, causal, None, True)
    # the forward's two results: the output, and the rows' log-sum-exp —
    # the running maximum and the sums' lanes put together — to float32
    out, lse = sa.forward(q, k, v, packed, causal, None, True)
    want, want_lse = fa.reference_attention(q, k, v, None, None, causal, 0.0,
                                            None, packed, True)
    assert lse.shape == (1, h, t, 1) and lse.dtype == jnp.float32
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(lse, want_lse, rtol=1e-6, atol=2e-6)
    # a row with no key: exactly +1e30 and 0.0; every other row is real
    no_key = np.zeros(t, bool)
    no_key[empty] = True
    np.testing.assert_array_equal(np.asarray(lse)[0, :, no_key], np.float32(
        1e30))
    np.testing.assert_array_equal(np.asarray(out)[0, :, no_key], 0.0)
    assert (np.asarray(lse)[0, :, ~no_key] < 1e3).all()
    ct = jnp.asarray(_rand(q.shape, 12))
    want = jax.grad(lambda *a: jnp.sum(xla(*a) * ct), (0, 1, 2))(q, k, v)
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) * ct), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


# (query heads, key/value heads, T, causal, selection as in _STREAMED_CASES,
# the fused kernel's (K/V heads, query heads of each) a grid step): T = 384 is
# three by three blocks of 128, T = 640 five by five
_FUSED_BACKWARD_CASES = {
    "g8_selected": (8, 1, 384, True, ((128, 256), 64), (1, 8)),
    "g8_causal_five_blocks": (8, 1, 640, True, None, (1, 8)),
    "g8_dense": (8, 1, 384, False, None, (1, 8)),
    # a group's heads over two and four steps: the K/V head's dK and dV stay
    # put while the head blocks pass, and add their heads in another order
    "g8_by4_selected": (8, 1, 384, True, ((128, 256), 64), (1, 4)),
    "g8_by2_causal": (8, 1, 384, True, None, (1, 2)),
    "g2_selected": (4, 2, 384, True, ((128, 256), 64), (1, 2)),
    "g2_by1_selected": (4, 2, 384, True, ((0, 128), 64), (1, 1)),
    "g2_noncausal_selected": (4, 2, 384, False, ((128, 256), 64), (1, 2)),
    # rows that select no key at all: no gradient from them, in any body
    "rows_without_keys": (4, 2, 384, True, ((128, 256), 64, (120, 200)),
                          (1, 2)),
    "rows_without_keys_plain": (2, 2, 384, False,
                                ((128, 256), 64, (120, 200)), (2, 1)),
    # plain heads under a selection, one and several a step
    "plain4_by1_selected": (4, 4, 384, True, ((128, 256), 64), (1, 1)),
    "plain4_by2_selected": (4, 4, 384, True, ((0, 128), 64), (2, 1)),
    "plain4_by4_selected": (4, 4, 384, True, ((128, 256), 64), (4, 1)),
    # the rule's own answer
    "g4_rule": (8, 2, 512, True, ((128, 256), 64), None),
}


@pytest.mark.parametrize("case", sorted(_FUSED_BACKWARD_CASES))
def test_fused_backward_interpreted_against_the_two_kernels(case,
                                                            monkeypatch):
    """The fused backward against ``jax.vjp`` of the XLA body AND against
    the dQ and dK/dV kernels on the same operands: dQ to the bit; dK and dV
    to the bit where the group's heads share steps as the two kernels'
    do."""
    from streamed_backward import check_fused_backward

    h, hk, t, causal, selection, heads = _FUSED_BACKWARD_CASES[case]
    q, k, v = _qkv(h=h, hk=hk, t=t)
    packed, empty = _packed_selection(t, selection, causal)
    g = h // hk
    dq, dk, dv = check_fused_backward(
        monkeypatch, q, k, v, jnp.asarray(_rand(q.shape, 12)), packed, causal,
        heads=heads, same_bits=heads is None or g == 1 or heads[1] == g)
    np.testing.assert_array_equal(np.asarray(dq)[0, :, empty], 0.0)


def test_resident_gradients_over_the_budget_take_the_two_kernels(monkeypatch):
    """The backward's rule: the fused kernel where a K/V head's float32 dK
    and dV (and the block they are cast into) fit the VMEM budget beside a
    step's blocks, with as many heads a step as fit; the dQ and dK/dV
    kernels, at the forward's heads a step, where one K/V head's do not —
    by the operands' shapes alone."""
    def grad_step(h, hk, t, dk, dv):
        q, k, v = (jax.ShapeDtypeStruct((1, n, t, w), jnp.bfloat16)
                   for n, w in ((h, dk), (hk, dk), (hk, dv)))
        return sa.grad_step(q, k, v), sa.step_heads(q, k, v)
    # the four decoder cells: all fused; the forward keeps its own heads
    assert grad_step(32, 4, 8192, 128, 128) == (("streamed_fused", (1, 8)),
                                                (1, 8))
    assert grad_step(32, 32, 8192, 192, 128) == (("streamed_fused", (2, 1)),
                                                 (8, 1))
    assert grad_step(16, 16, 4096, 128, 128) == (("streamed_fused", (4, 1)),
                                                 (8, 1))
    assert grad_step(40, 20, 4096, 64, 128) == (("streamed_fused", (1, 2)),
                                                (1, 2))
    # T x lanes(Dk + Dv) x (4 + 2) bytes: 24 MiB at 16k tokens fit, 48 MiB
    # at 32k do not, whatever the heads
    assert grad_step(32, 4, 16384, 128, 128)[0][0] == "streamed_fused"
    for h, hk in ((32, 4), (8, 8), (1, 1)):
        (body, heads), forwards = grad_step(h, hk, 32768, 128, 128)
        assert (body, heads) == ("streamed", forwards)
    # and a call under such a budget runs the two kernels: three traces a
    # signature with the forward, the standing results
    q, k, v = _qkv(h=4, hk=2, t=384)
    monkeypatch.setattr(sa, "_VMEM_BUDGET", sa._fused_step_bytes(
        1, 128, 128, 384, 128, 4) - 1)
    assert sa.grad_step(q, k, v) == ("streamed", sa.step_heads(q, k, v))
    from paddle_tpu.ops import pallas
    pallas.traced.cache_clear()
    before = dict(compile_cache.stats()["kernel_traces"].get(
        "streamed_attention", {"sites": 0, "traces": 0}))
    ct = jnp.asarray(_rand(q.shape, 12))
    got = jax.grad(lambda *a: jnp.sum(sa.streamed_attention(
        *a, None, True, None, True) * ct), (0, 1, 2))(q, k, v)
    after = compile_cache.stats()["kernel_traces"]["streamed_attention"]
    assert (after["sites"] - before["sites"],
            after["traces"] - before["traces"]) == (3, 3)
    want = jax.grad(lambda *a: jnp.sum(fa.reference_attention(
        *a, None, None, True, 0.0, None) * ct), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("g,hk,block,dk,dv,itemsize", [
    (8, 4, 512, 128, 128, 2),        # the long-document cell: all 8 a step
    (16, 1, 512, 256, 256, 4), (6, 2, 512, 256, 256, 4),
    (11, 3, 512, 256, 256, 4), (64, 1, 512, 128, 128, 2),
    (32, 2, 128, 128, 128, 2),
    # plain heads: several K/V heads a step, each with its own blocks
    (1, 32, 512, 192, 128, 2),       # the latent-attention cell
    (1, 16, 512, 128, 128, 2),       # the looped decoder's cell
    (1, 32, 512, 128, 128, 2), (1, 6, 512, 256, 256, 4),
    (1, 7, 512, 256, 256, 4), (1, 3, 128, 128, 128, 2), (1, 1, 512, 128, 128, 2),
])
def test_heads_per_step_is_a_divisor_inside_the_vmem_budget(g, hk, block, dk,
                                                            dv, itemsize):
    """Heads that share a K/V head: one K/V head a step and the most of its
    group that fit (the answers PR 29's rule gave).  Plain heads: the most
    K/V heads that fit, each counted with its own K and V blocks and, by
    dK/dV, its own accumulators and output blocks."""
    def fits(kh, gh):
        return sa._step_bytes(gh, block, block, dk, itemsize, dv,
                              kh) <= sa._VMEM_BUDGET
    kh, gh = sa._heads_per_step(g, hk, block, block, dk, itemsize, dv)
    assert g % gh == 0 and hk % kh == 0 and 1 in (kh, gh)
    if g > 1:
        assert kh == 1 and gh == max(
            n for n in range(1, g + 1) if g % n == 0 and (n == 1 or
                                                          fits(1, n)))
    else:
        assert kh == max(n for n in range(1, hk + 1)
                         if hk % n == 0 and (n == 1 or fits(n, 1)))
    expect = {(8, 4, 128): (1, 8),                   # Keye's, as it was
              (16, 1, 256): (1, 8), (6, 2, 256): (1, 6),
              (11, 3, 256): (1, 1),  # 11 do not fit, and 11 is prime
              (64, 1, 128): (1, 16), (32, 2, 128): (1, 32),
              (1, 7, 256): (1, 1),   # nor do 7 plain heads in float32
              (1, 3, 128): (3, 1), (1, 1, 128): (1, 1),
              # the latent cell's 32 plain heads at 192 / 128 bf16, 512
              # blocks: 8 a step fit (41.5 MiB of the 48), 16 do not
              (1, 32, 192): (8, 1), (1, 32, 128): (8, 1),
              (1, 16, 128): (8, 1)}
    if (g, hk, dk) in expect:
        assert (kh, gh) == expect[(g, hk, dk)]
    if (g, hk, dk, dv) == (1, 32, 192, 128):
        assert fits(8, 1) and not fits(16, 1)
    # a K/V head of the step counts: its K and V blocks twice buffered,
    # float32 dK and dV and their output blocks twice buffered
    k_and_v = block * (sa._lanes(dk) + sa._lanes(dv))
    one, two = (sa._step_bytes(1, block, block, dk, itemsize, dv, n)
                for n in (8, 9))
    assert two - one >= k_and_v * (2 * itemsize + 4 + 2 * itemsize)


def test_streamed_supported_says_what_it_takes():
    ok = ((1, 32, 8192, 128), (1, 4, 8192, 128), jnp.bfloat16, True, False, 0.0)
    assert sa.supported(*ok)
    assert not sa.supported((1, 32, 8192, 64), (1, 4, 8192, 64), *ok[2:])
    assert not sa.supported((1, 32, 100, 128), (1, 4, 100, 128), *ok[2:])
    assert not sa.supported(ok[0], (1, 5, 8192, 128), *ok[2:])
    assert not sa.supported(*ok[:4], True, 0.0)       # a padding mask
    assert not sa.supported(*ok[:5], 0.1)             # dropout


def _attention_program(h, hk, t, d, selected):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        q = fluid.layers.data("q", shape=[h, t, d], dtype="float32")
        k = fluid.layers.data("k", shape=[hk, t, d], dtype="float32")
        v = fluid.layers.data("v", shape=[hk, t, d], dtype="float32")
        for x in (q, k, v):
            x.stop_gradient = False
        sel = fluid.layers.data(
            "sel", shape=[t, ss.packed_width(t)], dtype="int32") \
            if selected else None
        out = fluid.layers.fused_attention(q, k, v, causal=True, selected=sel)
        loss = fluid.layers.reduce_sum(fluid.layers.elementwise_mul(out, out))
        fluid.append_backward(loss)
    return main, out


@pytest.mark.parametrize("body", ["xla", "streamed"])
def test_fused_attention_op_takes_grouped_heads_and_a_selection(body,
                                                                monkeypatch):
    """Through the op and its gradient op: the XLA body on the CPU, and the
    streamed kernel when the test lets the CPU trace take it."""
    from paddle_tpu.ops import attention as att

    if body == "streamed":
        monkeypatch.setattr(att, "_STREAMED_PLATFORMS", ("tpu", "cpu"))
    compile_cache.clear()
    q, k, v = _qkv(t=256)
    sel = ss.topk_key_mask(jnp.asarray(_rand((1, 256, 256), 5)), 32)
    main, out = _attention_program(4, 2, 256, 128, True)
    feed = {"q": np.asarray(q), "k": np.asarray(k), "v": np.asarray(v),
            "sel": np.asarray(ss.pack_key_mask(sel))}
    def bodies():
        got = compile_cache.stats()["kernel_bodies"]
        return (got.get("fused_attention:" + body, 0),
                got.get("fused_attention_grad:streamed_fused", 0),
                got.get("fused_attention_grad:streamed", 0),
                got.get("streamed_grad_step:1x2", 0))
    before = bodies()
    got = fluid.Executor(fluid.CPUPlace()).run(
        main, feed=feed, fetch_list=[out, "q@GRAD", "k@GRAD", "v@GRAD"])
    # the XLA body's gradient differentiates (and so re-traces) the
    # forward; the streamed body's runs its backward — the fused kernel, a
    # K/V head's two query heads a step — on the forward op's own output
    # and log-sum-exp, and no second forward
    after = bodies()
    assert tuple(a - b for a, b in zip(after, before)) == (
        (1, 1, 0, 1) if body == "streamed" else (2, 0, 0, 0))
    want = _dense_attention(q, k, v, sel[:, None])
    np.testing.assert_allclose(got[0], want, rtol=1e-4, atol=1e-4)
    grads = jax.grad(lambda *a: jnp.sum(_dense_attention(
        *a, sel[:, None]) ** 2), (0, 1, 2))(q, k, v)
    for a, b in zip(got[1:], grads):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)
    compile_cache.clear()


def test_fused_attention_refuses_heads_that_do_not_group():
    with pytest.raises(ValueError, match="whole multiple"):
        _attention_program(4, 3, 16, 8, False)
    with pytest.raises(ValueError, match="packed key mask"):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            q = fluid.layers.data("q", shape=[2, 16, 8], dtype="float32")
            bad = fluid.layers.data("sel", shape=[16, 16], dtype="int32")
            fluid.layers.fused_attention(q, q, q, causal=True, selected=bad)


# ---- routed experts ------------------------------------------------------------

def _expert_setup(n=64, d=16, f=8, total=8, k=2, seed=0):
    r = np.random.RandomState(seed)
    x = jnp.asarray(r.randn(n, d).astype("float32"))
    router = jnp.asarray(r.randn(d, total).astype("float32"))
    mats = [jnp.asarray(r.randn(total, *s).astype("float32") * 0.3)
            for s in ((d, f), (d, f), (f, d))]
    return x, router, mats, k


def _dense_experts(x, idx, c, mats, experts):
    y = 0.0
    for e in experts:
        ce = jnp.sum(jnp.where(idx == e, c, 0.0), -1)
        y = y + ce[:, None] * (
            (jax.nn.silu(x @ mats[0][e]) * (x @ mats[1][e])) @ mats[2][e])
    return y


def _share(x, router, mats, k, held, first, tile):
    routed = moe._router_compute({"X": [x], "W": [router]}, {"top_k": k},
                                 None, 0)
    lay = moe.dispatch_layout(routed["TopkIdx"], first, held, tile)
    layout = tuple(lay[s] for s in moe._LAYOUT)
    local = [m[first:first + held] for m in mats]
    y, pairs = moe.expert_ffn(x, routed["TopkWeight"], *local, layout, tile)
    return routed, lay, layout, local, y, pairs


def test_router_renormalises_over_the_top_k():
    x, router, _, k = _expert_setup()
    got = moe._router_compute({"X": [x], "W": [router]}, {"top_k": k}, None, 0)
    p = jax.nn.softmax(x @ router, -1)
    top, idx = jax.lax.top_k(p, k)
    assert np.array_equal(got["TopkIdx"], idx)
    np.testing.assert_allclose(got["TopkWeight"],
                               top / top.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(got["TopkWeight"].sum(-1), 1.0, rtol=1e-6)


def test_dispatch_puts_every_held_pair_in_its_experts_tiles():
    x, router, mats, k = _expert_setup()
    routed, lay, _, _, _, pairs = _share(x, router, mats, k, 3, 2, 8)
    idx = np.asarray(routed["TopkIdx"])
    counts = [(idx == e).sum() for e in (2, 3, 4)]
    assert np.array_equal(lay["Counts"], counts)
    assert int(lay["NumTiles"][0]) == sum(-(-c // 8) for c in counts)
    rows, slots = np.asarray(lay["RowToken"]), np.asarray(lay["RowSlot"])
    seen = set()
    for t in range(int(lay["NumTiles"][0])):
        e = int(lay["TileExpert"][t]) + 2
        for r, s in zip(rows[t * 8:(t + 1) * 8], slots[t * 8:(t + 1) * 8]):
            if r < 64:
                assert idx[r, s] == e
                seen.add((int(r), int(s)))
    assert len(seen) == sum(counts) == int(pairs)        # dropless
    assert lay["RowToken"].shape[0] == moe.dispatch_capacity(64 * k, 3, 8)


def test_expert_ffn_and_its_gradient_against_dense_experts():
    x, router, mats, k = _expert_setup()
    routed, _, layout, local, y, _ = _share(x, router, mats, k, 3, 2, 8)
    idx, c = routed["TopkIdx"], routed["TopkWeight"]
    np.testing.assert_allclose(
        y, _dense_experts(x, idx, c, mats, (2, 3, 4)), rtol=1e-5, atol=1e-5)
    dy = jnp.asarray(_rand(x.shape, 3))

    def dense(x, c, g, u, d):
        return jnp.sum(dy * _dense_experts(
            x, idx, c, [jnp.zeros_like(m).at[2:5].set(v)
                        for m, v in zip(mats, (g, u, d))], (2, 3, 4)))
    want = jax.grad(dense, (0, 1, 2, 3, 4))(x, c, *local)
    got = moe.expert_ffn_grad(x, c, *local, layout, 8, dy)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_one_expert_takes_every_token_and_nothing_is_dropped():
    """The worst imbalance: a router that sends every token to expert 5
    first.  Capacity is static and worst-case, so all 64 pairs run."""
    x, router, mats, k = _expert_setup()
    x = jnp.abs(x)
    router = router.at[:, 5].set(10.0)
    routed, lay, _, _, y, pairs = _share(x, router, mats, k, 2, 4, 8)
    assert int(lay["Counts"][1]) == 64 and int(pairs) == int(
        lay["Counts"].sum())
    np.testing.assert_allclose(y, _dense_experts(
        x, routed["TopkIdx"], routed["TopkWeight"], mats, (4, 5)),
        rtol=1e-5, atol=1e-5)


# ---- the grouped Pallas kernels against the loop ---------------------------------

def _routes(kind, n, total, k, first, held, r):
    """Routed ids ``[n, k]``: ``random`` — k distinct of all experts a token;
    ``none_held`` — never a held expert; ``all_held`` — every pair to a held
    expert (the worst case); ``one_empty`` — random, but the second held
    expert gets no token; ``one_heavy`` — every token's first choice is the
    first held expert."""
    outside = [e for e in range(total) if not first <= e < first + held]
    pool = {"none_held": outside, "all_held": range(first, first + held),
            "one_empty": [e for e in range(total) if e != first + 1]}.get(
                kind, range(total))
    idx = np.stack([r.permutation(list(pool))[:k] for _ in range(n)])
    if kind == "one_heavy":
        for row in idx:
            if first in row:
                row[list(row).index(first)] = row[0]
            row[0] = first
    return idx.astype("int32")


# name: (n, d, f, total, held, first, k, tile, dtype, routes, tiles a chunk
# if not the rule's, whether every kernel walks its blocked width in two
# blocks)
_GROUPED_CASES = {
    # the long-document cell's tile and held experts, widths reduced
    "longdoc_tile_640_held_16": (640, 128, 256, 32, 16, 8, 8, 640,
                                 "bfloat16", "random", None, False),
    # the latent cell's
    "mtp_tile_384_held_8": (384, 128, 256, 32, 8, 4, 8, 384, "bfloat16",
                            "random", None, False),
    "an_expert_with_no_token": (256, 128, 128, 8, 4, 2, 2, 128, "float32",
                                "one_empty", None, False),
    # 320 pairs of the first held expert: three tiles, in chunks of two
    "an_expert_with_three_tiles": (320, 128, 128, 8, 3, 1, 2, 128,
                                   "float32", "one_heavy", 2, False),
    "no_live_tile": (64, 128, 128, 8, 2, 3, 2, 128, "float32", "none_held",
                     None, False),
    "every_pair_held_in_several_chunks": (256, 128, 128, 8, 4, 0, 4, 128,
                                          "bfloat16", "all_held", 3, False),
    "widths_walked_in_blocks_float32": (192, 256, 512, 8, 4, 2, 2, 128,
                                        "float32", "random", 2, True),
    "widths_walked_in_blocks_bfloat16": (192, 256, 512, 8, 4, 2, 2, 128,
                                         "bfloat16", "random", 2, True),
}


def _grouped_ctx(monkeypatch, platforms=("tpu", "cpu"), **kw):
    """A trace context as the CPU executor makes it, the CPU let into the
    grouped kernels' platforms (interpreted; the program never is)."""
    from paddle_tpu.registry import ComputeContext

    monkeypatch.setattr(moe, "_GROUPED_PLATFORMS", platforms)
    return ComputeContext(key=jax.random.key(0), platform="cpu", **kw)


@pytest.mark.parametrize("case", sorted(_GROUPED_CASES))
def test_grouped_kernels_against_the_loop(case, monkeypatch):
    """``moe_expert_ffn`` and its gradient through the op's compute, the
    grouped kernels (interpreted) against today's loop: ``Out``, ``Pairs``
    and all five gradients; nothing dropped."""
    from paddle_tpu.ops.pallas import grouped_experts as ge

    n, d, f, total, held, first, k, tile, dtype, routes, chunk, halved = \
        _GROUPED_CASES[case]
    r = np.random.RandomState(len(case))
    if chunk is not None:
        monkeypatch.setattr(ge, "_chunk_tiles", lambda held: chunk)
    if halved:
        rule = ge._block
        monkeypatch.setattr(ge, "_block",
                            lambda kind, *a: rule(kind, *a) // 2)
    idx = jnp.asarray(_routes(routes, n, total, k, first, held, r))
    lay = moe.dispatch_layout(idx, first, held, tile)
    ins = {"X": [jnp.asarray(r.randn(n, d), dtype)],
           "TopkWeight": [jnp.asarray(r.rand(n, k), jnp.float32)],
           "GRAD::Out": [jnp.asarray(r.randn(n, d), dtype)]}
    for slot, shape in (("Gate", (d, f)), ("Up", (d, f)), ("Down", (f, d))):
        ins[slot] = [jnp.asarray(r.randn(held, *shape) * 0.3, dtype)]
    ins.update({s: [lay[s]] for s in moe._LAYOUT})
    routed = int(np.sum((np.asarray(idx) >= first)
                        & (np.asarray(idx) < first + held)))
    live = int(lay["NumTiles"][0])
    assert live > ge._chunk_tiles(held) or chunk is None

    def run(ctx):
        return jax.jit(lambda ins: (
            moe._ffn_compute(ins, {"tile": tile}, ctx, 0),
            moe._ffn_grad_compute(ins, {"tile": tile}, ctx, 0)))(ins)
    noted, (want, want_g) = _bodies_noted(
        lambda: run(_grouped_ctx(monkeypatch, ("tpu",))))
    assert noted == {"moe_expert_ffn:loop": 1, "moe_expert_ffn_grad:loop": 1}
    noted, (got, got_g) = _bodies_noted(lambda: run(_grouped_ctx(monkeypatch)))
    assert noted == {"moe_expert_ffn:grouped": 1,
                     "moe_expert_ffn_grad:grouped": 1}
    # dropless: every routed pair computed, by both bodies
    assert float(got["Pairs"][0]) == float(want["Pairs"][0]) == routed
    # the forward rounds where the loop rounds; the backward takes c after
    # the product dy Wd^T, not before it: one rounding fewer in bf16
    tol = 1e-5 if dtype == "float32" else 2e-2

    def close(a, b, tol):
        a, b = (np.asarray(v, "float32") for v in (a, b))
        assert a.shape == b.shape
        scale = np.abs(b).max()
        assert np.abs(a - b).max() <= tol * max(scale, 1e-6), (
            np.abs(a - b).max(), scale)
    assert got["Out"].dtype == want["Out"].dtype
    close(got["Out"], want["Out"], 1e-5 if dtype == "float32" else 1e-2)
    for slot in ("X", "TopkWeight", "Gate", "Up", "Down"):
        (a,), (b,) = got_g["GRAD::" + slot], want_g["GRAD::" + slot]
        assert a.dtype == b.dtype
        close(a, b, tol)
    counts = np.asarray(lay["Counts"])
    for slot in ("Gate", "Up", "Down"):
        grad = np.asarray(got_g["GRAD::" + slot][0], "float32")
        for e in np.flatnonzero(counts == 0):       # exact zeros, not small
            assert not grad[e].any()
    if routes == "one_empty":
        assert counts[1] == 0 and live > 0
    if routes == "one_heavy":
        assert counts[0] == n and -(-n // tile) >= 3
    if routes == "none_held":
        assert live == 0 and not np.asarray(got["Out"], "float32").any()


@pytest.mark.parametrize("case", ["taken", "cpu", "mesh", "flag_off",
                                  "odd_width", "odd_tile", "float16",
                                  "mixed_dtypes", "over_budget"])
def test_grouped_rule_reads_only_what_the_op_observes(case, monkeypatch):
    """Whatever ``supported()`` or the trace's platform refuses takes the
    loop, and the op's note says ``loop``."""
    import types

    from paddle_tpu import flags
    from paddle_tpu.ops.pallas import grouped_experts as ge
    from paddle_tpu.parallel.mesh import make_mesh

    tpu = types.SimpleNamespace(platform="tpu", mesh=None)
    tile, dtype = 640, jnp.bfloat16

    def spec(d=2048, f=768, dtype=dtype, gate_dtype=None):
        return (jax.ShapeDtypeStruct((8192, d), dtype),
                jax.ShapeDtypeStruct((16, d, f), gate_dtype or dtype))

    def body(ctx, x, gate, tile=tile):
        noted, (fwd, bwd) = _bodies_noted(
            lambda: moe._bodies(ctx, "moe_expert_ffn", x, gate, tile))
        loop = fwd is moe.expert_ffn and bwd is moe.expert_ffn_grad
        assert noted == {"moe_expert_ffn:%s" % ("loop" if loop
                                                else "grouped"): 1}
        return "loop" if loop else "grouped"
    if case == "taken":
        assert body(tpu, *spec()) == "grouped"             # both cells'
        assert body(tpu, *spec(), tile=384) == "grouped"
        assert body(tpu, *spec(dtype=jnp.float32), tile=256) == "grouped"
    elif case == "cpu":
        assert body(types.SimpleNamespace(platform="cpu", mesh=None),
                    *spec()) == "loop"
        assert body(None, *spec()) == "loop"
    elif case == "mesh":
        meshed = types.SimpleNamespace(platform="tpu",
                                       mesh=make_mesh((2, 4), ("dp", "tp")))
        assert body(meshed, *spec()) == "loop"
    elif case == "flag_off":
        monkeypatch.setitem(flags._FLAGS, "pallas_kernels", False)
        assert body(tpu, *spec()) == "loop"
    elif case == "odd_width":
        assert body(tpu, *spec(d=2000)) == "loop"
        assert body(tpu, *spec(f=700)) == "loop"
    elif case == "odd_tile":
        # a tile's rows are lanes of the weight gradients' operands
        assert body(tpu, *spec(), tile=648) == "loop"
        assert body(tpu, *spec(), tile=64) == "loop"
        assert body(tpu, *spec(), tile=128) == "grouped"
    elif case == "float16":
        assert body(tpu, *spec(dtype=jnp.float16)) == "loop"
    elif case == "mixed_dtypes":
        assert body(tpu, *spec(gate_dtype=jnp.float32)) == "loop"
    else:
        # no block leaves a step of these inside the budget
        assert body(tpu, *spec(), tile=8192) == "loop"
        assert body(tpu, *spec(dtype=jnp.float32), tile=1024) == "loop"
        monkeypatch.setattr(ge, "_VMEM_BUDGET", 4 * 1024 * 1024)
        assert body(tpu, *spec()) == "loop"


def test_grouped_blocking_follows_from_the_shapes_and_the_budget():
    """The widest block of F that fits, a chunk of whole tiles: at the two
    cells' shapes what ``tests/test_packed_attention_mosaic.py`` compiles."""
    from paddle_tpu.ops.pallas import grouped_experts as ge

    def blocks(tile):
        return {kind: ge._block(kind, 8192, tile, 2048, 768, 2)
                for kind in ge._KINDS}
    assert blocks(640) == {"gate_up": 768, "down": 512, "rows": 384,
                           "dx": 512, "weights": 384}
    assert blocks(384) == {"gate_up": 768, "down": 512, "rows": 768,
                           "dx": 512, "weights": 384}
    for tile in (640, 384):
        for kind, blk in blocks(tile).items():
            assert ge._step_bytes(kind, 8192, tile, 2048, 768, blk,
                                  2) <= ge._VMEM_BUDGET
    # the resident [N, D-block] is what bounds the tokens a step may hold
    assert ge._block("down", 65536, 640, 2048, 768, 2) is None
    # a chunk: the held experts' tiles and a quarter more, whatever the
    # capacity (119 and 179 tiles in the two cells)
    assert (ge._chunk_tiles(16), ge._chunk_tiles(8)) == (20, 10)


def _cfg(tiny=True):
    _, cfg, traffic = harness.resolve_cell(harness.load_benchmark(ROOT), CELL,
                                           tiny=tiny)
    return cfg, traffic


def test_the_shares_of_a_layer_add_up_to_the_uncut_reference():
    """Four shares of a small layer (8 experts, two held each), each with
    its own (held, first), through the program's ops; what every share
    computes alike (the attention branch and the residual) counted once;
    against the reference's layer holding all 8."""
    cfg, _ = _cfg()
    ref = harness.load_reference(cfg["reference"])
    whole = dict(cfg, num_local_experts=8, first_local_expert=0,
                 num_hidden_layers=1)
    p = weights.make_weights(ref.param_spec(whole), 3)
    x = jnp.asarray(_rand((64, cfg["hidden_size"]), 4))
    uncut = ref.layer(p, "l0.", x, whole, (8, 0), 32)
    # the part every share computes alike: x + attention
    h = ref.rms_norm(x, p["l0.ln1.g"], cfg["rms_norm_eps"])
    common = x + ref.f32_matmul(ref.attention(p, "l0.", h, whole, 32,
                                              ref.f32_matmul),
                                p["l0.attn.o"])
    h2 = ref.rms_norm(common, p["l0.ln2.g"], cfg["rms_norm_eps"])
    total, pairs = common, 0
    for first in (0, 2, 4, 6):
        mats = [jnp.stack([p["l0.moe.e%d.%s" % (e, m)]
                           for e in (first, first + 1)])
                for m in ("gate", "up", "down")]
        routed = moe._router_compute(
            {"X": [h2], "W": [p["l0.moe.router"]]},
            {"top_k": cfg["num_experts_per_tok"]}, None, 0)
        lay = moe.dispatch_layout(routed["TopkIdx"], first, 2, 8)
        y, n = moe.expert_ffn(h2, routed["TopkWeight"], *mats,
                              tuple(lay[s] for s in moe._LAYOUT), 8)
        one = ref.layer(p, "l0.", x, whole, (2, first), 32)
        np.testing.assert_allclose(common + y, one, rtol=1e-4, atol=1e-5)
        total, pairs = total + y, pairs + int(n)
    assert pairs == 64 * cfg["num_experts_per_tok"]       # every pair, once
    np.testing.assert_allclose(total, uncut, rtol=1e-4, atol=1e-5)


# ---- the model -------------------------------------------------------------------

def _train(precision, seed=5):
    from benchmark.generators import train_lm_steps as gen

    cfg, traffic = _cfg()
    cfg = dict(cfg, precision=precision)
    ref = harness.load_reference(cfg["reference"])
    batches = gen.make_batches(traffic, cfg["vocab_size"], seed)
    w0 = gen.seeded_weights(ref.param_spec(cfg), cfg, seed)
    want = gen.reference_readings(ref, cfg, batches, w0, ref.f32_matmul, 3)
    model = harness.load_module("models", cfg["builder"]).build_train(
        cfg, traffic, jax.devices()[:1])
    model.set_weights(w0)
    feeds = [model.make_feed(b) for b in batches]
    prog = gen.program_readings(model, feeds, w0, cfg["adam_beta1"], 3,
                                want["first_grad"], traffic["seq"])
    return cfg, model, prog, want, gen


def test_tiny_model_trains_like_the_plain_reference_in_float32():
    """Three losses, the first gradient leaf by leaf, three Adam steps, the
    first layer's selection and the dropless count: in float32 the program
    and the reference are the same mathematics."""
    cfg, model, prog, want, gen = _train("float32")
    for a, b in zip(prog["losses"], want["losses"]):
        assert abs(a - b) / b < 2e-6
    assert gen.rel_error_rms(prog["grad_errors"], want["grad_norms"]) < 1e-5
    assert gen.worst_leaf_gap(prog["grad_norms"], want["grad_norms"])[0] < 1e-5
    assert gen.worst_leaf_gap(prog["update_norms"],
                              want["update_norms"])[0] < 1e-4
    assert gen.overlap(prog["selected"], want["selected"]) == 1.0
    assert gen.dropped_pairs(prog["stats"]) == 0
    assert all(s["pairs_computed"] > 0 for s in prog["stats"])
    # the frozen indexer: no gradient, no Adam state, and it did not move
    names = set(model.scope.local_var_names()) \
        if hasattr(model.scope, "local_var_names") else None
    for leaf in ("l0.idx.q", "l0.idx.k", "l0.idx.w", "l0.idx.k_g",
                 "l1.idx.k_b"):
        assert model.scope.find_var(leaf + "_moment1_0") is None
        assert leaf not in want["grad_norms"]
    assert model.scope.find_var("l0.attn.q_moment1_0") is not None
    assert names is None or "l0.idx.q" in names
    model.close()


def test_program_lists_every_new_op_under_its_own_type():
    cfg, traffic = _cfg()
    model = harness.load_module("models", cfg["builder"]).build_train(
        cfg, traffic, jax.devices()[:1])
    types = [op.type for op in model.main.global_block().ops]
    n = cfg["num_hidden_layers"]
    for t in ("indexer_score", "select_topk_keys", "fused_attention",
              "moe_router", "moe_dispatch", "moe_expert_ffn",
              "fused_attention_grad", "moe_router_grad",
              "moe_expert_ffn_grad"):
        assert types.count(t) == n, t
    assert types.count("rms_norm") == 4 * n + 1
    # the indexer's q and k; the attention's ride on its op (ISSUE 50)
    assert types.count("rotary_embedding") == 2 * n
    assert "transpose" not in types
    for t in ("indexer_score_grad", "select_topk_keys_grad",
              "moe_dispatch_grad"):
        assert t not in types
    model.close()


# ---- mixed precision, step counters ---------------------------------------------

def test_amp_colours_of_the_new_ops_leave_the_fingerprinted_lists_alone():
    """The indexer's and the experts' products are white, the router
    black, by defaults that sit OUTSIDE the lists AMPPolicy's repr prints:
    an AMP program without these ops keeps its fingerprint (and its
    compiled module's name); a custom list still overrides a default."""
    from paddle_tpu.contrib import mixed_precision as mp

    lists = mp.AutoMixedPrecisionLists()
    assert lists.colour("indexer_score") == "white"
    assert lists.colour("moe_expert_ffn") == "white"
    assert lists.colour("moe_router") == "black"
    for gray in ("rms_norm", "rotary_embedding", "swiglu",
                 "select_topk_keys", "moe_dispatch", "layer_norm"):
        assert lists.colour(gray) is None
    assert lists.colour("mul") == "white" and lists.colour("adam") == "black"
    text = repr(mp.AMPPolicy())
    assert "moe" not in text and "indexer" not in text
    custom = mp.AutoMixedPrecisionLists(custom_black_list=["moe_expert_ffn"])
    assert custom.colour("moe_expert_ffn") == "black"
    assert "moe_expert_ffn" in repr(mp.AMPPolicy(custom))
    # the expert op rounds its own operands: routing weights stay float32
    ins = {"X": [jnp.ones((2, 2), jnp.float32)],
           "TopkWeight": [jnp.ones((2, 1), jnp.float32)]}
    out = mp.AMPPolicy().cast_inputs("moe_expert_ffn_grad", ins)
    assert out["TopkWeight"][0].dtype == jnp.float32
    assert mp.AMPPolicy().cast_inputs("indexer_score", ins)["X"][0].dtype \
        == jnp.bfloat16
    assert mp.AMPPolicy().cast_inputs(
        "moe_router", {"X": [jnp.ones((2,), jnp.bfloat16)]})["X"][0].dtype \
        == jnp.float32


def test_step_counters_ride_the_loss_into_the_step_record(tmp_path):
    """Fetched to the host with the loss, the builder's counters land in
    that step's StepStats record; left on the device they cost nothing and
    are left out."""
    from paddle_tpu import monitor
    from paddle_tpu.models import sparse_moe_decoder as smd

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        tok = fluid.layers.data("tok", shape=[32, 1], dtype="int64")
        lbl = fluid.layers.data("lbl", shape=[32, 1], dtype="int64")
        loss, stats, _ = smd.decoder_lm(tok, lbl, 64, 1, 32, 4, 2, 8,
                                        (2, 4, 1), 16, 2, 2, 8, 8,
                                        expert_tile=8)
    assert main.step_stats == (stats.name, smd.STEP_STATS)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    t = np.random.RandomState(0).randint(0, 64, (2, 32, 1)).astype("int64")
    monitor.enable(log_dir=str(tmp_path))
    try:
        _, st = exe.run(main, feed={"tok": t, "lbl": t},
                        fetch_list=[loss, stats])
        exe.run(main, feed={"tok": t, "lbl": t}, fetch_list=[loss, stats],
                return_numpy=False)
        exe.run(main, feed={"tok": t, "lbl": t}, fetch_list=[loss])
    finally:
        monitor.disable()
    import glob
    import json
    recs = [json.loads(line) for f in glob.glob(str(tmp_path / "*.jsonl"))
            for line in open(f)]
    steps = [r for r in recs if r.get("event") == "step_stats"]
    assert len(steps) == 3
    assert [steps[0][n] for n in smd.STEP_STATS] == st.tolist()
    assert steps[0]["moe_pairs_routed"] == steps[0]["moe_pairs_computed"] > 0
    assert 0 < steps[0]["selected_key_share"] <= 1
    for later in steps[1:]:
        assert not set(smd.STEP_STATS) & set(later)
