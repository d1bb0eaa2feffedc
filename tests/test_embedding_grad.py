"""The dense gradient of ``lookup_table`` by sorted segments
(``ops/pallas/embedding_grad.py``, interpreted here) against ``jax.vjp`` of
``jnp.take``; the op through a Fluid program with the body forced each way;
the rule that picks the body and the counters that say which it picked."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import compile_cache, flags
from paddle_tpu.ops import manipulation, pallas
from paddle_tpu.ops.pallas import embedding_grad as eg


def _take_vjp(ids, gout, v, padding_idx=None):
    """What the generic gradient computes: the scattered add of float32
    rows, a ``padding_idx`` row zeroed as the forward zeroes it."""
    def fwd(w):
        out = jnp.take(w, ids, axis=0)
        if padding_idx is not None:
            out = out * (ids != padding_idx)[:, None].astype(out.dtype)
        return out
    _, vjp = jax.vjp(fwd, jnp.zeros((v, gout.shape[1]), jnp.float32))
    return vjp(gout.astype(jnp.float32))[0]


def _ids(kind, n, v, rng):
    if kind == "same":
        return np.full(n, v // 3)
    if kind == "distinct":
        return rng.permutation(v)[:n]
    if kind == "ends":                  # the table's first and last rows only
        return rng.choice([0, v - 1], n)
    return rng.integers(0, v, n)        # unsorted, with repeats


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks and chunks of 16 rows at width 512 (8 at 2048 and 2560): a few
    hundred rows then cross many block and chunk boundaries."""
    monkeypatch.setattr(eg, "_BLOCK_BYTES", 16 * 512 * 4)
    pallas.traced.cache_clear()
    yield
    pallas.traced.cache_clear()


CASES = [
    # kind, n, v, d, dtype, padding_idx
    ("same", 40, 72, 512, jnp.float32, None),
    ("distinct", 48, 64, 512, jnp.float32, None),
    ("repeats", 100, 72, 512, jnp.float32, None),
    ("repeats", 100, 72, 512, jnp.bfloat16, None),
    ("repeats", 100, 72, 512, jnp.float32, "hit"),
    ("repeats", 100, 72, 512, jnp.bfloat16, "hit"),
    ("repeats", 100, 72, 512, jnp.float32, "missed"),
    ("repeats", 37, 104, 512, jnp.float32, None),    # N, V off the block
    ("repeats", 1, 8, 128, jnp.float32, None),
    ("ends", 50, 200, 256, jnp.float32, None),       # empty blocks between
    ("repeats", 64, 40, 2048, jnp.float32, None),
    ("repeats", 64, 40, 2048, jnp.bfloat16, None),
    ("repeats", 64, 40, 2560, jnp.float32, None),
    ("repeats", 64, 40, 2560, jnp.bfloat16, "hit"),
    ("repeats", 3000, 1000, 128, jnp.float32, None),  # ~3 rows an id
]


@pytest.mark.parametrize("kind,n,v,d,dtype,pad", CASES)
def test_the_segment_body_is_the_vjp_of_take(small_blocks, kind, n, v, d,
                                             dtype, pad):
    rng = np.random.default_rng(n * 7 + v)
    ids = jnp.asarray(_ids(kind, n, v, rng), jnp.int32)
    gout = jnp.asarray(rng.standard_normal((n, d)), jnp.float32).astype(dtype)
    assert eg.supported(n, v, d, jnp.float32, dtype)
    padding_idx = None
    if pad == "hit":
        padding_idx = int(ids[n // 2])
    elif pad == "missed":
        padding_idx = next(r for r in range(v)
                           if r not in set(np.asarray(ids).tolist()))
    got = eg.embedding_grad(ids, gout, v, padding_idx, interpret=True)
    want = _take_vjp(ids, gout, v, padding_idx)
    assert got.dtype == jnp.float32 and got.shape == (v, d)
    if pad == "hit":
        assert not np.any(np.asarray(got[padding_idx]))
    # float32 sums of the same float32 addends: equal to float32 rounding
    # (another order of a row's addends at most)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_the_work_items_cover_every_block_once_and_every_row_once():
    """The scalars the kernel walks: every block of the table is some
    item's, in order; the live items' ranges tile the sorted rows; a range
    stays inside its chunk and its block; the items past the live ones have
    no rows and repeat the last live item's block and chunk."""
    n, v, block, chunk = 53, 104, 16, 8
    rng = np.random.default_rng(3)
    sid = jnp.sort(jnp.asarray(rng.integers(0, v, n), jnp.int32))
    chunks = -(-n // chunk)
    blk, chunk_of, lo, hi = (np.asarray(a) for a in eg._items(
        sid, n, v, block, chunk, chunks))
    blocks = -(-v // block)
    assert len(blk) == blocks + chunks
    assert list(np.unique(blk)) == list(range(blocks))
    assert np.all(np.diff(blk) >= 0)
    covered = np.concatenate([np.arange(a, b) for a, b in zip(lo, hi)])
    assert list(covered) == list(range(n))
    for b, c, a, z in zip(blk, chunk_of, lo, hi):
        assert 0 <= c < chunks and c * chunk <= a <= z <= (c + 1) * chunk
        assert np.all(np.asarray(sid[a:z]) // block == b)
    live = int(np.max(np.nonzero(hi > lo)[0])) + 1
    assert np.all(blk[live:] == blocks - 1)
    assert np.all(chunk_of[live:] == chunk_of[live:][:1])


# ---- the op through a Fluid program -------------------------------------------------

def _embedding_program(v, d, n, padding_idx=None, is_sparse=False):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        ids = fluid.layers.data("ids", shape=[n, 1], dtype="int64")
        weight = fluid.layers.data("weight", shape=[n, d], dtype="float32")
        emb = fluid.layers.embedding(
            ids, size=[v, d], padding_idx=padding_idx, is_sparse=is_sparse,
            param_attr=fluid.ParamAttr(name="table"))
        loss = fluid.layers.reduce_sum(
            fluid.layers.elementwise_mul(emb, weight))
        fluid.backward.append_backward(loss)
    return main, startup


def _bodies_since(before):
    return {k: n - before.get(k, 0)
            for k, n in compile_cache.stats()["kernel_bodies"].items()
            if k.startswith("lookup_table_grad") and n - before.get(k, 0)}


@pytest.mark.parametrize("padding_idx", [None, 5])
def test_the_op_gives_the_same_gradient_with_either_body(monkeypatch,
                                                         padding_idx):
    """``layers.embedding`` + ``append_backward`` through the executor with
    the body forced each way: the same ``lookup_table_grad`` op, the same
    gradient, and ``kernel_bodies`` says which body each lowering took."""
    v, d, n = 40, 128, 24
    rng = np.random.default_rng(11)
    feed = {"ids": rng.integers(0, v, (2, n, 1)).astype("int64"),
            "weight": rng.standard_normal((2, n, d)).astype("float32")}
    feed["ids"][0, :4, 0] = 5
    grads = {}
    for body in ("xla", "segment"):
        monkeypatch.setattr(manipulation, "_SEGMENT_PLATFORMS",
                            ("tpu", "cpu") if body == "segment" else ())
        compile_cache.clear()
        main, startup = _embedding_program(v, d, n, padding_idx)
        assert [o.type for o in main.global_block().ops
                if o.type.startswith("lookup_table")] \
            == ["lookup_table", "lookup_table_grad"]
        before = dict(compile_cache.stats()["kernel_bodies"])
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            grads[body], = exe.run(main, feed=feed,
                                   fetch_list=["table@GRAD"])
        assert _bodies_since(before) == {"lookup_table_grad:" + body: 1}
    compile_cache.clear()
    want = _take_vjp(jnp.asarray(feed["ids"].reshape(-1), jnp.int32),
                     jnp.asarray(feed["weight"].reshape(-1, d)), v,
                     padding_idx)
    assert np.any(grads["xla"])
    np.testing.assert_allclose(grads["xla"], np.asarray(want), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(grads["segment"], grads["xla"], rtol=1e-6,
                               atol=1e-6)


def test_a_sparse_table_still_emits_the_selected_rows_gradient():
    main, _ = _embedding_program(40, 128, 24, is_sparse=True)
    assert [o.type for o in main.global_block().ops
            if o.type.startswith("lookup_table")] \
        == ["lookup_table", "lookup_table_sparse_grad"]


# ---- the rule and the counters ----------------------------------------------------------

def _ctx(platform, mesh=None):
    return types.SimpleNamespace(platform=platform, mesh=mesh)


# N / V / D of ``lookup_table_grad`` in the one-chip cells (the trunk's and
# the module's lookups of ``train_mtp_8k`` are one shape)
CELL_SHAPES = {
    "phi4_mini_flash.train_reason_4k": (4096, 25008, 2560),
    "keye_vl2_30b_a3b.train_longdoc_8k": (8192, 18992, 2048),
    "joyai_llm_flash.train_mtp_8k": (8192, 16160, 2048),
    "ouro_2_6b.train_loop_4k": (4096, 6144, 2048),
    "transformer_base.train_nmt": (16384, 32000, 512),
}


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_the_rule_at_the_cells_shapes(monkeypatch, cell):
    """On a TPU, one device, every one-chip cell's table takes the segment
    body (float32 tables and rows, whole lane and sublane tiles); under a
    mesh, on the CPU, or with ``FLAGS_pallas_kernels`` off, the generic
    one."""
    n, v, d = CELL_SHAPES[cell]
    f32 = jnp.float32
    assert manipulation.segment_body(_ctx("tpu"), n, v, d, f32, f32)
    assert not manipulation.segment_body(_ctx("tpu", mesh=object()), n, v, d,
                                         f32, f32)
    assert not manipulation.segment_body(_ctx("cpu"), n, v, d, f32, f32)
    monkeypatch.setitem(flags._FLAGS, "pallas_kernels", False)
    assert not manipulation.segment_body(_ctx("tpu"), n, v, d, f32, f32)


@pytest.mark.parametrize("n,v,d,w_dtype,g_dtype,takes", [
    (4096, 25008, 2560, jnp.float32, jnp.bfloat16, True),
    (4096, 25008, 2560, jnp.bfloat16, jnp.bfloat16, False),  # bf16 table
    (4096, 25008, 2560, jnp.float32, jnp.float16, False),
    (4096, 25008, 2500, jnp.float32, jnp.float32, False),    # lanes
    (4096, 25004, 2560, jnp.float32, jnp.float32, False),    # sublanes
    (0, 25008, 2560, jnp.float32, jnp.float32, False),
    (128 * 1024, 32000, 512, jnp.float32, jnp.float32, True),
    (128 * 1024 + 8, 32000, 512, jnp.float32, jnp.float32, False),   # SMEM
    (64, 1024, 128 * 1024, jnp.float32, jnp.float32, True),
    (64, 1024, 256 * 1024, jnp.float32, jnp.float32, False),         # VMEM
])
def test_the_rule_reads_shapes_and_dtypes(n, v, d, w_dtype, g_dtype, takes):
    assert manipulation.segment_body(_ctx("tpu"), n, v, d, w_dtype,
                                     g_dtype) == takes


def test_the_counters_name_the_body_and_the_kernels_trace(monkeypatch):
    """After a lowering ``kernel_bodies`` holds ``lookup_table_grad:segment``
    and ``kernel_traces`` the kernel's sites and traces: two tables of one
    shape in one program are two sites and ONE trace."""
    monkeypatch.setattr(manipulation, "_SEGMENT_PLATFORMS", ("tpu", "cpu"))
    compile_cache.clear()
    pallas.traced.cache_clear()
    v, d, n = 48, 128, 16
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        ids = fluid.layers.data("ids", shape=[n, 1], dtype="int64")
        embs = [fluid.layers.embedding(
            ids, size=[v, d], param_attr=fluid.ParamAttr(name="t%d" % i))
            for i in range(2)]
        loss = fluid.layers.reduce_sum(
            fluid.layers.elementwise_mul(embs[0], embs[1]))
        fluid.backward.append_backward(loss)

    def traces():
        return dict(compile_cache.stats()["kernel_traces"].get(
            "embedding_grad", {"sites": 0, "traces": 0}))
    before, bodies = traces(), dict(compile_cache.stats()["kernel_bodies"])
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.default_rng(2)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        g0, g1 = exe.run(
            main, feed={"ids": rng.integers(0, v, (1, n, 1)).astype("int64")},
            fetch_list=["t0@GRAD", "t1@GRAD"])
    assert _bodies_since(bodies) == {"lookup_table_grad:segment": 2}
    after = traces()
    assert (after["sites"] - before["sites"],
            after["traces"] - before["traces"]) == (2, 1)
    assert np.any(g0) and np.any(g1)
    compile_cache.clear()


def test_a_program_under_a_mesh_keeps_the_generic_body(monkeypatch):
    """``ParallelExecutor`` traces under its mesh: the dense gradient stays
    the scattered add (``lookup_table_grad:xla``) even where the platform
    would let the kernel in, as in the four-chip cell."""
    monkeypatch.setattr(manipulation, "_SEGMENT_PLATFORMS", ("tpu", "cpu"))
    compile_cache.clear()
    v, d, n = 40, 128, 8
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        ids = fluid.layers.data("ids", shape=[n, 1], dtype="int64")
        emb = fluid.layers.embedding(
            ids, size=[v, d], param_attr=fluid.ParamAttr(name="table"))
        loss = fluid.layers.mean(emb)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    before = dict(compile_cache.stats()["kernel_bodies"])
    rng = np.random.default_rng(4)
    with fluid.scope_guard(fluid.Scope()):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        pe = fluid.ParallelExecutor(loss_name=loss.name, main_program=main)
        out, = pe.run(
            feed={"ids": rng.integers(0, v, (16, n, 1)).astype("int64")},
            fetch_list=[loss])
    assert np.isfinite(np.asarray(out)).all()
    assert _bodies_since(before) == {"lookup_table_grad:xla": 1}
    compile_cache.clear()
