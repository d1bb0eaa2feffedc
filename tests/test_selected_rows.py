"""SelectedRows sparse-gradient tests (reference selected_rows.h /
lookup_table_op.cc sparse path / optimizer SelectedRows kernels:
sparse-vs-dense parity, lazy-update semantics, duplicate-row merging,
multi-use accumulation, and the mesh-sharded embedding path)."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import compile_cache
from paddle_tpu.framework import grad_var_name
from paddle_tpu.param_attr import ParamAttr

V, D = 20, 6


def _build(is_sparse, opt_factory, seed=13):
    fluid.default_main_program().random_seed = seed
    fluid.default_startup_program().random_seed = seed
    ids = fluid.layers.data("ids", shape=[4, 1], dtype="int64")
    y = fluid.layers.data("y", shape=[1], dtype="float32")
    emb = fluid.layers.embedding(
        ids, size=[V, D], is_sparse=is_sparse,
        param_attr=ParamAttr(name="emb_w"))
    pooled = fluid.layers.reduce_mean(emb, dim=1)          # [B, D]
    pred = fluid.layers.fc(pooled, size=1, act=None,
                           param_attr=ParamAttr(name="fc_w"),
                           bias_attr=ParamAttr(name="fc_b"))
    loss = fluid.layers.mean(fluid.layers.square(
        fluid.layers.elementwise_sub(pred, y)))
    opt_factory().minimize(loss)
    return loss


def _batches(steps=8, b=8):
    rng = np.random.RandomState(0)
    out = []
    for _ in range(steps):
        ids = rng.randint(0, V, (b, 4, 1)).astype("int64")  # dup rows likely
        yv = rng.rand(b, 1).astype("float32")
        out.append({"ids": ids, "y": yv})
    return out


def _train(is_sparse, opt_factory, steps=8):
    from paddle_tpu.framework import program_guard

    scope = fluid.Scope()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.scope_guard(scope), program_guard(main, startup):
        loss = _build(is_sparse, opt_factory)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        losses = [
            float(np.asarray(exe.run(main, feed=b,
                                     fetch_list=[loss])[0]).ravel()[0])
            for b in _batches(steps)
        ]
        emb_w = np.asarray(scope.var("emb_w"))
    return losses, emb_w


@pytest.mark.parametrize("opt", [
    lambda: fluid.optimizer.SGD(learning_rate=0.1),
    lambda: fluid.optimizer.Adagrad(learning_rate=0.1),
])
def test_sparse_matches_dense(opt):
    """For SGD/Adagrad a zero dense grad row is a no-op, so lazy sparse
    updates must match the dense path exactly.  (Momentum/Adam are NOT
    expected to match: their dense kernels keep moving untouched rows via
    velocity/moment decay while the reference sparse kernels are lazy —
    covered by the laziness tests below.)"""
    dense_losses, dense_w = _train(False, opt)
    sparse_losses, sparse_w = _train(True, opt)
    np.testing.assert_allclose(dense_losses, sparse_losses, rtol=1e-4)
    np.testing.assert_allclose(dense_w, sparse_w, rtol=1e-4, atol=1e-6)


def test_sparse_momentum_is_lazy():
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        loss = _build(True, lambda: fluid.optimizer.Momentum(
            learning_rate=0.1, momentum=0.9))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        ids1 = np.array([[[0], [1], [2], [3]]] * 2, "int64")
        exe.run(feed={"ids": ids1, "y": np.ones((2, 1), "float32")},
                fetch_list=[loss])
        w1 = np.asarray(scope.var("emb_w")).copy()
        ids2 = np.array([[[10], [11], [12], [13]]] * 2, "int64")
        exe.run(feed={"ids": ids2, "y": np.ones((2, 1), "float32")},
                fetch_list=[loss])
        w2 = np.asarray(scope.var("emb_w"))
        np.testing.assert_array_equal(w1[:4], w2[:4])   # frozen
        assert np.abs(w2[10:14] - w1[10:14]).sum() > 0


def test_sparse_adam_is_lazy():
    """Reference lazy-adam semantics: a row not touched this step keeps
    bit-identical param + moments (dense adam keeps moving it via
    momentum decay)."""
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        loss = _build(True, lambda: fluid.optimizer.Adam(learning_rate=0.1))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())

        # step 1: touch rows {0..3}
        ids1 = np.array([[[0], [1], [2], [3]]] * 2, "int64")
        exe.run(feed={"ids": ids1, "y": np.ones((2, 1), "float32")},
                fetch_list=[loss])
        w_after1 = np.asarray(scope.var("emb_w")).copy()
        moment_names = [n for n in scope.local_var_names()
                        if "emb_w" in n and "moment" in n]
        assert moment_names, list(scope.local_var_names())
        m1_after1 = np.asarray(scope.var(moment_names[0])).copy()

        # step 2: touch rows {10..13} only
        ids2 = np.array([[[10], [11], [12], [13]]] * 2, "int64")
        exe.run(feed={"ids": ids2, "y": np.ones((2, 1), "float32")},
                fetch_list=[loss])
        w_after2 = np.asarray(scope.var("emb_w"))

        # rows 0..3 untouched in step 2: bit-identical
        np.testing.assert_array_equal(w_after1[:4], w_after2[:4])
        # rows 10..13 did move
        assert np.abs(w_after2[10:14] - w_after1[10:14]).sum() > 0
        assert np.isfinite(m1_after1).all()


def test_sparse_grad_densifies_to_dense_grad():
    """get_tensor_from_selected_rows(lookup grad) == the dense grad."""
    ids = fluid.layers.data("ids", shape=[3, 1], dtype="int64")
    emb_sparse = fluid.layers.embedding(
        ids, size=[V, D], is_sparse=True,
        param_attr=ParamAttr(name="w_sp"))
    loss = fluid.layers.reduce_sum(
        fluid.layers.elementwise_mul(emb_sparse, emb_sparse))
    fluid.append_backward(loss)
    g = fluid.default_main_program().global_block().create_var(
        name="dense_of_sparse", shape=[V, D], dtype="float32")
    fluid.default_main_program().global_block().append_op(
        type="get_tensor_from_selected_rows",
        inputs={"X": [grad_var_name("w_sp")]},
        outputs={"Out": [g]})

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(1)
    idv = rng.randint(0, V, (4, 3, 1)).astype("int64")
    idv[0, 0, 0] = idv[0, 1, 0] = 5        # duplicate rows
    (gd,) = exe.run(feed={"ids": idv}, fetch_list=[g])

    scope = fluid.global_scope()
    w = np.asarray(scope.var("w_sp"))
    ref = np.zeros((V, D), "float32")
    for i in idv.reshape(-1):
        ref[i] += 2.0 * w[i]
    np.testing.assert_allclose(gd, ref, rtol=1e-5)


def test_embedding_used_twice_accumulates():
    """Two lookups on one table: sparse contributions concatenate."""
    a = fluid.layers.data("a", shape=[2, 1], dtype="int64")
    b = fluid.layers.data("b", shape=[2, 1], dtype="int64")
    ea = fluid.layers.embedding(a, size=[V, D], is_sparse=True,
                                param_attr=ParamAttr(name="w2"))
    eb = fluid.layers.embedding(b, size=[V, D], is_sparse=True,
                                param_attr=ParamAttr(name="w2"))
    loss = fluid.layers.reduce_sum(fluid.layers.elementwise_add(ea, eb))
    fluid.optimizer.SGD(learning_rate=1.0).minimize(loss)

    scope = fluid.global_scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    w0 = np.asarray(scope.var("w2")).copy()
    av = np.array([[[1], [2]]], "int64")
    bv = np.array([[[2], [3]]], "int64")
    exe.run(feed={"a": av, "b": bv}, fetch_list=[loss])
    w1 = np.asarray(scope.var("w2"))
    delta = w0 - w1
    # d(loss)/d(w[r]) = count of r among all looked-up ids
    np.testing.assert_allclose(delta[1], np.ones(D), atol=1e-6)
    np.testing.assert_allclose(delta[2], 2 * np.ones(D), atol=1e-6)
    np.testing.assert_allclose(delta[3], np.ones(D), atol=1e-6)
    np.testing.assert_allclose(delta[0], np.zeros(D), atol=1e-6)


def test_distributed_embedding_sharding_fn():
    """is_distributed tables are auto-row-sharded by the helper."""
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.parallel import (
        make_mesh, distributed_embedding_sharding_fn)

    ids = fluid.layers.data("ids", shape=[4, 1], dtype="int64")
    emb = fluid.layers.embedding(
        ids, size=[V, D], is_distributed=True,
        param_attr=ParamAttr(name="dist_w"))
    other = fluid.layers.fc(fluid.layers.reduce_mean(emb, dim=1), size=2)

    mesh = make_mesh((4, 2), ("dp", "ep"))
    fn = distributed_embedding_sharding_fn(
        fluid.default_main_program(), mesh)
    assert fn("dist_w", (V, D)) == P("ep")
    assert fn("fc_0.w_0", (D, 2)) is None
    # indivisible height falls back to replicated
    assert fn("dist_w", (V + 1, D)) is None


def test_sharded_embedding_parallel_parity():
    """Embedding table sharded over the mesh (the pserver sharded-table
    replacement): loss parity with the single-device run, sparse grads
    under pjit."""
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.parallel import make_mesh

    def opt():
        return fluid.optimizer.SGD(learning_rate=0.1)

    dense_losses, dense_w = _train(False, opt)

    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        loss = _build(True, opt)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())

        bs = fluid.BuildStrategy()
        bs.param_sharding_fn = lambda name, shape: (
            P("dp") if name == "emb_w" and shape and shape[0] % 4 == 0
            else None)
        mesh = make_mesh((4,), ("dp",))
        pe = fluid.ParallelExecutor(loss_name=loss.name, build_strategy=bs,
                                    mesh=mesh, scope=scope)
        losses = [
            float(np.asarray(pe.run(feed=b, fetch_list=[loss])[0]).ravel()[0])
            for b in _batches()
        ]
        w = np.asarray(scope.var("emb_w"))
    np.testing.assert_allclose(dense_losses, losses, rtol=1e-4)
    np.testing.assert_allclose(dense_w, w, rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# ISSUE 15: end-to-end SelectedRows path — bit-level parity, survivability
# through clip/regularizer aggregation, warm-path lowering count, and the
# row-sharded mesh update
# ---------------------------------------------------------------------------

def _build_tower(is_sparse, opt_factory, vocab=V, clip=None, reg=None,
                 seed=5):
    """Embedding -> mean-pool -> fc tower with optional global clip and
    per-param regularizer on the table."""
    main = fluid.default_main_program()
    main.random_seed = seed
    fluid.default_startup_program().random_seed = seed
    ids = fluid.layers.data("ids", shape=[4, 1], dtype="int64")
    y = fluid.layers.data("y", shape=[1], dtype="float32")
    emb = fluid.layers.embedding(
        ids, size=[vocab, D], is_sparse=is_sparse,
        param_attr=ParamAttr(name="emb_w", regularizer=reg))
    pred = fluid.layers.fc(fluid.layers.reduce_mean(emb, dim=1), size=1,
                           param_attr=ParamAttr(name="fc_w"),
                           bias_attr=ParamAttr(name="fc_b"))
    loss = fluid.layers.mean(fluid.layers.square(
        fluid.layers.elementwise_sub(pred, y)))
    if clip is not None:
        fluid.clip.set_gradient_clip(clip)
    opt_factory().minimize(loss)
    return loss


def _dup_batches(vocab, steps=2, b=8):
    rng = np.random.RandomState(0)
    out = []
    for _ in range(steps):
        ids = rng.randint(0, vocab, (b, 4, 1)).astype("int64")
        ids[0, 0, 0] = ids[0, 1, 0] = 3      # guaranteed duplicate row
        out.append({"ids": ids, "y": rng.rand(b, 1).astype("float32")})
    return out


def _one_run(is_sparse, opt_factory, vocab=V, steps=1, clip=None,
             reg=None, scope=None, table="emb_w"):
    from paddle_tpu.framework import program_guard

    scope = scope or fluid.Scope()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.scope_guard(scope), program_guard(main, startup):
        loss = _build_tower(is_sparse, opt_factory, vocab=vocab,
                            clip=clip, reg=reg)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        losses = [
            float(np.asarray(exe.run(main, feed=f,
                                     fetch_list=[loss])[0]).ravel()[0])
            for f in _dup_batches(vocab, steps)
        ]
        w = np.array(np.asarray(scope.var(table)), copy=True)
        slots = {n: np.array(np.asarray(scope.var(n)), copy=True)
                 for n in scope.local_var_names()
                 if n.startswith(table + "_")
                 and ("moment" in n or "velocity" in n)}
    return losses, w, slots


@pytest.mark.parametrize("opt", [
    lambda: fluid.optimizer.Adam(learning_rate=0.1),
    lambda: fluid.optimizer.Adagrad(learning_rate=0.1),
])
def test_sparse_update_bitwise_matches_dense_first_step(opt):
    """Touched rows match the dense update BIT-FOR-BIT (duplicate rows
    included: merge_rows sums duplicates exactly like the dense
    backward's scatter-add), and untouched rows are bit-identical
    trivially — so after one step from identical init the whole table
    and every slot var are bitwise equal across the two paths.  (Adam /
    Adagrad merge duplicates before the kernel; plain SGD scatter-adds
    duplicates sequentially, which is duplicate-safe but associates the
    sum differently — covered by test_sparse_matches_dense at rtol.)"""
    def norm(slots):
        # the unique-name counter differs between the two builds
        # (emb_w_moment1_0 vs _1): key by the stripped slot kind
        return {n.rsplit("_", 1)[0]: a for n, a in slots.items()}

    _, w_sp, s_sp = _one_run(True, opt, steps=1)
    _, w_dn, s_dn = _one_run(False, opt, steps=1)
    np.testing.assert_array_equal(w_sp, w_dn)
    s_sp, s_dn = norm(s_sp), norm(s_dn)
    assert set(s_sp) == set(s_dn) and s_sp
    for n in s_sp:
        np.testing.assert_array_equal(s_sp[n], s_dn[n])


def test_sparse_adam_untouched_moments_bit_stable():
    """The lazy kernel's defining invariant: a row not touched this step
    keeps bit-identical param AND Adam moments across the step."""
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        loss = _build_tower(True, lambda: fluid.optimizer.Adam(
            learning_rate=0.1))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        ids1 = np.array([[[0], [1], [2], [3]]] * 2, "int64")
        exe.run(feed={"ids": ids1, "y": np.ones((2, 1), "float32")},
                fetch_list=[loss])
        moment_names = [n for n in scope.local_var_names()
                        if n.startswith("emb_w_") and "moment" in n]
        assert len(moment_names) >= 2, scope.local_var_names()
        w1 = np.array(np.asarray(scope.var("emb_w")), copy=True)
        m1 = {n: np.array(np.asarray(scope.var(n)), copy=True)
              for n in moment_names}
        ids2 = np.array([[[10], [11], [12], [13]]] * 2, "int64")
        exe.run(feed={"ids": ids2, "y": np.ones((2, 1), "float32")},
                fetch_list=[loss])
        untouched = list(range(4)) + list(range(14, V))
        w2 = np.asarray(scope.var("emb_w"))
        np.testing.assert_array_equal(w1[untouched], w2[untouched])
        for n in moment_names:
            m2 = np.asarray(scope.var(n))
            np.testing.assert_array_equal(m1[n][untouched],
                                          m2[untouched])
            # and the touched rows' moments DID move
            assert np.abs(m2[10:14] - m1[n][10:14]).sum() > 0


def test_sparse_grad_survives_global_clip_and_decay():
    """The survivability tentpole: global-norm clip + L2 decay on an
    is_sparse table no longer densify (or crash) — the summed gradient
    var keeps SELECTED_ROWS type through clip/regularizer appenders,
    the optimizer still sees a SelectedRows gradient (lazy semantics
    hold), and the numerics match the dense path."""
    from paddle_tpu.core import VarType

    def opt():
        return fluid.optimizer.Adam(learning_rate=0.1)

    clip = fluid.clip.GradientClipByGlobalNorm(clip_norm=0.5)
    reg = fluid.regularizer.L2Decay(1e-3)

    # (a) laziness survives the whole aggregation chain
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        loss = _build_tower(True, opt, clip=clip, reg=reg)
        main = fluid.default_main_program()
        adam_grads = [
            op.inputs["Grad"][0] for op in main.global_block().ops
            if op.type == "adam"
            and op.inputs["Param"][0] == "emb_w"]
        assert adam_grads, "no adam op on emb_w"
        gvar = main.global_block()._find_var_recursive(adam_grads[0])
        assert gvar.type == VarType.SELECTED_ROWS, (
            "clip/decay densified the sparse gradient")
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        ids1 = np.array([[[0], [1], [2], [3]]] * 2, "int64")
        exe.run(feed={"ids": ids1, "y": np.ones((2, 1), "float32")},
                fetch_list=[loss])
        w1 = np.array(np.asarray(scope.var("emb_w")), copy=True)
        ids2 = np.array([[[10], [11], [12], [13]]] * 2, "int64")
        exe.run(feed={"ids": ids2, "y": np.ones((2, 1), "float32")},
                fetch_list=[loss])
        w2 = np.asarray(scope.var("emb_w"))
        # rows 4..9 never touched: decay must NOT have moved them
        # (the lazy decay applies to touched rows only)
        np.testing.assert_array_equal(w1[4:10], w2[4:10])

    # (b) numeric parity with the dense path under the same global clip
    # (clip is merge-exact: the sparse squared_l2_norm equals the dense
    # grad's, the scale is uniform).  Adagrad, not Adam: a dense zero
    # grad row is a no-op for Adagrad, so lazy == dense over many steps
    # (the lazy-Adam trajectory legitimately diverges once a previously
    # touched row goes untouched — test_sparse_matches_dense's note)
    def adagrad():
        return fluid.optimizer.Adagrad(learning_rate=0.1)

    sp_losses, w_sp, _ = _one_run(True, adagrad, steps=3, clip=clip)
    dn_losses, w_dn, _ = _one_run(False, adagrad, steps=3, clip=clip)
    np.testing.assert_allclose(sp_losses, dn_losses, rtol=1e-4)
    np.testing.assert_allclose(w_sp, w_dn, rtol=1e-4, atol=1e-6)

    # (c) decay semantics: on the FIRST step from identical init the
    # touched rows' decayed update matches the dense regularized update
    # (same merged grad + coeff*w term, zero prior moments), while the
    # dense path moves every untouched row too (full-table decay) and
    # the lazy path leaves them bit-identical — the documented
    # difference that keeps the update O(touched)
    batch = _dup_batches(V, steps=1)[0]
    touched = sorted(set(batch["ids"].ravel().tolist()))
    untouched = [r for r in range(V) if r not in touched]
    _, w_sp1, _ = _one_run(True, opt, steps=1, reg=reg)
    _, w_dn1, _ = _one_run(False, opt, steps=1, reg=reg)
    np.testing.assert_allclose(w_sp1[touched], w_dn1[touched],
                               rtol=1e-6, atol=1e-7)
    assert untouched
    assert np.abs(w_dn1[untouched] - w_sp1[untouched]).max() > 0


def test_warm_sparse_step_pays_zero_lowerings():
    """Acceptance: the sparse path costs no extra trace/compile on the
    warm step path — after the cold step, further steps (same feed
    signature) lower nothing."""
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        loss = _build_tower(True, lambda: fluid.optimizer.Adam(
            learning_rate=0.1))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        batches = _dup_batches(V, steps=3)
        exe.run(feed=batches[0], fetch_list=[loss])      # cold
        with compile_cache.count_compiles() as n:
            for f in batches[1:]:
                exe.run(feed=f, fetch_list=[loss])
        assert n()["jax_lowerings"] == 0, \
            "warm sparse step paid lowerings: %s" % n()


def _build_dist_tower(vocab, opt_factory, seed=5):
    main = fluid.default_main_program()
    main.random_seed = seed
    fluid.default_startup_program().random_seed = seed
    ids = fluid.layers.data("ids", shape=[4, 1], dtype="int64")
    y = fluid.layers.data("y", shape=[1], dtype="float32")
    emb = fluid.layers.embedding(
        ids, size=[vocab, D], is_sparse=True, is_distributed=True,
        param_attr=ParamAttr(name="emb_w"))
    pred = fluid.layers.fc(fluid.layers.reduce_mean(emb, dim=1), size=1,
                           param_attr=ParamAttr(name="fc_w"),
                           bias_attr=ParamAttr(name="fc_b"))
    loss = fluid.layers.mean(fluid.layers.square(
        fluid.layers.elementwise_sub(pred, y)))
    opt_factory().minimize(loss)
    return loss


def test_rowsharded_pe_sparse_update_engages_and_matches(monkeypatch):
    """The mesh tentpole on a 4-virtual-device dp x ep mesh: the
    row-sharded table's lookup AND lazy update run through the explicit
    shard_map lowerings (spied), optimizer slot vars inherit the row
    sharding, losses/table match the single-device sparse run, and
    untouched rows stay bit-stable across steps ON the mesh."""
    from paddle_tpu.framework import program_guard
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel import embedding as emb_mod

    def opt():
        return fluid.optimizer.Adam(learning_rate=0.1)

    batches = _dup_batches(V, steps=3)

    # single-device sparse reference
    scope = fluid.Scope()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.scope_guard(scope), program_guard(main, startup):
        loss = _build_tower(True, opt)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        ref = [float(np.asarray(exe.run(main, feed=f,
                                        fetch_list=[loss])[0]).ravel()[0])
               for f in batches]
        ref_w = np.array(np.asarray(scope.var("emb_w")), copy=True)

    calls = {"lookup": 0, "update": 0}
    orig_lookup = emb_mod.sharded_sparse_lookup
    orig_update = emb_mod.sharded_sparse_update

    def spy_lookup(*a, **kw):
        out = orig_lookup(*a, **kw)
        if out is not None:
            calls["lookup"] += 1
        return out

    def spy_update(*a, **kw):
        out = orig_update(*a, **kw)
        if out is not None:
            calls["update"] += 1
        return out

    monkeypatch.setattr(emb_mod, "sharded_sparse_lookup", spy_lookup)
    monkeypatch.setattr(emb_mod, "sharded_sparse_update", spy_update)

    scope = fluid.Scope()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.scope_guard(scope), program_guard(main, startup):
        loss = _build_dist_tower(V, opt)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        mesh = make_mesh((2, 2), ("dp", "ep"))
        bs = fluid.BuildStrategy()
        bs.param_sharding_fn = emb_mod.distributed_embedding_sharding_fn(
            main, mesh)
        pe = fluid.ParallelExecutor(loss_name=loss.name, mesh=mesh,
                                    build_strategy=bs, scope=scope)
        sharded = []
        w_prev = None
        for f in batches:
            sharded.append(float(np.asarray(
                pe.run(feed=f, fetch_list=[loss])[0]).ravel()[0]))
            w_now = np.array(np.asarray(scope.var("emb_w")), copy=True)
            if w_prev is not None:
                touched = set(f["ids"].ravel().tolist())
                stable = [r for r in range(V) if r not in touched]
                np.testing.assert_array_equal(w_prev[stable],
                                              w_now[stable])
            w_prev = w_now
        w = np.asarray(scope.var("emb_w"))
        # slot vars ride the table's row sharding (never a replicated
        # [vocab, D] moment buffer)
        moments = [n for n in scope.local_var_names()
                   if n.startswith("emb_w_") and "moment" in n]
        assert moments
        for n in moments:
            arr = scope.var(n)
            spec = tuple(getattr(arr.sharding, "spec", ()))
            assert spec and spec[0] == "ep", (n, spec)

    assert calls["lookup"] >= 1, "sharded lookup never engaged"
    assert calls["update"] >= 1, "sharded sparse update never engaged"
    np.testing.assert_allclose(sharded, ref, rtol=1e-4)
    np.testing.assert_allclose(w, ref_w, rtol=1e-4, atol=1e-6)


@pytest.mark.slow   # two PE compiles on an 8-device virtual mesh; the
# engagement + parity invariants stay tier-1 via the test above
def test_mesh_sharded_sparse_never_materializes_dense_table_grad():
    """The no-dense-materialization acceptance: per-device argument
    bytes of the row-sharded sparse run carry only the 1/N table+slot
    share, and per-device peak stays far under the replicated run's
    (which holds the full table per device) — i.e. the update never
    all-gathers the table or builds a dense [vocab, D] gradient."""
    from paddle_tpu import monitor
    from paddle_tpu.framework import program_guard
    from paddle_tpu.monitor import program_profile
    from paddle_tpu.parallel import make_mesh
    from paddle_tpu.parallel import embedding as emb_mod

    monitor.enable()
    vocab, ep = 4096, 4

    def opt():
        return fluid.optimizer.Adam(learning_rate=0.1)

    peaks, args_bytes = {}, {}
    for label, shard in (("replicated", False), ("sharded", True)):
        scope = fluid.Scope()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.scope_guard(scope), program_guard(main, startup):
            loss = _build_dist_tower(vocab, opt)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            mesh = make_mesh((2, ep), ("dp", "ep"))
            bs = fluid.BuildStrategy()
            if shard:
                bs.param_sharding_fn = \
                    emb_mod.distributed_embedding_sharding_fn(main, mesh)
            pe = fluid.ParallelExecutor(loss_name=loss.name, mesh=mesh,
                                        build_strategy=bs, scope=scope)
            f = _dup_batches(vocab, steps=1)[0]
            pe.run(feed=f, fetch_list=[loss])
            prof = program_profile.get(
                compile_cache.program_fingerprint(main))
            assert prof is not None, label
            b = prof.breakdown()
            peaks[label] = b["peak_hbm_bytes"]
            args_bytes[label] = b["argument_bytes"]

    table_bytes = vocab * D * 4 * 3      # param + 2 Adam moments
    saved = args_bytes["replicated"] - args_bytes["sharded"]
    # the sharded run sheds ~(1 - 1/ep) of the table+slots per device
    assert saved > table_bytes * (1 - 1.0 / ep) * 0.8, (
        saved, table_bytes)
    # and its peak must stay well under the replicated peak: a dense
    # [vocab, D] grad or an all-gathered table would erase the gap
    assert peaks["sharded"] < peaks["replicated"] - \
        table_bytes * (1 - 1.0 / ep) * 0.5, peaks


@pytest.mark.slow   # ~1e6-row tables: the vocab-scaling acceptance
# drill (the bench rung's predicate, asserted with generous margins;
# run solo — CPU wall clock under concurrent load is noise)
def test_vocab_scaling_sparse_flat_dense_linear():
    """Acceptance: sparse step time ~flat in vocab while dense grows
    linearly — >=3x advantage at vocab=1e6 on CPU (the bench rung
    measures 14x; the test asserts a floor robust to load)."""
    import time as _time

    from paddle_tpu.framework import program_guard

    def opt():
        return fluid.optimizer.Adam(learning_rate=1e-3)

    def step_time(vocab, is_sparse):
        scope = fluid.Scope()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.scope_guard(scope), program_guard(main, startup):
            loss = _build_tower(is_sparse, opt, vocab=vocab)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            feeds = _dup_batches(vocab, steps=5)
            times = []
            for i, f in enumerate(feeds):
                t0 = _time.monotonic()
                out = exe.run(main, feed=f, fetch_list=[loss])
                float(np.asarray(out[0]).ravel()[0])
                if i >= 2:
                    times.append(_time.monotonic() - t0)
        return min(times)

    sp_small = step_time(10_000, True)
    sp_big = step_time(1_000_000, True)
    dn_big = step_time(1_000_000, False)
    assert dn_big / sp_big >= 3.0, (sp_big, dn_big)
    assert sp_big / sp_small < 3.0, (sp_small, sp_big)
