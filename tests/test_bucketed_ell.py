"""Degree-bucketed blocked-ELL aggregation: layout, custom VJP, trainer
parity (the paper's §4 operator as the distributed hot path)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core import (
    DistConfig,
    DistributedTrainer,
    GCNConfig,
    prepare_distributed,
)
from repro.core.exchange import scatter_recv, stack_halo_plan
from repro.core.layers import gat_aggregate, gat_attention, gat_scores, init_layer
from repro.graph import (
    build_hierarchical_partitioned_graph,
    build_partitioned_graph,
    rmat_graph,
)
from repro.graph.remote import build_halo_plan
from repro.graph.structure import (
    bucketed_ell_from_csr,
    coo_to_csr,
    ell_from_csr,
    reverse_slot_index,
    stack_bucketed_ells,
    transpose_csr,
)
from repro.kernels import bucketed_aggregate, device_bucketed
from repro.launch.mesh import make_hier_worker_mesh


def _random_coo(rng, n_src, n_dst, hub_degree=0):
    """Random rectangular COO with degree-0 and degree-1 rows plus an
    optional hub row whose degree exceeds every other row's."""
    n_edges = int(rng.integers(1, 4 * max(n_dst, 1)))
    src = rng.integers(0, n_src, n_edges)
    dst = rng.integers(0, n_dst, n_edges)
    if hub_degree:
        src = np.concatenate([src, rng.integers(0, n_src, hub_degree)])
        dst = np.concatenate([dst, np.full(hub_degree, int(rng.integers(0, n_dst)))])
    w = rng.uniform(0.1, 1.0, len(src)).astype(np.float32)
    return src.astype(np.int32), dst.astype(np.int32), w


def _coo_ref(x, src, dst, w, n_dst):
    out = np.zeros((n_dst, x.shape[1]), np.float32)
    np.add.at(out, dst, w[:, None] * np.asarray(x)[src])
    return out


def _device_pair(src, dst, w, n_src, n_dst):
    csr = coo_to_csr(src, dst, w, n_dst, n_src)
    fwd = device_bucketed(stack_bucketed_ells([bucketed_ell_from_csr(csr)]),
                          squeeze=True)
    rev = device_bucketed(
        stack_bucketed_ells([bucketed_ell_from_csr(transpose_csr(csr))]),
        squeeze=True)
    return fwd, rev


class TestEllOverflowRegression:
    def test_max_nnz_overflow_raises(self):
        """Regression: ell_from_csr used to silently drop overflow edges
        (keep = slots < k); it must raise instead."""
        src = np.array([1, 2, 3, 4], np.int32)
        dst = np.zeros(4, np.int32)  # row 0 has degree 4
        csr = coo_to_csr(src, dst, None, 5, 5)
        with pytest.raises(ValueError, match="drop edges"):
            ell_from_csr(csr, max_nnz=2)

    def test_explicit_truncate_keeps_first_slots(self):
        src = np.array([1, 2, 3, 4], np.int32)
        dst = np.zeros(4, np.int32)
        csr = coo_to_csr(src, dst, None, 5, 5)
        idx, w, valid = ell_from_csr(csr, max_nnz=2, on_overflow="truncate")
        assert idx.shape == (5, 2) and valid[0].all()

    def test_bucketed_is_lossless_past_any_cap(self):
        """The spill path: bucketed_ell_from_csr keeps every edge that a
        capped single-K layout would drop."""
        rng = np.random.default_rng(0)
        src, dst, w = _random_coo(rng, 32, 32, hub_degree=50)
        csr = coo_to_csr(src, dst, w, 32, 32)
        ell = bucketed_ell_from_csr(csr)
        assert sum(int((b.w != 0).sum()) for b in ell.buckets) == csr.nnz
        x = rng.normal(size=(32, 4)).astype(np.float32)
        fwd, rev = _device_pair(src, dst, w, 32, 32)
        out = bucketed_aggregate(jnp.asarray(x), fwd, rev, 32)
        np.testing.assert_allclose(out, _coo_ref(x, src, dst, w, 32),
                                   rtol=1e-5, atol=1e-5)


class TestBucketedLayout:
    def test_padding_bound_on_rmat(self):
        """Acceptance: growth-2 ladder keeps padded slots <= 2 x nnz on a
        power-law graph, where max-degree padding blows up by orders of
        magnitude."""
        g = rmat_graph(10, edge_factor=8, seed=1).mean_normalized()
        csr = g.csr_by_dst()
        ell = bucketed_ell_from_csr(csr)
        assert ell.padded_slots <= 2 * csr.nnz
        maxpad = csr.num_rows * int(csr.row_degrees().max())
        assert maxpad > 10 * ell.padded_slots

    def test_zero_degree_rows_absent(self):
        src = np.array([0, 1], np.int32)
        dst = np.array([3, 3], np.int32)
        csr = coo_to_csr(src, dst, None, 6, 6)
        ell = bucketed_ell_from_csr(csr)
        assert [b.k for b in ell.buckets] == [2]
        assert ell.buckets[0].rows.tolist() == [3]

    def test_bucket_rows_highest_degree_first(self):
        """Inside a bucket rows run from highest degree down (ties in node
        order), so each slot's padding trails its real rows in every row tile
        the kernel takes; each row keeps its own neighbours in CSR order."""
        g = rmat_graph(9, edge_factor=8, seed=2).mean_normalized()
        csr = g.csr_by_dst()
        deg = csr.row_degrees()
        ell = bucketed_ell_from_csr(csr)
        for b in ell.buckets:
            d = deg[b.rows]
            assert (np.diff(d) <= 0).all()
            assert all((np.diff(b.rows[d == v]) > 0).all() for v in np.unique(d))
            np.testing.assert_array_equal(
                b.w != 0, np.arange(b.k)[None, :] < d[:, None])
            lo = csr.indptr[b.rows]
            for i in range(0, len(b.rows), max(1, len(b.rows) // 7)):
                np.testing.assert_array_equal(
                    b.idx[i, :d[i]], csr.indices[lo[i]:lo[i] + d[i]])

    def test_partition_stats_accounting_matches_layouts(self):
        """partition_stats' padded-slot accounting == the slots the
        partition-time layouts actually materialize."""
        from repro.graph import partition_stats
        g = rmat_graph(8, edge_factor=6, seed=4)
        pg = build_partitioned_graph(g, 4, strategy="hybrid", seed=0)
        st = partition_stats(g, pg.part)
        assert st["agg_padded_slots"] == sum(
            e.padded_slots for e in pg.local_ell)
        assert st["agg_padding_ratio"] <= 2.0

    def test_empty_graph(self):
        csr = coo_to_csr(np.array([], np.int32), np.array([], np.int32),
                         None, 4, 4)
        ell = bucketed_ell_from_csr(csr)
        assert ell.buckets == [] and ell.padded_slots == 0
        fwd = device_bucketed(stack_bucketed_ells([ell]), squeeze=True)
        out = bucketed_aggregate(jnp.ones((4, 8)), fwd, fwd, 4)
        np.testing.assert_array_equal(out, np.zeros((4, 8)))


class TestBucketedAggregateVJP:
    @settings(max_examples=15, deadline=None)
    @given(st.integers(2, 40), st.integers(2, 40), st.integers(0, 60),
           st.integers(0, 9999))
    def test_forward_and_grad_match_coo(self, n_src, n_dst, hub, seed):
        """Property: bucketed forward == COO scatter-add, and the custom
        VJP == jax.grad of the COO path — across degree-0 rows, degree-1
        rows, and hub rows larger than every other degree class."""
        rng = np.random.default_rng(seed)
        src, dst, w = _random_coo(rng, n_src, n_dst, hub_degree=hub)
        x = rng.normal(size=(n_src, 4)).astype(np.float32)
        cot = rng.normal(size=(n_dst, 4)).astype(np.float32)
        fwd, rev = _device_pair(src, dst, w, n_src, n_dst)
        sj, dj, wj = jnp.asarray(src), jnp.asarray(dst), jnp.asarray(w)

        def coo_loss(xx):
            out = jnp.zeros((n_dst, 4)).at[dj].add(wj[:, None] * xx[sj])
            return jnp.vdot(out, cot)

        def ell_loss(xx):
            return jnp.vdot(bucketed_aggregate(xx, fwd, rev, n_dst), cot)

        np.testing.assert_allclose(
            bucketed_aggregate(jnp.asarray(x), fwd, rev, n_dst),
            _coo_ref(x, src, dst, w, n_dst), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(jax.grad(ell_loss)(jnp.asarray(x)),
                                   jax.grad(coo_loss)(jnp.asarray(x)),
                                   rtol=1e-5, atol=1e-5)

    def test_bitforbit_exact_sums(self):
        """Integer features + unit weights: every partial sum is exact in
        fp32, so forward AND backward must match the COO path bit-for-bit."""
        rng = np.random.default_rng(7)
        src, dst, _ = _random_coo(rng, 24, 24, hub_degree=30)
        w = np.ones(len(src), np.float32)
        x = rng.integers(0, 8, size=(24, 4)).astype(np.float32)
        cot = rng.integers(0, 8, size=(24, 4)).astype(np.float32)
        fwd, rev = _device_pair(src, dst, w, 24, 24)
        sj, dj, wj = jnp.asarray(src), jnp.asarray(dst), jnp.asarray(w)

        def coo_loss(xx):
            out = jnp.zeros((24, 4)).at[dj].add(wj[:, None] * xx[sj])
            return jnp.vdot(out, cot)

        def ell_loss(xx):
            return jnp.vdot(bucketed_aggregate(xx, fwd, rev, 24), cot)

        np.testing.assert_array_equal(
            np.asarray(bucketed_aggregate(jnp.asarray(x), fwd, rev, 24)),
            _coo_ref(x, src, dst, w, 24))
        np.testing.assert_array_equal(
            np.asarray(jax.grad(ell_loss)(jnp.asarray(x))),
            np.asarray(jax.grad(coo_loss)(jnp.asarray(x))))

    def test_vjp_under_vmap(self):
        """The float0 layout cotangents must survive vmap batching (the
        virtual-worker trainer differentiates through a vmapped call)."""
        rng = np.random.default_rng(3)
        P, n = 3, 16
        stacked_fwd, stacked_rev, xs = [], [], []
        for _ in range(P):
            src, dst, w = _random_coo(rng, n, n, hub_degree=8)
            csr = coo_to_csr(src, dst, w, n, n)
            stacked_fwd.append(bucketed_ell_from_csr(csr))
            stacked_rev.append(bucketed_ell_from_csr(transpose_csr(csr)))
            xs.append(rng.normal(size=(n, 4)).astype(np.float32))
        fwd = device_bucketed(stack_bucketed_ells(stacked_fwd))
        rev = device_bucketed(stack_bucketed_ells(stacked_rev))
        x = jnp.asarray(np.stack(xs))

        def loss(xx, f, r):
            return (bucketed_aggregate(xx, f, r) ** 2).sum()

        g = jax.vmap(jax.grad(loss))(x, fwd, rev)
        assert g.shape == x.shape and bool(jnp.isfinite(g).all())

    def test_kernel_matches_reference_under_vmap_grad(self):
        """The Pallas kernel (interpreted here) equals the XLA reference in
        the forward and through the custom VJP, under the worker vmap its
        batching rule folds into one call over the concatenated graphs."""
        rng = np.random.default_rng(4)
        P, n = 3, 24
        stacked_fwd, stacked_rev, xs = [], [], []
        for _ in range(P):
            src, dst, w = _random_coo(rng, n, n, hub_degree=20)
            csr = coo_to_csr(src, dst, w, n, n)
            stacked_fwd.append(bucketed_ell_from_csr(csr))
            stacked_rev.append(bucketed_ell_from_csr(transpose_csr(csr)))
            xs.append(rng.normal(size=(n, 5)).astype(np.float32))
        fwd = device_bucketed(stack_bucketed_ells(stacked_fwd))
        rev = device_bucketed(stack_bucketed_ells(stacked_rev))
        x = jnp.asarray(np.stack(xs))

        def run(use_kernel):
            agg = lambda xx, f, r: bucketed_aggregate(xx, f, r,
                                                      use_kernel=use_kernel)
            loss = lambda xx, f, r: (agg(xx, f, r) ** 2).sum()
            return (jax.vmap(agg)(x, fwd, rev),
                    jax.vmap(jax.grad(loss))(x, fwd, rev))

        (out_k, g_k), (out_r, g_r) = run(True), run(False)
        np.testing.assert_allclose(out_k, out_r, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(g_k, g_r, rtol=1e-5, atol=1e-5)


class TestScatterRecvEll:
    def test_matches_coo_forward_and_grad(self):
        """The exchange receive-side scatter through the segment-aggregate
        primitive == the COO scatter, values and recv-cotangents both."""
        g = rmat_graph(8, edge_factor=6, seed=2).mean_normalized()
        pg = build_partitioned_graph(g, 4, strategy="hybrid", seed=0)
        M = pg.max_owned
        hp = build_halo_plan(pg)
        plan = stack_halo_plan(hp, num_rows=M)
        assert plan.recv_ell is not None
        rng = np.random.default_rng(0)
        wire = hp.send_gather_idx.shape[-1]
        recv = jnp.asarray(rng.normal(size=(4, wire, 8)).astype(np.float32))
        acc = jnp.asarray(rng.normal(size=(4, M, 8)).astype(np.float32))

        def run(backend):
            def one(a, r, pl):
                return scatter_recv(a, r, pl, agg_backend=backend)
            return jax.vmap(one)(acc, recv, plan)

        np.testing.assert_allclose(run("ell"), run("coo"),
                                   rtol=1e-5, atol=1e-5)

        def loss(r, backend):
            def one(a, rr, pl):
                return scatter_recv(a, rr, pl, agg_backend=backend)
            return (jax.vmap(one)(acc, r, plan) ** 2).sum()

        np.testing.assert_allclose(jax.grad(loss)(recv, "ell"),
                                   jax.grad(loss)(recv, "coo"),
                                   rtol=1e-4, atol=1e-4)


class TestGATSharedLayout:
    def test_bucketed_gat_matches_dense_ell(self):
        """GAT over the shared bucketed layout == GAT over the max-degree
        ELL (same per-row softmax, bounded padding)."""
        g = rmat_graph(7, edge_factor=4, seed=5).mean_normalized()
        csr = g.csr_by_dst()
        idx, w, valid = ell_from_csr(csr)
        ell = device_bucketed(
            stack_bucketed_ells([bucketed_ell_from_csr(csr)]), squeeze=True)
        p = init_layer(jax.random.PRNGKey(0), "gat", 8, 16, heads=4)
        h = jax.random.normal(jax.random.PRNGKey(1), (g.num_nodes, 8))
        wx = h @ p["w"]
        scores = gat_scores(p, wx)
        dense = gat_aggregate(wx, *scores, jnp.asarray(idx), jnp.asarray(valid))
        bucketed = gat_attention(wx, *scores, ell)
        np.testing.assert_allclose(bucketed, dense, rtol=1e-4, atol=1e-5)


def _gat_layouts(g, workers=1):
    """Forward and reverse bucketed layouts of ``g`` (stacked for
    ``workers`` copies of it) and the reverse slot index."""
    csr = g.csr_by_dst()
    fwd = stack_bucketed_ells([bucketed_ell_from_csr(csr)] * workers)
    rev = stack_bucketed_ells([bucketed_ell_from_csr(transpose_csr(csr))] * workers)
    return fwd, rev, reverse_slot_index(fwd, rev)


class TestReverseSlotIndex:
    @pytest.mark.parametrize("workers", [1, 3])
    def test_forward_values_reach_the_transpose_slot_order(self, workers):
        """A value set on each forward slot as a function of its edge,
        gathered through the index, lands on the reverse slot of the same
        edge; padding gathers the appended zero."""
        g = rmat_graph(7, edge_factor=4, seed=2).dedupe().mean_normalized()
        fwd, rev, slot_t = _gat_layouts(g, workers)
        n = g.num_nodes
        edge_value = lambda dst, src: 1.0 + dst * n + src   # never 0
        flat = np.concatenate([
            np.where(w != 0, edge_value(rows[..., None], idx), 0.0).reshape(workers, -1)
            for _, rows, idx, w in fwd] + [np.zeros((workers, 1))], axis=1)
        for (_, rows_t, idx_t, w_t), s in zip(rev, slot_t):
            assert s.shape == idx_t.shape and s.dtype == np.int32
            got = np.take_along_axis(flat, s.reshape(workers, -1), axis=1).reshape(s.shape)
            want = np.where(w_t != 0, edge_value(idx_t, rows_t[..., None]), 0.0)
            np.testing.assert_array_equal(got, want)

    def test_an_edge_missing_from_the_forward_layout_is_refused(self):
        g = rmat_graph(6, edge_factor=4, seed=3).dedupe().mean_normalized()
        fwd, rev, _ = _gat_layouts(g)
        k, rows, idx, w = fwd[-1]
        w = w.copy()
        w[0, 0, 0] = 0.0
        with pytest.raises(ValueError, match="does not"):
            reverse_slot_index(fwd[:-1] + [(k, rows, idx, w)], rev)


class TestGATAttentionOp:
    """The bucketed attention op (custom VJP: weighted kernel over the
    reverse layout, SDDMM, softmax backward) against JAX's autodiff of the
    dense max-degree ELL form."""

    @pytest.mark.parametrize("use_kernel", [False, True])
    @pytest.mark.parametrize("heads,dh", [(4, 8), (3, 5)])
    def test_value_and_grad_match_dense_ell(self, use_kernel, heads, dh):
        g = rmat_graph(6, edge_factor=4, seed=5).mean_normalized()
        n = g.num_nodes
        idx, _, valid = ell_from_csr(g.csr_by_dst())
        fwd, rev, slot_t = _gat_layouts(g)
        ell, ell_t = (device_bucketed(s, squeeze=True) for s in (fwd, rev))
        slot_t = tuple(jnp.asarray(a[0]) for a in slot_t)
        kx, ks, kd, kc = jax.random.split(jax.random.PRNGKey(heads), 4)
        wx = jax.random.normal(kx, (n, heads * dh))
        e_src = jax.random.normal(ks, (n, heads))
        e_dst = jax.random.normal(kd, (n, heads))
        cot = jax.random.normal(kc, (n, heads * dh))

        def dense(wx, es, ed):
            return gat_aggregate(wx, es, ed, jnp.asarray(idx), jnp.asarray(valid))

        def op(wx, es, ed):
            return gat_attention(wx, es, ed, ell, ell_t, slot_t,
                                 use_kernel=use_kernel)

        np.testing.assert_allclose(jax.jit(op)(wx, e_src, e_dst),
                                   dense(wx, e_src, e_dst), rtol=1e-5, atol=1e-5)
        loss = lambda f: lambda *a: jnp.vdot(f(*a), cot)
        got = jax.jit(jax.grad(loss(op), argnums=(0, 1, 2)))(wx, e_src, e_dst)
        want = jax.grad(loss(dense), argnums=(0, 1, 2))(wx, e_src, e_dst)
        for name, a, b in zip(("wx", "e_src", "e_dst"), got, want):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4, err_msg=name)


class TestTrainerParity:
    """Acceptance: full training runs with agg_backend='ell' match the COO
    backend's loss trajectory to <= 1e-5 on the RMAT test graph."""

    def _graph(self):
        g = rmat_graph(8, edge_factor=6, seed=3)
        rng = np.random.default_rng(0)
        g.labels = rng.integers(0, 5, g.num_nodes).astype(np.int32)
        g.train_mask = rng.random(g.num_nodes) < 0.5
        x = rng.normal(size=(g.num_nodes, 8)).astype(np.float32)
        return g.mean_normalized(), x

    def _losses(self, cfg, dc, wd, epochs=5):
        tr = DistributedTrainer(cfg, dc, wd, seed=0)
        return [tr.train_epoch()["loss"] for _ in range(epochs)], tr.evaluate()

    @pytest.mark.parametrize("bits", [0, 2])
    def test_flat_schedule(self, bits):
        gn, x = self._graph()
        cfg = GCNConfig(model="sage", in_dim=8, hidden_dim=16, num_classes=5,
                        num_layers=2, dropout=0.0, label_prop=False)
        pg = build_partitioned_graph(gn, 4, strategy="hybrid", seed=0)
        wd = prepare_distributed(gn, x, pg)
        l_ell, e_ell = self._losses(
            cfg, DistConfig(nparts=4, bits=bits, agg_backend="ell"), wd)
        l_coo, e_coo = self._losses(
            cfg, DistConfig(nparts=4, bits=bits, agg_backend="coo"), wd)
        np.testing.assert_allclose(l_ell, l_coo, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(e_ell, e_coo, rtol=1e-5, atol=1e-6)

    def test_hierarchical_schedule(self):
        gn, x = self._graph()
        cfg = GCNConfig(model="sage", in_dim=8, hidden_dim=16, num_classes=5,
                        num_layers=2, dropout=0.0, label_prop=False)
        hpg = build_hierarchical_partitioned_graph(gn, 2, 2,
                                                   strategy="hybrid", seed=0)
        wd = prepare_distributed(gn, x, hpg)
        mk = lambda ab: DistConfig(nparts=4, num_groups=2, group_size=2,
                                   agg_backend=ab)
        l_ell, e_ell = self._losses(cfg, mk("ell"), wd)
        l_coo, e_coo = self._losses(cfg, mk("coo"), wd)
        np.testing.assert_allclose(l_ell, l_coo, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(e_ell, e_coo, rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("mode", ["vmap", "shard_map"])
    def test_predict_logits_match_coo(self, mode):
        """Per-node eval logits, ELL vs COO, at the initial parameters (the
        chip smoke's check): the same neighbour sums in another order.
        Under shard_map they come back on the worker axis like vmap's."""
        gn, x = self._graph()
        cfg = GCNConfig(model="sage", in_dim=8, hidden_dim=16, num_classes=5,
                        num_layers=2, dropout=0.0, label_prop=False)
        hpg = build_hierarchical_partitioned_graph(gn, 2, 2,
                                                   strategy="hybrid", seed=0)
        wd = prepare_distributed(gn, x, hpg)
        mesh = make_hier_worker_mesh(2, 2) if mode == "shard_map" else None
        logits = {}
        for ab in ("ell", "coo"):
            dc = DistConfig(nparts=4, num_groups=2, group_size=2,
                            agg_backend=ab)
            tr = DistributedTrainer(cfg, dc, wd, mode=mode, mesh=mesh, seed=0)
            logits[ab] = np.asarray(tr.predict())
        assert logits["ell"].shape[-2:] == (wd.x.shape[1], 5)
        assert int(np.prod(logits["ell"].shape[:-2])) == 4
        scale = np.abs(logits["coo"]).max()
        assert np.abs(logits["ell"] - logits["coo"]).max() <= 1e-6 * scale
