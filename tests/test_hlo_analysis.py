"""The loop-aware HLO analysis layer (launch/hlo_stats.py) — the roofline's
measurement foundation, validated on programs with known costs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_stats import analyze_hlo, collective_order, parse_collectives


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile().as_text()


class TestDotFlops:
    def test_plain_matmul(self):
        txt = _compile(lambda a, b: a @ b,
                       jax.ShapeDtypeStruct((64, 32), jnp.float32),
                       jax.ShapeDtypeStruct((32, 16), jnp.float32))
        flops = analyze_hlo(txt)["dot_flops"]
        assert flops == 2 * 64 * 32 * 16

    def test_scan_multiplies_trip_count(self):
        def f(x, w):
            def body(h, _):
                return jnp.tanh(h @ w), None
            h, _ = jax.lax.scan(body, x, None, length=10)
            return h
        txt = _compile(f, jax.ShapeDtypeStruct((128, 256), jnp.float32),
                       jax.ShapeDtypeStruct((256, 256), jnp.float32))
        flops = analyze_hlo(txt)["dot_flops"]
        assert flops == 10 * 2 * 128 * 256 * 256

    def test_grad_counts_fwd_recompute_bwd(self):
        def g(x, w):
            def body(h, _):
                return jax.checkpoint(lambda hh: jnp.tanh(hh @ w))(h), None
            h, _ = jax.lax.scan(body, x, None, length=7)
            return h.sum()
        txt = _compile(jax.grad(g, argnums=1),
                       jax.ShapeDtypeStruct((64, 128), jnp.float32),
                       jax.ShapeDtypeStruct((128, 128), jnp.float32))
        flops = analyze_hlo(txt)["dot_flops"]
        # fwd + remat recompute + 2 bwd matmuls = 4x fwd
        assert flops == pytest.approx(4 * 7 * 2 * 64 * 128 * 128, rel=0.01)

    def test_batched_einsum(self):
        def f(a, b):
            return jnp.einsum("bik,bkj->bij", a, b)
        txt = _compile(f, jax.ShapeDtypeStruct((4, 8, 16), jnp.float32),
                       jax.ShapeDtypeStruct((4, 16, 8), jnp.float32))
        flops = analyze_hlo(txt)["dot_flops"]
        assert flops == 2 * 4 * 8 * 16 * 8


class TestCollectiveParsing:
    def test_compact_replica_groups(self):
        hlo = """
ENTRY %main (a: f32[4]) -> f32[64] {
  %ag = f32[64]{0} all-gather(%a), replica_groups=[4,16]<=[64], dimensions={0}
}
"""
        st = parse_collectives(hlo)
        assert st["all-gather"]["count"] == 1
        np.testing.assert_allclose(st["all-gather"]["wire_bytes"],
                                   256 * 15 / 16)

    def test_explicit_list_replica_groups(self):
        """shard_map emits explicit {{0,1,...}} lists — group size must be
        parsed from the id count (regression: GCN dry-run parsed g=1)."""
        hlo = """
ENTRY %main (a: f32[8]) -> f32[8] {
  %psum.1 = f32[8]{0} all-reduce(%a), replica_groups={{0,1,2,3,4,5,6,7,8,9,10,11,12,13,14,15}}, to_apply=%add
}
"""
        st = parse_collectives(hlo)
        ar = st["all-reduce"]
        np.testing.assert_allclose(ar["wire_bytes"], 32 * 2 * 15 / 16)

    def test_tuple_result_all_to_all(self):
        """Tuple results carry /*index=N*/ comments containing '=' — the op
        regex must span them (regression: GCN a2a ops were invisible)."""
        hlo = """
ENTRY %main (a: f32[2]) -> f32[2] {
  %all-to-all.1 = (f32[1,7]{1,0}, f32[1,7]{1,0}, /*index=2*/f32[1,7]{1,0}, f32[1,7]{1,0}) all-to-all(%a, %b, %c, %d), replica_groups={{0,1,2,3}}, dimensions={0}
}
"""
        st = parse_collectives(hlo)
        a2a = st["all-to-all"]
        assert a2a["count"] == 1
        assert a2a["result_bytes"] == 4 * 7 * 4

    def test_sub_byte_s4_all_to_all(self):
        """XLA's packed sub-byte s4/u4 payloads (the Int4 wire once XLA
        packs it) carry fractional byte widths, rounded up per buffer."""
        hlo = """
ENTRY %main (a: s4[112,16]) -> s4[112,16] {
  %all-to-all.7 = s4[112,16]{1,0} all-to-all(s4[112,16]{1,0} %a), replica_groups={{0,1,2,3}}, dimensions={0}
}
"""
        st = parse_collectives(hlo)
        a2a = st["all-to-all"]
        assert a2a["count"] == 1
        assert a2a["result_bytes"] == 896  # ceil(112*16 * 0.5)
        np.testing.assert_allclose(a2a["wire_bytes"], 896 * 3 / 4)

    def test_sub_byte_s2_rounds_up_per_buffer(self):
        hlo = """
ENTRY %main (a: s2[9]) -> s2[9] {
  %cp = s2[9]{0} collective-permute(s2[9]{0} %a), source_target_pairs={{0,1}}
}
"""
        st = parse_collectives(hlo)
        assert st["collective-permute"]["result_bytes"] == 3  # ceil(9/4)

    def test_tuple_result_sub_byte_all_to_all(self):
        """Tuple-typed results with sub-byte elements: each member buffer
        rounds up independently (4 x ceil(7 * 0.5) = 16, not ceil(14))."""
        hlo = """
ENTRY %main (a: u4[2]) -> u4[2] {
  %all-to-all.2 = (u4[1,7]{1,0}, u4[1,7]{1,0}, /*index=2*/u4[1,7]{1,0}, u4[1,7]{1,0}) all-to-all(%a, %b, %c, %d), replica_groups={{0,1,2,3}}, dimensions={0}
}
"""
        st = parse_collectives(hlo)
        a2a = st["all-to-all"]
        assert a2a["count"] == 1
        assert a2a["result_bytes"] == 4 * 4

    def test_while_loop_multiplication_end_to_end(self):
        """Compiled JAX scan with a psum inside (vmap->jit collective)."""
        mesh = jax.make_mesh((1,), ("w",))
        from jax.sharding import PartitionSpec as P

        def worker(x):
            def body(c, xi):
                return c + jax.lax.psum(xi, "w"), None
            out, _ = jax.lax.scan(body, jnp.zeros_like(x[0]), x)
            return out
        f = jax.shard_map(worker, mesh=mesh, in_specs=(P(None),),
                          out_specs=P(), check_vma=False)
        txt = jax.jit(f).lower(
            jax.ShapeDtypeStruct((5, 8), jnp.float32)).compile().as_text()
        st = parse_collectives(txt)
        # 5 loop iterations x 1 psum (or unrolled equivalents)
        assert st["total"]["count"] >= 1


class TestCollectiveOrder:
    """collective_order parses overlap evidence from *lowered* StableHLO
    (trace order; the compiled text is scheduler-normalized)."""

    OVERLAPPED = """
module @jit_step {
  func.func public @main(%arg0: tensor<8x4xf32>) -> tensor<8x4xf32> {
    %0 = "stablehlo.reduce_scatter"(%arg0) {replica_groups = dense<[[0, 1, 2, 3]]> : tensor<1x4xi64>} : (tensor<8x4xf32>) -> tensor<2x4xf32>
    %1 = "stablehlo.all_to_all"(%0) {replica_groups = dense<[[0, 4], [1, 5]]> : tensor<2x2xi64>} : (tensor<2x4xf32>) -> tensor<2x4xf32>
    %2 = "stablehlo.all_gather"(%1) {replica_groups = dense<[[0, 1, 2, 3]]> : tensor<1x4xi64>} : (tensor<2x4xf32>) -> tensor<8x4xf32>
    %3 = stablehlo.dot_general %2, %2, contracting_dims = [1] x [0] : (tensor<8x4xf32>, tensor<4x8xf32>) -> tensor<8x8xf32>
    return %2 : tensor<8x4xf32>
  }
}
"""

    SEQUENTIAL = """
module @jit_step {
  func.func public @main(%arg0: tensor<8x4xf32>) -> tensor<8x4xf32> {
    %0 = stablehlo.dot_general %arg0, %arg0, contracting_dims = [1] x [0] : (tensor<8x4xf32>, tensor<4x8xf32>) -> tensor<8x8xf32>
    %1 = "stablehlo.all_to_all"(%arg0) {replica_groups = dense<[[0, 4], [1, 5]]> : tensor<2x2xi64>} : (tensor<8x4xf32>) -> tensor<8x4xf32>
    return %1 : tensor<8x4xf32>
  }
}
"""

    def test_wire_issued_before_compute(self):
        order = collective_order(self.OVERLAPPED)
        assert order["wire_before_compute"]
        assert order["inter_wire_before_compute"]
        # The grouped pre-wire opens the program; its replica group spans
        # the 4-worker shard axis.
        assert order["first_wire"]["op"] == "reduce-scatter"
        assert order["first_wire"]["group_size"] == 4
        assert order["first_compute"]["op"] == "dot_general"

    def test_sequential_trace_detected(self):
        order = collective_order(self.SEQUENTIAL)
        assert not order["wire_before_compute"]
        assert order["first_inter_wire"] is None
        assert not order["inter_wire_before_compute"]
        assert order["first_wire"]["op"] == "all-to-all"
        assert order["first_wire"]["group_size"] == 2

    def test_real_lowering_flat_overlap(self):
        """End-to-end on a real lowered module: a toy program that issues
        an all_to_all before its dot, under shard_map on 2 virtual
        devices (the conftest provides 8 host devices)."""
        mesh = jax.make_mesh((2,), ("w",))
        from jax.sharding import PartitionSpec as P

        def worker(x):
            recv = jax.lax.all_to_all(x, "w", split_axis=0,
                                      concat_axis=0, tiled=False)
            local = x[0] @ x[0].T
            return local + recv[0] @ recv[0].T

        f = jax.shard_map(worker, mesh=mesh, in_specs=(P("w"),),
                          out_specs=P("w"), check_vma=False)
        txt = jax.jit(f).lower(
            jax.ShapeDtypeStruct((4, 2, 8), jnp.float32)).as_text()
        order = collective_order(txt)
        assert order["wire_before_compute"]
