"""Sharding rules + dry-run machinery (single-device-safe parts).

The full 512-device lowering is exercised by ``launch/dryrun.py`` (and the
subprocess integration test in test_dryrun_integration.py); here we verify
the rule layer itself on small meshes.
"""

import jax
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, get_arch, get_smoke_arch
from repro.launch.hlo_stats import parse_collectives
from repro.models import init_params
from repro.sharding.specs import batch_spec, cache_specs, param_specs


@pytest.fixture(scope="module")
def tiny_mesh():
    # 1x1 mesh with production axis names: rules must degrade gracefully.
    return jax.make_mesh((1, 1), ("data", "model"))


class TestParamSpecs:
    @pytest.mark.parametrize("name", ARCH_NAMES)
    def test_specs_match_tree_and_divide(self, name, tiny_mesh):
        cfg = get_smoke_arch(name)
        shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                                jax.random.PRNGKey(0))
        specs = param_specs(shapes, tiny_mesh)
        # tree structures align
        jax.tree_util.tree_map(lambda a, b: None, shapes, specs)

        flat_s = jax.tree_util.tree_leaves_with_path(shapes)
        flat_p = jax.tree_util.tree_leaves(specs)
        for (path, leaf), spec in zip(flat_s, flat_p):
            assert len(spec) <= len(leaf.shape), (path, spec, leaf.shape)
            for dim, ax in zip(leaf.shape, tuple(spec)):
                if ax is None:
                    continue
                axes = (ax,) if isinstance(ax, str) else ax
                n = int(np.prod([tiny_mesh.shape[a] for a in axes]))
                assert dim % n == 0, (path, spec, leaf.shape)

    def test_production_mesh_rules(self):
        """On a 4x4 stand-in of the production mesh, big matrices must be
        2-D sharded (TP x FSDP) and scan stacks must keep dim0 unsharded."""
        mesh = jax.sharding.AbstractMesh((2, 2), ("data", "model"))
        cfg = get_arch("tinyllama-1.1b")
        shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                                jax.random.PRNGKey(0))
        specs = param_specs(shapes, mesh)
        wq = specs["blocks"]["attn"]["w_q"]
        assert wq[0] is None                # scan dim replicated
        assert "model" in str(wq)           # TP somewhere
        assert "data" in str(wq)            # FSDP somewhere
        # small tables replicate for train (§Perf iter D); big ones shard
        assert str(specs["embed"]) == "PartitionSpec(None, None)"
        big = get_arch("qwen2.5-32b")
        bshapes = jax.eval_shape(lambda k: init_params(k, big),
                                 jax.random.PRNGKey(0))
        bspecs = param_specs(bshapes, mesh)
        assert "model" in str(bspecs["embed"])
        # inference: TP-only (no FSDP axis on weights)
        ispecs = param_specs(shapes, mesh, fsdp=False)
        assert "data" not in str(ispecs["blocks"]["attn"]["w_q"])

    def test_batch_spec_divisibility(self, tiny_mesh):
        mesh = jax.sharding.AbstractMesh((2, 2), ("data", "model"))
        assert batch_spec(mesh, 128)[0] in ("data", ("data",))
        assert batch_spec(mesh, 1)[0] is None  # long_500k: replicate


class TestCacheSpecs:
    def test_cache_seq_sharded_over_model(self):
        from repro.models import init_cache
        mesh = jax.sharding.AbstractMesh((2, 2), ("data", "model"))
        cfg = get_smoke_arch("tinyllama-1.1b")
        cache = jax.eval_shape(lambda: init_cache(cfg, 4, 128))
        specs = cache_specs(cache, mesh, 4)
        k_spec = specs.layers.k  # [L, B, S, KV, hd]
        assert k_spec[1] in ("data", ("data",))
        assert "model" in str(k_spec)


class TestHloStats:
    def test_loop_multiplication(self):
        """Collectives inside a scan must be multiplied by the trip count."""
        mesh = jax.make_mesh((1,), ("x",))
        hlo = """
HloModule test

%body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %ar = f32[8]{0} all-reduce(%z), replica_groups=[1,4]<=[4], to_apply=%add
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %w = (s32[], f32[8]) while(%t), condition=%cond.1, body=%body.1, backend_config={"known_trip_count":{"n":"7"}}
}
"""
        stats = parse_collectives(hlo)
        assert stats["all-reduce"]["count"] == 7
        assert stats["all-reduce"]["operand_bytes"] == 7 * 32

    def test_wire_bytes_semantics(self):
        hlo = """
ENTRY %main (a: f32[4]) -> f32[64] {
  %ag = f32[64]{0} all-gather(%a), replica_groups=[1,16]<=[16], dimensions={0}
}
"""
        stats = parse_collectives(hlo)
        ag = stats["all-gather"]
        assert ag["operand_bytes"] == 64 * 4 / 16
        assert ag["result_bytes"] == 256
        np.testing.assert_allclose(ag["wire_bytes"], 256 * 15 / 16)


class TestInputSpecsLogic:
    def test_skip_rules(self):
        from repro.configs import get_shape
        from repro.launch.input_specs import effective_window, skip_reason
        whisper = get_arch("whisper-small")
        assert skip_reason(whisper, get_shape("long_500k"))
        assert skip_reason(whisper, get_shape("decode_32k")) is None
        dense = get_arch("llama3.2-3b")
        assert skip_reason(dense, get_shape("long_500k")) is None
        assert effective_window(dense, get_shape("long_500k")) == 8192
        assert effective_window(dense, get_shape("train_4k")) is None
        ssm = get_arch("xlstm-350m")
        assert effective_window(ssm, get_shape("long_500k")) is None

    def test_microbatch_token_budget(self):
        from repro.configs import get_shape
        from repro.launch.input_specs import MB_TOKENS_PER_DEVICE, num_microbatches
        mesh = jax.make_mesh((1, 1), ("data", "model"))

        class FakeMesh:
            shape = {"data": 16, "model": 16}
            axis_names = ("data", "model")
        nm = num_microbatches(get_arch("tinyllama-1.1b"),
                              get_shape("train_4k"), FakeMesh())
        shape = get_shape("train_4k")
        tokens_per_dev = shape.global_batch * shape.seq_len // 16
        assert shape.global_batch % nm == 0
        assert tokens_per_dev // nm <= MB_TOKENS_PER_DEVICE
