"""RunSpec API: serialization round-trips, content-hash stability, the
--set override layer, validation, the legacy-flag alias table, the
Int2-inter default flip, and build_session-vs-hand-constructed parity
(the acceptance criterion: a spec serialized by one driver reproduces a
bit-identical first-epoch loss when loaded by another)."""

import argparse
import json
from pathlib import Path

import numpy as np
import pytest

from repro.run import (
    LEGACY_ALIASES,
    BuildCache,
    RunSpec,
    SpecError,
    build_session,
    legacy_overrides,
)

ROOT = Path(__file__).resolve().parents[1]

TINY = ["graph.nodes=300", "graph.classes=4", "graph.avg_degree=10",
        "graph.feat_dim=8", "model.hidden_dim=16", "model.num_layers=2",
        "model.dropout=0.0", "model.label_prop=false",
        "partition.nparts=4", "exec.epochs=3"]


def tiny_spec(*extra):
    return RunSpec().with_overrides(TINY + list(extra))


class TestRoundTrip:
    def test_dict_json_identity(self):
        spec = tiny_spec("partition.groups=2", "schedule.inter_cd=3",
                         "schedule.overlap=true")
        assert RunSpec.from_dict(spec.to_dict()) == spec
        assert RunSpec.from_json(spec.to_json()) == spec
        assert RunSpec.from_json(spec.to_json()).content_hash() \
            == spec.content_hash()

    def test_save_load(self, tmp_path):
        spec = tiny_spec("schedule.bits=2")
        p = tmp_path / "s.json"
        spec.save(p)
        assert RunSpec.load(p) == spec

    def test_missing_sections_default(self):
        # A partial dict fills unmentioned sections with defaults.
        spec = RunSpec.from_dict({"partition": {"nparts": 4}})
        assert spec.partition.nparts == 4
        assert spec.model == RunSpec().model

    def test_null_round_trips(self):
        spec = tiny_spec("partition.groups=2", "schedule.inter_bits=2")
        d = json.loads(spec.to_json())
        assert d["schedule"]["intra_bits"] is None
        assert RunSpec.from_json(spec.to_json()).schedule.intra_bits is None


class TestContentHash:
    def test_key_order_independent(self):
        spec = tiny_spec()
        d = spec.to_dict()
        scrambled = json.loads(json.dumps(d, sort_keys=True))
        assert RunSpec.from_dict(scrambled).content_hash() \
            == spec.content_hash()

    def test_any_field_changes_hash(self):
        spec = tiny_spec()
        assert spec.with_overrides(["schedule.bits=2"]).content_hash() \
            != spec.content_hash()
        assert spec.with_overrides(["graph.seed=1"]).content_hash() \
            != spec.content_hash()

    def test_default_spec_hash_pinned(self):
        # The stability contract: hashing is canonical-JSON sha256. This
        # value changes iff the spec schema or its defaults change — which
        # invalidates recorded artifacts and should be a conscious act.
        # (PR 7 added exec.nprocs, rehashing from rs-408ff1e8bfd8; PR 8
        # added exec.ckpt_every/max_restarts/heartbeat_s, rehashing from
        # rs-d87a4352cce8; PR 9 added partition.refine + exec.auto,
        # rehashing from rs-58ae58fdfdbc.)
        assert RunSpec().content_hash() == "rs-f356a4f93c9f"

    def test_key_added_at_its_default_keeps_the_hash(self):
        """``model.gat_out_heads`` (marked OMIT_DEFAULT) is left out of the
        canonical form while at its default, so recorded hashes stand; set,
        it counts like any field and round-trips."""
        spec = tiny_spec()
        assert "gat_out_heads" not in spec.to_dict()["model"]
        assert spec.content_hash() == spec.with_overrides(
            ["model.gat_out_heads=1"]).content_hash()
        six = spec.with_overrides(["model.gat_out_heads=6"])
        assert six.to_dict()["model"]["gat_out_heads"] == 6
        assert six.content_hash() != spec.content_hash()
        assert RunSpec.from_json(six.to_json()) == six

    def test_sub_spec_hashes(self):
        # Per-section hashes: kind-prefixed, content-addressed, and only
        # sensitive to their own section.
        spec = tiny_spec()
        assert spec.graph.content_hash().startswith("gs-")
        assert spec.partition.content_hash().startswith("ps-")
        assert spec.schedule.content_hash().startswith("ss-")
        assert spec.model.content_hash().startswith("ms-")
        assert spec.exec.content_hash().startswith("es-")
        bumped = spec.with_overrides(["schedule.bits=2"])
        assert bumped.graph.content_hash() == spec.graph.content_hash()
        assert bumped.schedule.content_hash() != spec.schedule.content_hash()


class TestOverrides:
    def test_type_coercion(self):
        spec = RunSpec().with_overrides([
            "graph.avg_degree=12",          # int literal -> float field
            "exec.lr=0.05",
            "model.label_prop=false",
            "schedule.overlap=true",
            "schedule.inter_bits=null",
            "partition.strategy=hybrid",    # bare string
        ])
        assert spec.graph.avg_degree == 12.0
        assert spec.exec.lr == 0.05
        assert spec.model.label_prop is False
        assert spec.schedule.overlap is True
        assert spec.schedule.inter_bits is None

    @pytest.mark.parametrize("bad,msg", [
        ("nonsense", "KEY=VALUE"),
        ("bits=2", "section.field"),
        ("sched.bits=2", "unknown section"),
        ("schedule.bitz=2", "unknown field"),
        ("partition.nparts=4.5", "expected int"),
        ("model.label_prop=maybe", "expected bool"),
        ("exec.epochs=many", "expected int"),
    ])
    def test_bad_overrides_raise(self, bad, msg):
        with pytest.raises(SpecError, match=msg):
            RunSpec().with_overrides([bad])

    def test_later_override_wins(self):
        spec = RunSpec().with_overrides(["schedule.bits=2",
                                         "schedule.bits=4"])
        assert spec.schedule.bits == 4


class TestValidation:
    def test_groups_divisibility(self):
        with pytest.raises(SpecError, match="must divide"):
            RunSpec().with_overrides(["partition.nparts=8",
                                      "partition.groups=3"])

    def test_group_size_consistency(self):
        with pytest.raises(SpecError, match="must equal nparts"):
            RunSpec().with_overrides(["partition.nparts=8",
                                      "partition.groups=2",
                                      "partition.group_size=3"])

    def test_group_size_auto_derivation(self):
        spec = RunSpec().with_overrides(["partition.nparts=8",
                                         "partition.groups=2"])
        assert spec.partition.resolved_group_size() == 4
        dc = spec.schedule.to_dist_config(spec.partition)
        assert (dc.num_groups, dc.group_size) == (2, 4)

    def test_unknown_graph_source(self):
        with pytest.raises(SpecError, match="unknown source"):
            RunSpec().with_overrides(["graph.source=ogbn-papers100M"])

    def test_unknown_feature_source(self):
        with pytest.raises(SpecError, match="unknown feature source"):
            RunSpec().with_overrides(["graph.features=pca"])

    def test_stage_override_needs_hierarchy(self):
        with pytest.raises(SpecError, match="partition.groups"):
            RunSpec().with_overrides(["schedule.inter_bits=2"])

    def test_unknown_field_in_dict(self):
        with pytest.raises(SpecError, match="unknown field"):
            RunSpec.from_dict({"schedule": {"bitz": 2}})
        with pytest.raises(SpecError, match="unknown section"):
            RunSpec.from_dict({"sched": {}})

    def test_bad_mode_and_bits(self):
        with pytest.raises(SpecError, match="vmap|shard_map"):
            RunSpec().with_overrides(["exec.mode=pmap"])
        with pytest.raises(SpecError, match="bits"):
            RunSpec().with_overrides(["schedule.bits=3"])

    def test_nprocs_validation(self):
        # nprocs is multiproc-only and must match the partition when set.
        with pytest.raises(SpecError, match="multiproc"):
            RunSpec().with_overrides(["exec.nprocs=4"])
        with pytest.raises(SpecError, match="one process per partition"):
            RunSpec().with_overrides(["partition.nparts=8",
                                      "exec.mode=multiproc",
                                      "exec.nprocs=4"])
        spec = RunSpec().with_overrides(["partition.nparts=4",
                                         "exec.mode=multiproc",
                                         "exec.nprocs=4"])
        assert spec.exec.nprocs == 4
        assert RunSpec().with_overrides(
            ["exec.mode=multiproc"]).exec.nprocs == 0  # 0 = inherit nparts


class TestLegacyAliases:
    def test_flag_asymmetry_fixed(self):
        # The launcher exposed --inter-bits/--inter-cd but not the intra
        # pair; the alias table now carries all four per-stage overrides.
        for dest in ("intra_bits", "inter_bits", "intra_cd", "inter_cd"):
            assert dest in LEGACY_ALIASES

    def test_legacy_namespace_to_overrides(self):
        ns = argparse.Namespace(nparts=8, groups=2, intra_bits=0,
                                inter_bits=2, bits=None, seed=3)
        ov = legacy_overrides(ns)
        assert "partition.nparts=8" in ov
        assert "schedule.intra_bits=0" in ov
        assert "schedule.inter_bits=2" in ov
        assert all(not o.startswith("schedule.bits=") for o in ov)
        # --seed fans out to every stage's seed (historical behavior).
        assert {"graph.seed=3", "partition.seed=3", "exec.seed=3"} <= set(ov)
        spec = RunSpec().with_overrides(ov)
        assert spec.partition.groups == 2 and spec.exec.seed == 3

    def test_train_parser_accepts_intra_flags(self):
        from repro.launch import train
        import sys
        argv, sys.argv = sys.argv, ["train", "--gcn", "--groups", "2",
                                    "--nparts", "4", "--intra-bits", "2",
                                    "--intra-cd", "2", "--print-spec"]
        try:
            with pytest.raises(SystemExit) as e:
                train.main()
            assert e.value.code == 0
        finally:
            sys.argv = argv


class TestInterBitsDefault:
    def test_hier_default_is_int2_inter(self):
        from repro.core.trainer import DistConfig, HIER_INTER_BITS_DEFAULT
        assert HIER_INTER_BITS_DEFAULT == 2
        dc = DistConfig(nparts=4, num_groups=2, group_size=2)
        stages = dc.schedule().stages
        assert stages[0].bits == 0 and stages[1].bits == 2

    def test_explicit_bits_inherited(self):
        from repro.core.trainer import DistConfig
        dc = DistConfig(nparts=4, bits=8, num_groups=2, group_size=2)
        assert [s.bits for s in dc.schedule().stages] == [8, 8]

    def test_inter_pin_fp32(self):
        from repro.core.trainer import DistConfig
        dc = DistConfig(nparts=4, inter_bits=0, num_groups=2, group_size=2)
        assert [s.bits for s in dc.schedule().stages] == [0, 0]

    def test_sync_fp32_pins_inter(self):
        from repro.core.trainer import DistConfig
        dc = DistConfig(nparts=4, num_groups=2, group_size=2).sync_fp32()
        assert all(s.bits == 0 and s.cd == 1 for s in dc.schedule().stages)

    def test_flat_unaffected(self):
        from repro.core.trainer import DistConfig
        assert DistConfig(nparts=4).schedule().stages[0].bits == 0


def _spec_files():
    """specs/*.json with the loader each needs: serve specs (a top-level
    ``serve`` section) are ServeSpecs, the rest RunSpecs."""
    from repro.serve.spec import ServeSpec, is_serve_spec_dict
    for p in sorted((ROOT / "specs").glob("*.json")):
        serve = is_serve_spec_dict(json.loads(p.read_text()))
        yield p, (ServeSpec if serve else RunSpec)


class TestCheckedInSpecs:
    def test_matrix_covers_support_classes(self):
        specs = {p.stem: cls.load(p) for p, cls in _spec_files()
                 if cls is RunSpec}
        assert len(specs) >= 5
        classes = {
            "flat_fp32": lambda s: (not s.partition.hierarchical
                                    and s.schedule.bits == 0),
            "hier_int2_inter": lambda s: (
                s.partition.hierarchical
                and s.schedule.to_dist_config(s.partition)
                .schedule().stages[1].bits == 2),
            "cd>1": lambda s: s.schedule.cd > 1,
            "coo": lambda s: s.schedule.agg_backend == "coo",
            "shard_map": lambda s: s.exec.mode == "shard_map",
        }
        for cname, pred in classes.items():
            assert any(pred(s) for s in specs.values()), \
                f"no canonical spec covers {cname}"

    def test_specs_round_trip_canonically(self):
        for p, cls in _spec_files():
            spec = cls.load(p)
            assert spec.to_json() + "\n" == p.read_text(), \
                f"{p.name} is not in canonical to_json() form"


class TestSessionParity:
    """build_session must reproduce the hand-assembled pipeline the
    launchers used to run, bit for bit — flat and hierarchical."""

    def _hand_trainer(self, spec):
        from repro.core import (DistConfig, DistributedTrainer, GCNConfig,
                                prepare_distributed)
        from repro.graph import (build_hierarchical_partitioned_graph,
                                 build_partitioned_graph, sbm_graph)
        from repro.graph.generators import sbm_features

        gs, ps, ss, ms, es = (spec.graph, spec.partition, spec.schedule,
                              spec.model, spec.exec)
        g = sbm_graph(gs.nodes, gs.classes, avg_degree=gs.avg_degree,
                      homophily=gs.homophily, seed=gs.seed)
        x, _ = sbm_features(g, gs.feat_dim, noise=gs.feat_noise,
                            seed=gs.seed + 1)
        gn = g.mean_normalized()
        if ps.hierarchical:
            W = ps.nparts // ps.groups
            pg = build_hierarchical_partitioned_graph(
                gn, ps.groups, W, strategy=ps.strategy, seed=ps.seed)
            dc = DistConfig(nparts=ps.nparts, bits=ss.bits, cd=ss.cd,
                            lr=es.lr, num_groups=ps.groups, group_size=W,
                            inter_bits=ss.inter_bits, inter_cd=ss.inter_cd)
        else:
            pg = build_partitioned_graph(gn, ps.nparts, strategy=ps.strategy,
                                         seed=ps.seed)
            dc = DistConfig(nparts=ps.nparts, bits=ss.bits, cd=ss.cd,
                            lr=es.lr)
        wd = prepare_distributed(gn, x, pg)
        cfg = GCNConfig(model=ms.model, in_dim=gs.feat_dim,
                        hidden_dim=ms.hidden_dim, num_classes=gs.classes,
                        num_layers=ms.num_layers, dropout=ms.dropout,
                        label_prop=ms.label_prop, quant_bits=ss.bits)
        return DistributedTrainer(cfg, dc, wd, mode="vmap", seed=es.seed)

    @pytest.mark.parametrize("topology", ["flat", "hier"])
    def test_loss_trajectory_matches_hand_constructed(self, topology):
        extra = (["partition.groups=2", "schedule.inter_bits=2",
                  "schedule.inter_cd=2"] if topology == "hier" else
                 ["schedule.bits=2"])
        spec = tiny_spec(*extra)
        session = build_session(spec)
        hand = self._hand_trainer(spec)
        for _ in range(3):
            m_s = session.train_epoch()
            m_h = hand.train_epoch()
            assert m_s["loss"] == m_h["loss"], topology
        np.testing.assert_array_equal(session.evaluate(), hand.evaluate())

    def test_cross_driver_first_epoch_loss_bit_identical(self, tmp_path):
        """Acceptance: serialize in one driver, load in another, identical
        first-epoch loss."""
        spec = tiny_spec("partition.groups=2")
        p = tmp_path / "handoff.json"
        spec.save(p)
        loss_a = build_session(spec).train_epoch()["loss"]
        loss_b = build_session(RunSpec.load(p)).train_epoch()["loss"]
        assert loss_a == loss_b

    def test_build_cache_hit_is_identical(self):
        cache = BuildCache()
        spec = tiny_spec()
        s1 = build_session(spec, cache=cache)
        s2 = build_session(spec.with_overrides(["schedule.bits=2"]),
                           cache=cache)
        assert s1.pg is s2.pg  # graph+partition stages shared
        l1 = s1.train_epoch()["loss"]
        l2 = build_session(spec).train_epoch()["loss"]
        assert l1 == l2

    def test_build_cache_keys_are_content_hashes(self):
        # The docstring's promise: cache keys ARE the sub-spec content
        # hashes stamped into artifacts, not ad-hoc JSON dumps.
        cache = BuildCache()
        spec = tiny_spec()
        assert BuildCache._graph_key(spec) == spec.graph.content_hash()
        assert BuildCache._part_key(spec) == (
            f"{spec.graph.content_hash()}|{spec.partition.content_hash()}")
        build_session(spec, cache=cache)
        assert set(cache.graphs) == {spec.graph.content_hash()}
        # A downstream-only change (schedule) shares both stages; a graph
        # change misses.
        build_session(spec.with_overrides(["schedule.bits=2"]), cache=cache)
        assert len(cache.graphs) == 1 and len(cache.partitions) == 1
        build_session(spec.with_overrides(["graph.seed=9"]), cache=cache)
        assert len(cache.graphs) == 2 and len(cache.partitions) == 2

    def test_stage_hlo_payload_bytes_ceil_div(self):
        # Odd row counts still ship a (zero, scale) pair for the partial
        # trailing ROW_GROUP — ceil-div, not the old floor-div undercount.
        from repro.run.session import stage_hlo_payload_bytes
        assert stage_hlo_payload_bytes(8, 4, 0) == 8 * 4 * 4.0
        assert stage_hlo_payload_bytes(8, 4, 2) == 8 * 4 * 4.0 + 2 * 2 * 4.0
        # 6 rows = 1 full group + 1 partial -> 2 (zero, scale) pairs.
        assert stage_hlo_payload_bytes(6, 8, 2) == 6 * 8 * 4.0 + 2 * 2 * 4.0
        # rows=1: floor-div said 0 quant-param bytes; ceil says 1 pair.
        assert stage_hlo_payload_bytes(1, 8, 4) == 1 * 8 * 4.0 + 1 * 2 * 4.0

    def test_session_lower_and_accounting(self):
        spec = tiny_spec("partition.groups=2")
        session = build_session(spec)
        # vmap lowers the virtual-worker collectives to dense ops, so only
        # assert the dry-run hook produces a lowerable module.
        text = session.lower().as_text()
        assert "func.func public" in text
        wb = session.predicted_wire_bytes()
        assert set(wb) == {"intra", "inter"} and wb["inter"] > 0
        assert session.comm_stats().num_groups == 2
