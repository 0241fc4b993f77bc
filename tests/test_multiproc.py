"""Multi-process runtime (``exec.mode="multiproc"``): loss-trajectory
parity against the in-process vmap trainer, numpy wire packing vs the
jax reference, and shared-memory teardown (normal exit and a worker
killed mid-run must both leave zero leaked segments).

Spawning real OS processes (each importing jax) is expensive on the
1-core CI box, so each fleet is module-scoped and every assertion that
can share a fleet does.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.launch.multiproc import (
    MultiprocRuntime,
    _np_pack,
    _np_unpack,
    _pack_chunk,
    _unpack_chunk,
    chunk_bytes,
    quant_payload_bytes,
)
from repro.launch.shm_store import leaked_segments
from repro.quant.stochastic import pack_bits
from repro.run import RunSpec, build_session

TOL = 1e-5  # float drift budget: psum order + batched-vs-single matmul ulps


def _flat_spec():
    """P=2 flat Int2: feat 16 = one packed int32 word per row at 2 bits,
    so the packed mailbox payload path is what's exercised."""
    return RunSpec().with_overrides([
        "graph.source=sbm", "graph.nodes=96", "graph.classes=4",
        "graph.feat_dim=16", "graph.feat_noise=2.0", "graph.homophily=0.8",
        "graph.norm=mean", "partition.nparts=2", "schedule.bits=2",
        "model.model=sage", "model.hidden_dim=16", "model.num_layers=2",
        "model.dropout=0.0", "model.label_prop=false",
        "exec.mode=multiproc", "exec.nprocs=2", "exec.epochs=3"])


def _hier_spec():
    """P=4 hierarchical 2x2, Int2 inter wire, cd=2 (epochs alternate
    refresh/stale), overlap on — the flagship shape at toy scale."""
    return RunSpec().with_overrides([
        "graph.source=sbm", "graph.nodes=128", "graph.classes=4",
        "graph.feat_dim=16", "graph.feat_noise=2.0", "graph.homophily=0.8",
        "graph.norm=mean", "partition.nparts=4", "partition.groups=2",
        "schedule.inter_bits=2", "schedule.inter_cd=2",
        "schedule.overlap=true", "schedule.agg_backend=ell",
        "model.model=sage", "model.hidden_dim=16", "model.num_layers=2",
        "model.dropout=0.0", "model.label_prop=true",
        "exec.mode=multiproc", "exec.nprocs=4", "exec.epochs=4"])


def _trajectories(spec, epochs):
    """(multiproc losses, vmap losses, eval accs, runtime stats)."""
    mp_losses, vm_losses = [], []
    session = build_session(spec)
    rt = session.trainer
    try:
        for _ in range(epochs):
            mp_losses.append(session.train_epoch()["loss"])
        mp_eval = session.evaluate()
        stats = {"token": rt.token, "epoch_stats": list(rt.epoch_stats),
                 "summary": rt.summary()}
    finally:
        session.close()
    vspec = spec.with_overrides(["exec.mode=vmap", "exec.nprocs=0"])
    vsession = build_session(vspec)
    try:
        for _ in range(epochs):
            vm_losses.append(vsession.train_epoch()["loss"])
        vm_eval = vsession.evaluate()
    finally:
        vsession.close()
    return mp_losses, vm_losses, (mp_eval, vm_eval), stats


@pytest.fixture(scope="module")
def flat_run():
    return _trajectories(_flat_spec(), epochs=3)


@pytest.fixture(scope="module")
def hier_run():
    return _trajectories(_hier_spec(), epochs=4)


class TestParity:
    def test_flat_int2_loss_trajectory_matches_vmap(self, flat_run):
        mp_losses, vm_losses, (mp_eval, vm_eval), _ = flat_run
        assert len(mp_losses) == 3
        np.testing.assert_allclose(mp_losses, vm_losses, atol=TOL, rtol=0)
        assert mp_eval == pytest.approx(vm_eval, abs=TOL)

    def test_hier_int2_cd2_loss_trajectory_matches_vmap(self, hier_run):
        """Covers refresh AND stale (delayed-comm) epochs: cd=2 over 4
        epochs serves the cached inter wire on epochs 1 and 3."""
        mp_losses, vm_losses, (mp_eval, vm_eval), _ = hier_run
        assert len(mp_losses) == 4
        np.testing.assert_allclose(mp_losses, vm_losses, atol=TOL, rtol=0)
        assert mp_eval == pytest.approx(vm_eval, abs=TOL)

    def test_cd2_stale_epochs_send_fewer_wire_bytes(self, hier_run):
        """The measured proof that cd>1 skips the stale send: per-epoch
        wire-byte counters must alternate high (refresh) / low (stale)."""
        *_, stats = hier_run
        per_epoch = [s["wire_bytes"][0] for s in stats["epoch_stats"]]
        refresh, stale = per_epoch[0], per_epoch[1]
        assert stale < refresh
        assert per_epoch == [refresh, stale, refresh, stale]

    def test_rank_rss_shows_one_shared_store_copy(self, hier_run):
        """Attaching the store must not duplicate it per rank: the RSS
        delta across attach stays far below the store size + each rank's
        private slices stay bounded."""
        *_, stats = hier_run
        smry = stats["summary"]
        for r in smry["ranks"]:
            attach_delta = r["rss_after_attach"] - r["rss_before_attach"]
            assert attach_delta < max(smry["store_bytes"], 1 << 20)

    def test_fleet_runs_on_the_cpu(self, hier_run):
        """Children are spawned with JAX_PLATFORMS=cpu: the fleet never
        competes with its parent for an accelerator."""
        *_, stats = hier_run
        smry = stats["summary"]
        assert smry["platform"] == "cpu"
        assert [r["platform"] for r in smry["ranks"]] == ["cpu"] * len(
            smry["ranks"])


class TestTeardown:
    def test_normal_exit_unlinks_all_segments(self, flat_run, hier_run):
        for run in (flat_run, hier_run):
            token = run[-1]["token"]
            assert token is not None
            assert leaked_segments(token) == []

    def test_killed_worker_aborts_run_and_unlinks(self):
        session = build_session(_flat_spec())
        rt = session.trainer
        try:
            session.train_epoch()  # spawn + one good epoch
            token = rt.token
            rt._procs[1].kill()
            with pytest.raises(RuntimeError, match="multiproc run aborted"):
                for _ in range(2):  # next command must detect the death
                    session.train_epoch()
        finally:
            session.close()
        assert leaked_segments(token) == []


class TestAccounting:
    def test_dry_plan_spawns_no_processes(self):
        session = build_session(_flat_spec())
        rt = session.trainer
        try:
            assert isinstance(rt, MultiprocRuntime)
            plan = rt.dry_plan()
            assert plan["store_bytes"] > 0
            assert plan["mailbox_bytes"] > 0
            assert plan["mailbox_ops"] > 0
            assert rt._procs == [] and not rt._started
            assert rt.lower_step is not None
            with pytest.raises(NotImplementedError):
                rt.lower_step()
        finally:
            session.close()

    def test_nprocs_must_match_nparts(self):
        spec = _flat_spec()
        with pytest.raises(Exception, match="per partition"):
            spec.with_overrides(["exec.nprocs=3"])


class TestWirePacking:
    def test_np_pack_matches_jax_pack_bits(self):
        rng = np.random.default_rng(0)
        for bits in (2, 4, 8):
            q = rng.integers(0, 1 << bits, size=(8, 32), dtype=np.int32)
            ours = _np_pack(q, bits)
            ref = np.asarray(pack_bits(jnp.asarray(q), bits))
            np.testing.assert_array_equal(ours.view(np.int32), ref)
            np.testing.assert_array_equal(_np_unpack(ours, bits, 32), q)

    def test_chunk_roundtrip_packed_and_fallback(self):
        rng = np.random.default_rng(1)
        for rows, feat, bits in ((8, 16, 2), (8, 6, 4)):  # packed, fallback
            q = rng.integers(0, 1 << bits, size=(rows, feat), dtype=np.int32)
            zero = rng.standard_normal(rows // 4).astype(np.float32)
            scale = rng.standard_normal(rows // 4).astype(np.float32)
            buf = _pack_chunk(q, zero, scale, bits)
            assert buf.nbytes == chunk_bytes(rows, feat, bits)
            q2, z2, s2 = _unpack_chunk(buf, rows, feat, bits)
            np.testing.assert_array_equal(q2, q)
            np.testing.assert_array_equal(z2, zero)
            np.testing.assert_array_equal(s2, scale)

    def test_payload_bytes(self):
        assert quant_payload_bytes(8, 16, 2) == 8 * 4      # one word/row
        assert quant_payload_bytes(8, 6, 4) == 8 * 6       # byte fallback
        assert chunk_bytes(8, 16, 0) == 8 * 16 * 4         # fp32 wire
