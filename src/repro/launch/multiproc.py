"""Multi-process training runtime over the shared-memory graph store.

``ExecSpec.mode="multiproc"`` runs P *real* pinned OS processes (spawned
via ``multiprocessing``, ``OMP_NUM_THREADS`` partitioned across ranks)
instead of P virtual vmap workers in one address space. The parent builds
the partition once (``prepare_distributed_host``) and publishes every
partition-time array — features, labels, masks, COO triples, bucketed-ELL
layouts, halo plans — through one :class:`~repro.launch.shm_store.ShmArena`
segment; each worker maps that single copy and device-materializes only
its own rank's slice, so co-located workers cost one partition copy
(measured by per-rank RSS), the DGL ``dist_graph`` shared-store shape.

The halo exchange executes the *existing* :class:`ExchangeSchedule` stage
plans over shared-memory mailboxes. Each stage's wire pipeline decomposes
into the same collective sequence the in-process runtime lowers —

  a2a stages      quantize(full wire buffer) -> all_to_all of
                  (packed ints + fp32 zero/scale per 4-row group)
                  -> dequantize
  grouped stages  psum_scatter over the node axis -> quantized all_to_all
                  over the group axis -> all_gather over the node axis

— realized as host rounds of :meth:`Mailboxes.post` / ``collect`` with the
identical per-stage PRNG folds, so the loss trajectory matches the
in-process vmap run to float tolerance. Two ``jax.custom_vjp`` transports
(:func:`_mp_post` / :func:`_mp_collect`) wrap the host rounds in
``jax.pure_callback`` so gradients flow through the wire with the same
self-transpose structure (re-quantized backward all_to_all under the
``fold_in(key, 0x5BD1)`` backward key).

What becomes *measured* instead of modelled here (the ROADMAP item):

* overlap — an ``overlap=True`` stage posts its send chunks in the layer's
  ``issue`` phase and only spin-waits on peers in ``finalize``, after the
  local bucketed aggregation; with ``overlap=False`` every rank posts and
  immediately waits while its peers are still aggregating. The wall-clock
  difference is the real (not HLO-order-inferred) overlap win.
* delayed communication — on a stale epoch (``epoch % cd != 0``) the
  transport is *skipped entirely* (no bytes posted; ``Mailboxes``
  byte counters prove it), not computed-and-discarded as under jit.

Determinism: every rank executes the identical linear sequence of mailbox
ops per epoch (same program, deterministic autodiff order), each op's
posts precede its reads, and the per-epoch gradient all-reduce is a full
barrier — so the wire is deadlock-free and slot reuse across epochs is
safe. The all-reduce sums contributions in rank order on every rank, so
optimizer states stay bitwise identical with no broadcast.

Fault tolerance (:class:`MultiprocRuntime` docstring has the protocol):
per-rank heartbeat words let the parent tell dead / hung / failing
workers apart; on failure it quiesces survivors through the RECOVER
control word, respawns the lost ranks against the existing segments,
restores everyone from the newest per-rank checkpoint step all ranks
hold, and retries — degrading to a clean abort (segments unlinked,
checkpoints preserved) after ``exec.max_restarts`` recoveries. The
deterministic chaos harness (``repro.launch.chaos``) drives this path.
"""

from __future__ import annotations

import functools
import multiprocessing as mp
import os
import signal
import time
import traceback
from pathlib import Path
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from repro.checkpoint.ckpt import (
    CheckpointManager,
    latest_common_step,
    restore_train_state,
)
from repro.core import model as M
from repro.core.exchange import (
    DeviceHaloPlan,
    DeviceHierPlan,
    ExchangeSchedule,
    StageSpec,
    StageTopo,
    assemble_send,
    scatter_recv,
)
from repro.core.trainer import WorkerData, _local_aggregate
from repro.kernels import device_bucketed
from repro.launch.shm_store import (
    Mailboxes,
    ShmArena,
    TransportAborted,
    TransportRecover,
    TransportTimeout,
    plan_mailbox,
    publish_store,
    rss_bytes,
    run_token,
)
from repro.optim import adamw_init, adamw_update

_THREAD_ENV = ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS", "MKL_NUM_THREADS")
# The fleet emulates the paper's CPU workers. A chip belongs to one
# process, so children are pinned to the CPU and never race the parent
# (or each other) for an accelerator.
_FLEET_PLATFORM = "cpu"
_BWD_KEY_FOLD = 0x5BD1  # must match exchange._quantized_exchange_bwd
_WORKER_WAIT_S = 600.0  # mailbox spin deadline (1-core containers are slow)
_PARENT_WAIT_S = 900.0  # parent deadline per command round
_RECOVER_DRAIN_S = 120.0  # per-round deadline quiescing survivors
_COLD_GRACE_S = 300.0  # hang deadline for a rank's first command: a fresh
# worker compiles its whole first epoch before the mailbox ops that bump
# its heartbeat, and must not read as hung at tight heartbeat_s settings
_CHAOS_STALL_S = 3600.0  # a chaos "stall" sleeps this long (heartbeat-free)

# Deterministic fault injection (launch.chaos): a worker whose rank matches
# REPRO_CHAOS_RANK fires REPRO_CHAOS_FAULT (kill | stall) at the start of
# the train_epoch that follows REPRO_CHAOS_EPOCH completed epochs — but
# only on spawn generation 0, so a respawned worker never re-triggers.
_CHAOS_ENV = ("REPRO_CHAOS_FAULT", "REPRO_CHAOS_RANK", "REPRO_CHAOS_EPOCH")


def _chaos_from_env(rank: int, generation: int) -> Optional[dict]:
    fault = os.environ.get("REPRO_CHAOS_FAULT")
    if not fault or generation != 0:
        return None
    if int(os.environ.get("REPRO_CHAOS_RANK", "0")) != rank:
        return None
    return {"fault": fault,
            "epoch": int(os.environ.get("REPRO_CHAOS_EPOCH", "1"))}


def _transport_kind(e: BaseException) -> Optional[str]:
    """Classify an exception escaping a worker command: "recover" /
    "abort" / "timeout" transport conditions, else None (a real error).
    The transports fire inside ``jax.pure_callback``, which may re-raise
    them wrapped (XlaRuntimeError), so walk the cause/context chain and
    fall back to matching the rendered message."""
    seen, stack = set(), [e]
    while stack:
        x = stack.pop()
        if x is None or id(x) in seen:
            continue
        seen.add(id(x))
        if isinstance(x, TransportRecover):
            return "recover"
        if isinstance(x, TransportAborted):
            return "abort"
        if isinstance(x, TransportTimeout):
            return "timeout"
        stack += [x.__cause__, x.__context__]
    s = repr(e)
    for name, kind in (("TransportRecover", "recover"),
                       ("TransportAborted", "abort"),
                       ("TransportTimeout", "timeout")):
        if name in s:
            return kind
    return None


# --------------------------------------------------------------------------
# Wire payload accounting + numpy bit packing (matches quant.pack_bits)
# --------------------------------------------------------------------------


def quant_payload_bytes(rows: int, feat: int, bits: int) -> int:
    """Mailbox bytes for a quantized [rows, feat] chunk's int payload:
    packed int32 words when the feature width divides the word, else one
    byte per value (the unpacked fallback)."""
    per_word = 32 // bits
    if feat % per_word == 0:
        return rows * (feat // per_word) * 4
    return rows * feat


def chunk_bytes(rows: int, feat: int, bits: int) -> int:
    """Mailbox slot bytes for one wire chunk (payload + fp32 zero/scale
    per 4-row quant group when the stage quantizes)."""
    if not bits:
        return rows * feat * 4
    return quant_payload_bytes(rows, feat, bits) + (rows // 4) * 2 * 4


def _np_pack(q: np.ndarray, bits: int) -> np.ndarray:
    """Pack ints in [0, 2^bits) into uint32 words, little-end-first within
    the word — the same layout as ``quant.stochastic.pack_bits``."""
    per = 32 // bits
    rows, feat = q.shape
    qw = q.reshape(rows, feat // per, per).astype(np.uint32)
    shifts = (np.arange(per, dtype=np.uint32) * np.uint32(bits))
    return (qw << shifts[None, None, :]).sum(axis=-1, dtype=np.uint32)


def _np_unpack(words: np.ndarray, bits: int, feat: int) -> np.ndarray:
    per = 32 // bits
    rows = words.shape[0]
    shifts = (np.arange(per, dtype=np.uint32) * np.uint32(bits))
    mask = np.uint32((1 << bits) - 1)
    q = (words[:, :, None] >> shifts[None, None, :]) & mask
    return q.reshape(rows, feat).astype(np.int32)


def _pack_chunk(q: np.ndarray, zero: np.ndarray, scale: np.ndarray,
                bits: int) -> np.ndarray:
    """[payload][zero f32][scale f32] as one contiguous uint8 buffer."""
    rows, feat = q.shape
    if feat % (32 // bits) == 0:
        payload = np.ascontiguousarray(_np_pack(q, bits)).view(np.uint8)
    else:
        payload = q.astype(np.uint8)
    return np.concatenate([
        payload.reshape(-1),
        np.ascontiguousarray(zero, dtype=np.float32).view(np.uint8),
        np.ascontiguousarray(scale, dtype=np.float32).view(np.uint8),
    ])


def _unpack_chunk(buf: np.ndarray, rows: int, feat: int, bits: int
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    groups = rows // 4
    pe = buf.nbytes - 2 * groups * 4
    zero = buf[pe:pe + groups * 4].copy().view(np.float32)
    scale = buf[pe + groups * 4:].copy().view(np.float32)
    payload = buf[:pe]
    if feat % (32 // bits) == 0:
        words = payload.copy().view(np.uint32).reshape(rows, -1)
        q = _np_unpack(words, bits, feat)
    else:
        q = payload.reshape(rows, feat).astype(np.int32)
    return q, zero, scale


def _as_f32(buf: np.ndarray, rows: int, feat: int) -> np.ndarray:
    return buf.copy().view(np.float32).reshape(rows, feat)


# --------------------------------------------------------------------------
# Op table: every (op id, src->dst pair, slot bytes) of one run
# --------------------------------------------------------------------------


def _ordered_pairs(ranks: Sequence[int]) -> List[List[int]]:
    return [[s, d] for s in ranks for d in ranks]


def _a2a_pairs(nprocs: int, chunks: int) -> List[List[int]]:
    """Pair set of a tiled all_to_all: all ordered pairs inside each
    contiguous block of ``chunks`` ranks (the whole world when chunks ==
    nprocs — the flat exchange; per-group blocks for the intra level)."""
    if chunks == nprocs:
        return _ordered_pairs(range(nprocs))
    pairs: List[List[int]] = []
    for g in range(nprocs // chunks):
        pairs.extend(_ordered_pairs(range(g * chunks, (g + 1) * chunks)))
    return pairs


def _grouped_pairs(nprocs: int, num_groups: int, group_size: int
                   ) -> Tuple[List[List[int]], List[List[int]]]:
    """(node-axis mate pairs, group-axis peer pairs) of the grouped stage.
    Rank r sits at (g, w) = (r // W, r % W) — the stacked [G, W] order the
    hierarchical vmap runtime uses."""
    mates: List[List[int]] = []
    for g in range(num_groups):
        mates.extend(_ordered_pairs(
            [g * group_size + v for v in range(group_size)]))
    gpeers: List[List[int]] = []
    for w in range(group_size):
        gpeers.extend(_ordered_pairs(
            [b * group_size + w for b in range(num_groups)]))
    return mates, gpeers


def _op(op_id: str, pairs: List[List[int]], nbytes: int) -> dict:
    return {"id": op_id, "pairs": [[s, d, nbytes] for s, d in pairs]}


def build_op_table(schedule: ExchangeSchedule,
                   eval_schedule: ExchangeSchedule,
                   nprocs: int, num_layers: int,
                   feat_dims: Sequence[int],
                   wire_rows: Dict[str, int],
                   nparams: int) -> List[dict]:
    """The full mailbox op table of one run: per (tag, layer, stage) the
    stage's collective sub-ops, plus the global reductions. Parent and
    workers derive op ids from the same (schedule, layer) naming, so the
    table is the single source of slot layout truth."""
    ops: List[dict] = []
    for tag, sched in (("t", schedule), ("e", eval_schedule)):
        for l in range(num_layers):
            f = feat_dims[l]
            for stage in sched.stages:
                topo = sched.topo(stage)
                rows = wire_rows[stage.level]
                base = f"{tag}.L{l}.{stage.level}"
                if topo.kind == "a2a":
                    nb = chunk_bytes(rows // topo.wire_chunks, f, stage.bits)
                    pairs = _a2a_pairs(nprocs, topo.wire_chunks)
                    ops.append(_op(f"{base}.x", pairs, nb))
                    if tag == "t":
                        ops.append(_op(f"{base}.xb", pairs, nb))
                else:
                    G, W = topo.wire_chunks, topo.shard_size
                    s = rows // (G * W)
                    mates, gpeers = _grouped_pairs(nprocs, G, W)
                    shard_nb = G * s * f * 4
                    a2a_nb = chunk_bytes(s, f, stage.bits)
                    names = [("psc", mates, shard_nb),
                             ("a2a", gpeers, a2a_nb),
                             ("ag", mates, shard_nb)]
                    if tag == "t":
                        names += [("pscb", mates, shard_nb),
                                  ("a2ab", gpeers, a2a_nb),
                                  ("agb", mates, shard_nb)]
                    for name, pairs, nb in names:
                        ops.append(_op(f"{base}.{name}", pairs, nb))
    world = _ordered_pairs(range(nprocs))
    ops.append(_op("t.cnt", world, 4))
    ops.append(_op("t.grads", world, (nparams + 3) * 4))
    ops.append(_op("e.metrics", world, 8))
    return ops


# --------------------------------------------------------------------------
# The two custom-VJP transports (host rounds behind pure_callback)
# --------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mp_post(ex, send):
    """Post ``send``'s wire chunks to peers (no waiting) and pass ``send``
    through as the in-flight carrier :func:`_mp_collect` consumes."""
    jax.pure_callback(ex.h_post, ex.dummy_struct, send)
    return send


def _mp_post_fwd(ex, send):
    return _mp_post(ex, send), None


def _mp_post_bwd(ex, _res, g):
    return (g,)


_mp_post.defvjp(_mp_post_fwd, _mp_post_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mp_collect(ex, carrier):
    """Wait for peers' chunks and assemble this stage's full recv buffer.
    The backward rule runs the stage's transpose wire (re-quantized under
    the backward key) as one combined host round."""
    return jax.pure_callback(ex.h_collect, ex.recv_struct, carrier)


def _mp_collect_fwd(ex, carrier):
    return _mp_collect(ex, carrier), None


def _mp_collect_bwd(ex, _res, g):
    return (jax.pure_callback(ex.h_bwd, ex.send_struct, g),)


_mp_collect.defvjp(_mp_collect_fwd, _mp_collect_bwd)


# --------------------------------------------------------------------------
# Per-(tag, layer, stage) executor: the host halves of the wire
# --------------------------------------------------------------------------


class _StageExec:
    """One stage's mailbox geometry + host transport rounds for one rank.

    Forward a2a stages split across ``h_post`` (quantize + post chunks;
    runs in the layer's issue phase for overlapped stages) and
    ``h_collect`` (wait + assemble + dequantize). Grouped stages post
    their psum_scatter contributions in ``h_post`` and run the remaining
    rounds (scatter-sum, quantized group all_to_all, node all_gather) in
    ``h_collect``. ``h_bwd`` is the stage's full transpose pipeline in one
    combined round — identical collective structure to the in-process
    custom VJP, including the ``fold_in(key, 0x5BD1)`` backward quant key.

    The callback bodies are **pure numpy + mailbox** by design: under the
    overlapped schedule XLA runs ``h_collect`` on its own callback thread
    concurrently with the main thread's eager dispatch of the local
    aggregation, and a nested jax dispatch from that thread deadlocks on
    jax/XLA internal locks (observed as an all-threads futex hang). The
    stochastic-rounding uniforms depend only on (key, shape), so
    :meth:`begin` draws them through the real jax PRNG on the main
    thread; the quantize/dequantize arithmetic is replicated in float32
    numpy (same op order as ``quant.stochastic``).
    """

    def __init__(self, mb: Mailboxes, op_base: str, spec: StageSpec,
                 topo: StageTopo, rank: int, nprocs: int,
                 rows: int, feat: int):
        self.mb = mb
        self.bits = spec.bits
        self.topo = topo
        self.rank, self.nprocs = rank, nprocs
        self.rows, self.feat = rows, feat
        if topo.kind == "a2a":
            C = topo.wire_chunks
            g = rank // C if C < nprocs else 0
            self.peers = [g * C + j for j in range(C)]
            self.chunk_rows = rows // C
            self.op_x, self.op_xb = f"{op_base}.x", f"{op_base}.xb"
        else:
            G, W = topo.wire_chunks, topo.shard_size
            g, w = rank // W, rank % W
            self.G, self.W = G, W
            self.s = rows // (G * W)
            self.mates = [g * W + v for v in range(W)]
            self.gpeers = [b * W + w for b in range(G)]
            for name in ("psc", "a2a", "ag", "pscb", "a2ab", "agb"):
                setattr(self, f"op_{name}", f"{op_base}.{name}")
        self.recv_struct = jax.ShapeDtypeStruct((rows, feat), jnp.float32)
        self.send_struct = jax.ShapeDtypeStruct((rows, feat), jnp.float32)
        self.dummy_struct = jax.ShapeDtypeStruct((), jnp.int32)
        # Rows of the buffer each quantized round covers: the full wire
        # buffer for flat a2a, the psum_scattered [G*s, F] shard pipeline
        # for grouped stages (forward middle a2a and its transpose).
        self._qrows = rows if topo.kind == "a2a" else self.G * self.s
        self._u_fwd: Optional[np.ndarray] = None
        self._u_bwd: Optional[np.ndarray] = None

    def begin(self, key) -> None:
        """Draw this execution's stochastic-rounding uniforms on the main
        thread (the only place jax may dispatch — see class docstring).
        They depend on (key, shape) alone, exactly as ``quantize`` draws
        them internally, so bit parity with the in-process wire holds;
        the backward round's key folds in the 0x5BD1 constant."""
        if key is None or not self.bits:
            self._u_fwd = self._u_bwd = None
            return
        k = jnp.asarray(np.asarray(key))
        shape = (self._qrows // 4, 4, self.feat)
        self._u_fwd = np.asarray(
            jax.random.uniform(k, shape, dtype=jnp.float32))
        self._u_bwd = np.asarray(jax.random.uniform(
            jax.random.fold_in(k, _BWD_KEY_FOLD), shape, dtype=jnp.float32))

    # -- quant helpers (float32 numpy, same op order as quant.stochastic) --

    def _quantize(self, w: np.ndarray, u: np.ndarray):
        rows, feat = w.shape
        levels = np.float32((1 << self.bits) - 1)
        g = rows // 4
        xg = w.reshape(g, 4 * feat)
        lo, hi = xg.min(axis=1), xg.max(axis=1)
        scale = (hi - lo) / levels
        safe = np.where(scale > 0, scale, np.float32(1.0))
        rcp = np.float32(1.0) / safe
        xs = (w.reshape(g, 4, feat) - lo[:, None, None]) * rcp[:, None, None]
        q = np.clip(np.floor(xs + u), 0, levels)
        return (q.astype(np.int32).reshape(rows, feat), lo,
                np.where(scale > 0, scale, np.float32(0.0)))

    @staticmethod
    def _dequantize(q, zero, scale) -> np.ndarray:
        rows, feat = q.shape
        g = rows // 4
        x = (q.astype(np.float32).reshape(g, 4, feat)
             * scale[:, None, None] + zero[:, None, None])
        return x.reshape(rows, feat)

    # -- a2a rounds --------------------------------------------------------

    def _a2a_round(self, op: str, w: np.ndarray, peers: Sequence[int],
                   rows: int, u: Optional[np.ndarray]) -> np.ndarray:
        """One quantize-post-collect-dequantize all_to_all of wire buffer
        ``w`` ([len(peers)*rows, feat]) over ``peers``, chunk j <-> peer j."""
        self._a2a_post(op, w, peers, rows, u)
        return self._a2a_read(op, peers, rows)

    def _a2a_post(self, op: str, w: np.ndarray, peers: Sequence[int],
                  rows: int, u: Optional[np.ndarray]) -> None:
        if self.bits:
            q, zero, scale = self._quantize(w, u)
            gpc = rows // 4
            for j, peer in enumerate(peers):
                self.mb.post(op, peer, _pack_chunk(
                    q[j * rows:(j + 1) * rows],
                    zero[j * gpc:(j + 1) * gpc],
                    scale[j * gpc:(j + 1) * gpc], self.bits))
        else:
            for j, peer in enumerate(peers):
                self.mb.post(op, peer, np.ascontiguousarray(
                    w[j * rows:(j + 1) * rows], dtype=np.float32))

    def _a2a_read(self, op: str, peers: Sequence[int], rows: int
                  ) -> np.ndarray:
        parts = [self.mb.collect(op, peer) for peer in peers]
        self.mb.complete(op)
        if self.bits:
            qs, zs, ss = zip(*(_unpack_chunk(p, rows, self.feat, self.bits)
                               for p in parts))
            return self._dequantize(np.concatenate(qs),
                                    np.concatenate(zs), np.concatenate(ss))
        return np.concatenate([_as_f32(p, rows, self.feat) for p in parts])

    # -- grouped sub-rounds ------------------------------------------------

    def _psc_post(self, op: str, x: np.ndarray) -> None:
        """Post psum_scatter contributions: mate at node index w gets my
        [G, s, F] slice y[:, w]."""
        y = x.reshape(self.G, self.W, self.s, self.feat)
        for w_i, mate in enumerate(self.mates):
            self.mb.post(op, mate, np.ascontiguousarray(y[:, w_i]))

    def _psc_read(self, op: str) -> np.ndarray:
        """Sum the W mates' contributions in node-index order -> [G*s, F]."""
        acc = np.zeros((self.G, self.s, self.feat), np.float32)
        for mate in self.mates:
            acc += self.mb.collect(op, mate).view(np.float32).reshape(
                self.G, self.s, self.feat)
        self.mb.complete(op)
        return acc.reshape(self.G * self.s, self.feat)

    def _ag_round(self, op: str, shard: np.ndarray) -> np.ndarray:
        """all_gather over the node axis: [G*s, F] -> [G*W*s, F]."""
        buf = np.ascontiguousarray(shard, dtype=np.float32)
        for mate in self.mates:
            self.mb.post(op, mate, buf)
        parts = [self.mb.collect(op, mate).view(np.float32).reshape(
            self.G, self.s, self.feat) for mate in self.mates]
        self.mb.complete(op)
        return np.stack(parts, axis=1).reshape(self.rows, self.feat)

    # -- the three pure_callback entry points ------------------------------

    def h_post(self, send) -> np.int32:
        send = np.asarray(send, np.float32)
        if self.topo.kind == "a2a":
            self._a2a_post(self.op_x, send, self.peers, self.chunk_rows,
                           self._u_fwd)
        else:
            self._psc_post(self.op_psc, send)
        return np.int32(0)

    def h_collect(self, _carrier) -> np.ndarray:
        if self.topo.kind == "a2a":
            return self._a2a_read(self.op_x, self.peers, self.chunk_rows)
        shard = self._psc_read(self.op_psc)
        wire = self._a2a_round(self.op_a2a, shard, self.gpeers, self.s,
                               self._u_fwd)
        return self._ag_round(self.op_ag, wire)

    def h_bwd(self, g) -> np.ndarray:
        g = np.asarray(g, np.float32)
        if self.topo.kind == "a2a":
            return self._a2a_round(self.op_xb, g, self.peers,
                                   self.chunk_rows, self._u_bwd)
        # Transpose of ag -> psum_scatter of the cotangent; then the
        # re-quantized group all_to_all; then the transpose of the forward
        # psum_scatter -> all_gather. Same rounds, reverse roles.
        self._psc_post(self.op_pscb, g)
        gw = self._psc_read(self.op_pscb)
        gr = self._a2a_round(self.op_a2ab, gw, self.gpeers, self.s,
                             self._u_bwd)
        return self._ag_round(self.op_agb, gr)


# --------------------------------------------------------------------------
# Per-layer program over the mailbox wire (mirrors exchange.LayerProgram)
# --------------------------------------------------------------------------


class _MpInFlight(NamedTuple):
    h: jax.Array
    key: Optional[jax.Array]
    epoch: Optional[int]
    cache_entry: Optional[Sequence[jax.Array]]
    carrier: Tuple[Optional[jax.Array], ...]
    recv: Tuple[Optional[jax.Array], ...]
    entry: Tuple[Optional[jax.Array], ...]


class _MpLayerProgram:
    """One layer's schedule against the mailbox wire.

    Differences from the in-process :class:`LayerProgram` that change
    *timing*, never values: overlapped stages only post in ``issue``
    (collect happens in ``finalize``, after the local aggregation), and a
    delayed stage on a stale epoch skips its transport entirely — the
    in-process runtime computes-and-discards the fresh exchange under jit;
    here ``epoch`` is a concrete int on every rank, so all ranks agree to
    skip and the mailbox op counters stay aligned. The stale buffer is
    served under stop_gradient exactly like the in-process ``where``
    select (whose not-taken branch contributes exact zeros)."""

    def __init__(self, schedule: ExchangeSchedule, wd, agg_backend: str,
                 execs: Sequence[_StageExec]):
        self.agg_backend = agg_backend
        self._stages = tuple(
            (spec, schedule.plan_for(spec, wd)) for spec in schedule.stages)
        self._execs = tuple(execs)
        self._cache_slot = {si: ci for ci, si
                            in enumerate(schedule.delayed_indices)}
        self._issue_order = tuple(
            si for si in reversed(range(len(self._stages)))
            if self._stages[si][0].overlap)

    def _stale(self, si: int, spec: StageSpec, epoch, cache_entry: bool):
        if spec.delayed:
            if cache_entry is None or epoch is None:
                raise ValueError(
                    f"stage {spec.level!r} is delayed(cd={spec.cd}) and "
                    "needs a halo cache + epoch")
            return int(epoch) % spec.cd != 0
        return False

    def _launch(self, si: int, h, key):
        ex = self._execs[si]
        ex.begin(None if key is None else jax.random.fold_in(key, si))
        return _mp_post(ex, assemble_send(h, self._stages[si][1]))

    def issue(self, h: jax.Array, key, cache_entry=None,
              epoch: Optional[int] = None) -> _MpInFlight:
        n = len(self._stages)
        carrier: List[Optional[jax.Array]] = [None] * n
        recv: List[Optional[jax.Array]] = [None] * n
        entry: List[Optional[jax.Array]] = [None] * n
        for si in self._issue_order:
            spec = self._stages[si][0]
            if self._stale(si, spec, epoch, cache_entry):
                stale = jax.lax.stop_gradient(
                    cache_entry[self._cache_slot[si]])
                recv[si], entry[si] = stale, stale
            else:
                carrier[si] = self._launch(si, h, key)
        return _MpInFlight(h=h, key=key, epoch=epoch,
                           cache_entry=cache_entry, carrier=tuple(carrier),
                           recv=tuple(recv), entry=tuple(entry))

    def finalize(self, local_agg: jax.Array, inflight: _MpInFlight
                 ) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
        acc = local_agg
        new_entry: List[jax.Array] = []
        for si, (spec, plan) in enumerate(self._stages):
            r, e = inflight.recv[si], inflight.entry[si]
            if r is None and inflight.carrier[si] is not None:
                r = _mp_collect(self._execs[si], inflight.carrier[si])
                if spec.delayed:
                    e = jax.lax.stop_gradient(r)
            elif r is None:
                # Sequential (overlap=False) stage: post + collect
                # back-to-back, the strict in-order fallback.
                if self._stale(si, spec, inflight.epoch,
                               inflight.cache_entry):
                    stale = jax.lax.stop_gradient(
                        inflight.cache_entry[self._cache_slot[si]])
                    r, e = stale, stale
                else:
                    c = self._launch(si, inflight.h, inflight.key)
                    r = _mp_collect(self._execs[si], c)
                    if spec.delayed:
                        e = jax.lax.stop_gradient(r)
            if spec.delayed:
                new_entry.append(e)
            acc = scatter_recv(acc, r, plan, agg_backend=self.agg_backend)
        return acc, tuple(new_entry)


# --------------------------------------------------------------------------
# Worker process
# --------------------------------------------------------------------------


_PLAN_FIELDS = ("send_gather_idx", "send_gather_mask", "pre_src", "pre_slot",
                "pre_weight", "recv_row", "recv_dst", "recv_weight")
_PLAN_INT_FIELDS = frozenset(
    ("send_gather_idx", "pre_src", "pre_slot", "recv_row", "recv_dst"))


def _pin(rank: int, nprocs: int) -> None:
    """Pin this rank to its share of the CPU set (skip when the container
    has fewer cores than ranks — everyone shares)."""
    try:
        cpus = sorted(os.sched_getaffinity(0))
        if len(cpus) >= nprocs:
            per = len(cpus) // nprocs
            os.sched_setaffinity(0, set(cpus[rank * per:(rank + 1) * per]))
    except (AttributeError, OSError):
        pass


def _rank_ell(views: Dict[str, np.ndarray], prefix: str, ks: Sequence[int],
              rank: int):
    """Per-rank DeviceBucketedEll from the arena's stacked bucket arrays
    (device-copying only this rank's [1, ...] slices)."""
    if not ks:
        return None
    stacked = [(k, views[f"{prefix}.{i}.rows"][rank:rank + 1],
                views[f"{prefix}.{i}.idx"][rank:rank + 1],
                views[f"{prefix}.{i}.w"][rank:rank + 1])
               for i, k in enumerate(ks)]
    return device_bucketed(stacked, squeeze=True)


def _rank_plan(views: Dict[str, np.ndarray], prefix: str, plan_meta: dict,
               rank: int) -> DeviceHaloPlan:
    kw = {}
    for f in _PLAN_FIELDS:
        a = views[f"plan.{prefix}.{f}"][rank]
        kw[f] = (jnp.asarray(a, jnp.int32) if f in _PLAN_INT_FIELDS
                 else jnp.asarray(a))
    return DeviceHaloPlan(
        **kw,
        recv_ell=_rank_ell(views, f"plan.{prefix}.rell",
                           plan_meta["rell_ks"], rank),
        recv_ell_t=_rank_ell(views, f"plan.{prefix}.rellt",
                             plan_meta["rellt_ks"], rank))


class _RankWorker:
    """One rank's training state, rebuilt from the manifest + shared store.

    ``generation`` counts respawns of this rank (0 = original spawn); a
    respawned worker reattaches the *existing* segments — the store is
    never republished — so recovery costs O(one worker boot), not
    O(rebuild). When the manifest carries a ``ckpt`` section the worker
    snapshots its resumable state per epoch period into a per-rank
    :class:`CheckpointManager` directory, and the parent's ``restore``
    command winds the state back to a step every rank holds.
    """

    def __init__(self, rank: int, nprocs: int, manifest: dict,
                 generation: int = 0):
        from repro.run.spec import RunSpec

        self.rank, self.nprocs = rank, nprocs
        self.generation = generation
        self._chaos = _chaos_from_env(rank, generation)
        spec = RunSpec.from_dict(manifest["spec"])
        self.spec = spec
        self.dc = spec.schedule.to_dist_config(spec.partition,
                                               lr=spec.exec.lr)
        self.cfg = spec.model.to_gcn_config(spec.graph, spec.schedule)
        self.schedule = self.dc.schedule()
        self.eval_schedule = self.dc.sync_fp32().schedule()
        meta = manifest["meta"]

        self.rss_before_attach = rss_bytes()
        self.arena = ShmArena.attach(manifest["store"]["name"],
                                     manifest["store"]["table"])
        self.mb = Mailboxes.attach(manifest["mailbox"]["name"],
                                   manifest["mailbox"], rank,
                                   wait_timeout_s=_WORKER_WAIT_S)
        views = self.arena.views()
        self.rss_after_attach = rss_bytes()

        # Device-copy only this rank's slices of the shared store.
        plan = hier_plan = None
        if "flat" in meta["plans"]:
            plan = _rank_plan(views, "flat", meta["plans"]["flat"], rank)
        else:
            hier_plan = DeviceHierPlan(
                intra=_rank_plan(views, "intra", meta["plans"]["intra"],
                                 rank),
                inter=_rank_plan(views, "inter", meta["plans"]["inter"],
                                 rank))
        self.wd = WorkerData(
            x=jnp.asarray(views["x"][rank]),
            labels=jnp.asarray(views["labels"][rank]),
            train_mask=jnp.asarray(views["train_mask"][rank]),
            eval_mask=jnp.asarray(views["eval_mask"][rank]),
            owned_mask=jnp.asarray(views["owned_mask"][rank]),
            coo_src=jnp.asarray(views["coo_src"][rank], jnp.int32),
            coo_dst=jnp.asarray(views["coo_dst"][rank], jnp.int32),
            coo_w=jnp.asarray(views["coo_w"][rank]),
            plan=plan, hier_plan=hier_plan,
            ell=_rank_ell(views, "ell", meta["ell_ks"], rank),
            ell_t=_rank_ell(views, "ellt", meta["ellt_ks"], rank))
        jax.block_until_ready(self.wd.x)
        self.rss_after_slices = rss_bytes()

        self.params = M.init_params(jax.random.PRNGKey(spec.exec.seed),
                                    self.cfg)
        self.opt_state = adamw_init(self.params)
        self.epoch = 0
        dims = self.cfg.dims()[: self.cfg.num_layers]
        self.cache = (self.schedule.init_cache(self.wd, dims, lead=())
                      if self.schedule.uses_cache else None)
        ck = meta.get("ckpt")
        self.ckpt_every = int(ck["every"]) if ck else 0
        self.ckpt_mgr = (CheckpointManager(
            Path(ck["dir"]) / f"rank{rank}", keep=int(ck.get("keep", 3)))
            if ck else None)
        wire_rows = meta["wire_rows"]
        self._progs: Dict[str, List[_MpLayerProgram]] = {}
        for tag, sched in (("t", self.schedule), ("e", self.eval_schedule)):
            progs = []
            for l in range(self.cfg.num_layers):
                execs = [
                    _StageExec(self.mb, f"{tag}.L{l}.{stage.level}", stage,
                               sched.topo(stage), rank, nprocs,
                               wire_rows[stage.level], dims[l])
                    for stage in sched.stages]
                progs.append(_MpLayerProgram(
                    sched, self.wd, self.dc.agg_backend, execs))
            self._progs[tag] = progs

    # -- collectives outside autodiff --------------------------------------

    def _allreduce(self, op: str, vec: np.ndarray) -> np.ndarray:
        """Sum ``vec`` over all ranks, accumulating in rank order so every
        rank computes the bitwise-identical result (no broadcast needed)."""
        v = np.ascontiguousarray(vec, dtype=np.float32)
        for d in range(self.nprocs):
            self.mb.post(op, d, v)
        out = np.zeros_like(v)
        for s in range(self.nprocs):
            out += self.mb.collect(op, s).view(np.float32)
        self.mb.complete(op)
        return out

    # -- forward/step -------------------------------------------------------

    def _forward(self, params, prop_mask, key, train: bool, tag: str,
                 cache, epoch: Optional[int]):
        progs = self._progs[tag]
        new_cache: List[Tuple[jax.Array, ...]] = []

        def agg_fn(l: int, h: jax.Array) -> jax.Array:
            kq = jax.random.fold_in(key, 7919 + l) if key is not None else None
            entry = cache[l] if cache is not None else None
            inflight = progs[l].issue(h, kq, cache_entry=entry, epoch=epoch)
            local = _local_aggregate(h, self.wd, self.dc.agg_backend)
            agg, ne = progs[l].finalize(local, inflight)
            new_cache.append(ne)
            return agg

        kd = (jax.random.fold_in(key, 104729) if key is not None
              else jax.random.PRNGKey(0))
        logits = M.forward(params, self.cfg, self.wd.x, self.wd.labels,
                           prop_mask, agg_fn, train=train, dropout_key=kd)
        return logits, new_cache

    def _maybe_chaos(self) -> None:
        """Fire a pending env-injected fault (see ``_chaos_from_env``)."""
        if self._chaos is None or self.epoch != self._chaos["epoch"]:
            return
        if self._chaos["fault"] == "kill":
            os._exit(137)  # simulated crash: no cleanup, no reply
        if self._chaos["fault"] == "stall":
            # Simulated hang: sleep without touching the mailbox, so this
            # rank's heartbeat freezes while the process stays alive.
            time.sleep(_CHAOS_STALL_S)

    def train_epoch(self) -> dict:
        self._maybe_chaos()
        t0 = time.perf_counter()
        wait0, bytes0 = self.mb.wait_s, self.mb.bytes_written
        epoch = self.epoch
        key = jax.random.PRNGKey(1000003 + epoch)
        kw = jax.random.fold_in(key, self.rank)
        kp = jax.random.fold_in(kw, 1)
        prop_mask, loss_mask = M.lp_masks(kp, self.wd.train_mask,
                                          self.cfg.lp_rate)
        if not self.cfg.label_prop:
            prop_mask = jnp.zeros_like(prop_mask)
            loss_mask = self.wd.train_mask

        # The global loss denominator before the backward pass, so local
        # cotangents match the in-process psum'd-mean seeding exactly.
        cnt_local = float(jnp.sum(loss_mask.astype(jnp.float32)))
        gcnt = float(self._allreduce("t.cnt",
                                     np.array([cnt_local], np.float32))[0])
        denom = max(gcnt, 1.0)
        cache_out: List = []

        def loss_fn(p):
            logits, nc = self._forward(p, prop_mask, kw, True, "t",
                                       self.cache, epoch)
            cache_out.extend(nc)
            ls, correct, cnt = M.loss_and_metrics(logits, self.wd.labels,
                                                  loss_mask)
            return ls / denom, (ls, correct, cnt)

        (_, (ls, correct, cnt)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(self.params)

        flat, unravel = ravel_pytree(grads)
        vec = np.concatenate([
            np.asarray(flat, np.float32),
            np.array([float(ls), float(correct), float(cnt)], np.float32)])
        gsum = self._allreduce("t.grads", vec)
        grads = unravel(jnp.asarray(gsum[:-3]))
        gls, gcorrect, gcnt2 = (float(gsum[-3]), float(gsum[-2]),
                                float(gsum[-1]))
        self.params, self.opt_state = adamw_update(
            grads, self.opt_state, self.params, self.dc.lr)
        if self.schedule.uses_cache:
            self.cache = cache_out
        self.epoch += 1
        jax.block_until_ready(self.params)
        self.mb.heartbeat()  # the optimizer tail has no mailbox ops
        if (self.ckpt_mgr is not None and self.ckpt_every
                and self.epoch % self.ckpt_every == 0):
            self.ckpt_mgr.save(self._ckpt_state(), step=self.epoch,
                               meta={"epoch": self.epoch, "rank": self.rank})
            self.mb.heartbeat()
        return {"loss": gls / max(gcnt2, 1.0),
                "train_acc": gcorrect / max(gcnt2, 1.0),
                "epoch": self.epoch,
                "epoch_s": time.perf_counter() - t0,
                "wait_s": self.mb.wait_s - wait0,
                "wire_bytes": self.mb.bytes_written - bytes0}

    # -- checkpoint/restore -------------------------------------------------

    def _ckpt_state(self) -> dict:
        """The resumable pytree: params, opt state and (delayed-comm
        schedules) the per-stage halo cache. All per-epoch RNG derives
        from the epoch number and the gradient all-reduce accumulates in
        rank order on every rank, so restoring this at epoch E reproduces
        the uninterrupted trajectory bit-for-bit from E on."""
        state = {"params": self.params, "opt_state": self.opt_state}
        if self.schedule.uses_cache:
            state["cache"] = self.cache
        return state

    def restore(self, step: Optional[int]) -> dict:
        """Wind back to checkpoint ``step`` (or reinit from scratch when
        None / unconfigured) and clear the per-op mailbox counts — the
        worker half of the parent's recovery protocol, whose
        ``reset_counts`` zeroed the shared words while the fleet was
        quiesced."""
        self.mb.reset_local()
        if self.ckpt_mgr is not None and step is not None:
            template = self._ckpt_state()
            state, manifest = restore_train_state(
                self.ckpt_mgr.path_for(step), template)
            self.params = state["params"]
            self.opt_state = state["opt_state"]
            if self.schedule.uses_cache:
                self.cache = state["cache"]
            self.epoch = int(manifest.get("meta", {}).get("epoch", step))
        else:
            self.params = M.init_params(
                jax.random.PRNGKey(self.spec.exec.seed), self.cfg)
            self.opt_state = adamw_init(self.params)
            if self.schedule.uses_cache:
                dims = self.cfg.dims()[: self.cfg.num_layers]
                self.cache = self.schedule.init_cache(self.wd, dims, lead=())
            self.epoch = 0
        return {"epoch": self.epoch}

    def evaluate(self) -> dict:
        prop = (self.wd.train_mask if self.cfg.label_prop
                else jnp.zeros_like(self.wd.train_mask))
        logits, _ = self._forward(self.params, prop, jax.random.PRNGKey(0),
                                  False, "e", None, None)
        _, correct, cnt = M.loss_and_metrics(logits, self.wd.labels,
                                             self.wd.eval_mask)
        g = self._allreduce("e.metrics", np.array(
            [float(correct), float(cnt)], np.float32))
        return {"eval_acc": float(g[0]) / max(float(g[1]), 1.0)}

    def summary(self) -> dict:
        return {"rank": self.rank,
                "platform": jax.default_backend(),
                "rss_before_attach": self.rss_before_attach,
                "rss_after_attach": self.rss_after_attach,
                "rss_after_slices": self.rss_after_slices,
                "rss_now": rss_bytes(),
                "wait_s": self.mb.wait_s,
                "wire_bytes": self.mb.bytes_written}

    def close(self) -> None:
        self.mb.close()
        self.arena.close()


def _safe_send(conn, msg: dict) -> bool:
    try:
        conn.send(msg)
        return True
    except (OSError, ValueError, BrokenPipeError):
        return False  # parent gone; caller unwinds


def _worker_entry(rank: int, nprocs: int, manifest: dict, conn,
                  generation: int = 0) -> None:
    """Spawned-process entry: pin, attach the shared store, serve commands.

    Command exceptions are classified (``_transport_kind``) instead of
    killing the worker: a RECOVER flag means the parent is running fault
    recovery — reply ``{"status": "recover"}`` and stay in the loop to
    await the restore command; a real error or a transport timeout is
    reported and the worker *stays alive* so the supervisor decides
    (respawn via kill, or abort by closing the pipe). Only an abort flag
    or a lost parent ends the loop.
    """
    worker = None
    try:
        _pin(rank, nprocs)
        worker = _RankWorker(rank, nprocs, manifest, generation=generation)
        conn.send({"status": "ok", **worker.summary()})
        while True:
            try:
                msg = conn.recv()
            except (EOFError, OSError):
                break
            cmd = msg.get("cmd")
            try:
                if cmd == "stop":
                    break
                if cmd == "epoch":
                    rep = {"status": "ok", **worker.train_epoch()}
                elif cmd == "eval":
                    rep = {"status": "ok", **worker.evaluate()}
                elif cmd == "summary":
                    rep = {"status": "ok", **worker.summary()}
                elif cmd == "restore":
                    rep = {"status": "ok", **worker.restore(msg.get("step"))}
                else:
                    _safe_send(conn, {"status": "error",
                                      "error": f"unknown command {cmd!r}"})
                    break
                if not _safe_send(conn, rep):
                    break
            except Exception as e:  # noqa: BLE001 — classify, don't die
                kind = _transport_kind(e)
                if kind == "recover":
                    if not _safe_send(conn, {"status": "recover"}):
                        break
                    continue
                detail = (f"{type(e).__name__}: {e}" if kind else
                          f"{type(e).__name__}: {e}\n"
                          f"{traceback.format_exc()}")
                if not _safe_send(conn, {"status": "error", "error": detail}):
                    break
                if kind == "abort":
                    break
    except Exception as e:  # noqa: BLE001 — report, don't hang the parent
        _safe_send(conn, {"status": "error",
                          "error": f"{type(e).__name__}: {e}\n"
                                   f"{traceback.format_exc()}"})
    finally:
        if worker is not None:
            worker.close()
        try:
            conn.close()
        except OSError:
            pass


# --------------------------------------------------------------------------
# Parent runtime
# --------------------------------------------------------------------------


def _add_ell(arrays: Dict[str, np.ndarray], prefix: str, stacked
             ) -> List[int]:
    ks = []
    for i, (k, rows, idx, w) in enumerate(stacked):
        arrays[f"{prefix}.{i}.rows"] = rows
        arrays[f"{prefix}.{i}.idx"] = idx
        arrays[f"{prefix}.{i}.w"] = w
        ks.append(int(k))
    return ks


def _add_plan(arrays: Dict[str, np.ndarray], prefix: str, hp,
              max_owned: int) -> dict:
    from repro.core.exchange import host_recv_bucketed
    for f in _PLAN_FIELDS:
        arrays[f"plan.{prefix}.{f}"] = getattr(hp, f)
    fwd, rev = host_recv_bucketed(hp, max_owned)
    return {"rell_ks": _add_ell(arrays, f"plan.{prefix}.rell", fwd),
            "rellt_ks": _add_ell(arrays, f"plan.{prefix}.rellt", rev)}


def _arena_arrays(hwd) -> Tuple[Dict[str, np.ndarray], dict]:
    """(shared-store array dict, manifest meta) from a HostWorkerData."""
    arrays: Dict[str, np.ndarray] = {
        "x": hwd.x, "labels": hwd.labels, "train_mask": hwd.train_mask,
        "eval_mask": hwd.eval_mask, "owned_mask": hwd.owned_mask,
        "coo_src": hwd.coo_src, "coo_dst": hwd.coo_dst, "coo_w": hwd.coo_w,
    }
    meta: dict = {
        "ell_ks": _add_ell(arrays, "ell", hwd.ell_stacked),
        "ellt_ks": _add_ell(arrays, "ellt", hwd.ell_t_stacked),
        "plans": {}, "max_owned": int(hwd.max_owned),
    }
    if hwd.hier_plan is not None:
        meta["plans"]["intra"] = _add_plan(arrays, "intra",
                                           hwd.hier_plan.intra,
                                           hwd.max_owned)
        meta["plans"]["inter"] = _add_plan(arrays, "inter",
                                           hwd.hier_plan.inter,
                                           hwd.max_owned)
        meta["wire_rows"] = {
            "intra": int(hwd.hier_plan.intra.send_gather_idx.shape[-1]),
            "inter": int(hwd.hier_plan.inter.send_gather_idx.shape[-1])}
    else:
        meta["plans"]["flat"] = _add_plan(arrays, "flat", hwd.plan,
                                          hwd.max_owned)
        meta["wire_rows"] = {
            "flat": int(hwd.plan.send_gather_idx.shape[-1])}
    return arrays, meta


class _WorkerFailure(Exception):
    """Internal detection signal: ranks failed (dead / hung / failing)
    while the parent waited on ``pending`` ranks' replies."""

    def __init__(self, ranks: Sequence[int], kind: str,
                 pending: Sequence[int] = (), detect_s: float = 0.0,
                 errors: Optional[Dict[int, str]] = None):
        self.ranks = sorted(set(ranks))
        self.kind = kind
        self.pending = sorted(set(pending) - set(ranks))
        self.detect_s = detect_s
        self.errors = errors or {}
        super().__init__(f"ranks {self.ranks} {kind}")


class MultiprocRuntime:
    """P real processes over one shared graph store — the trainer-shaped
    driver behind ``ExecSpec.mode="multiproc"``, with a fault-tolerant
    supervisor.

    Lazy: the store is published and the workers spawn on the first
    train/eval command, so spec-level accounting (:meth:`dry_plan`) costs
    no processes.

    Supervision: while waiting on a command's replies the parent
    distinguishes a **dead** rank (exitcode / hung-up pipe), a **hung**
    rank (its heartbeat word frozen past ``exec.heartbeat_s`` while the
    process is alive) and a **failing** rank (an error-status reply). On
    any of these it runs the recovery protocol — flip the mailbox control
    word to RECOVER so blocked survivors unwind to their command loop,
    drain their in-flight replies, kill and respawn the lost ranks against
    the *existing* segments (O(respawn), nothing republished), zero the
    wire counters, restore every rank from the newest checkpoint step all
    ranks hold (:meth:`configure_ckpt`; from-scratch reinit when none) and
    retry the command. After ``exec.max_restarts`` recoveries the runtime
    degrades to a clean abort: survivors unblocked via the abort flag,
    fleet terminated, both segments unlinked, the latest checkpoints left
    on disk, and ``RuntimeError`` raised. Each recovery is appended to
    ``recovery_events`` (kind, ranks, detection latency, restore step) —
    the chaos harness's report source.
    """

    def __init__(self, spec, hwd):
        self.spec = spec
        self.nprocs = spec.exec.nprocs or spec.partition.nparts
        if self.nprocs != spec.partition.nparts:
            raise ValueError(
                f"multiproc runs one process per partition: nprocs "
                f"{self.nprocs} != partition.nparts {spec.partition.nparts}")
        self.dc = spec.schedule.to_dist_config(spec.partition,
                                               lr=spec.exec.lr)
        self.schedule = self.dc.schedule()
        self.cfg = spec.model.to_gcn_config(spec.graph, spec.schedule)
        self.epoch = 0
        self.epoch_stats: List[dict] = []
        self.token: Optional[str] = None
        self._arrays, self._meta = _arena_arrays(hwd)
        nparams = int(ravel_pytree(M.init_params(
            jax.random.PRNGKey(spec.exec.seed), self.cfg))[0].size)
        feat_dims = self.cfg.dims()[: self.cfg.num_layers]
        self._eval_schedule = self.dc.sync_fp32().schedule()
        self._op_table = build_op_table(
            self.schedule, self._eval_schedule, self.nprocs,
            self.cfg.num_layers, feat_dims, self._meta["wire_rows"],
            nparams)
        self._meta.update(nparams=nparams, feat_dims=list(feat_dims))
        self._started = False
        self._procs: List = []
        self._conns: List = []
        self._arena: Optional[ShmArena] = None
        self._mb: Optional[Mailboxes] = None
        self.ready_stats: List[dict] = []
        # Supervision state
        self.restarts = 0
        self.recovery_events: List[dict] = []
        self._recovering = False
        self._generation = 0
        self._ckpt: Optional[dict] = None
        self._manifest: Optional[dict] = None
        self._ctx = None
        self._signals_installed = False
        # Ranks that have completed a supervised command since (re)spawn:
        # only they get the tight heartbeat_s hang deadline (cold ranks
        # are still compiling; see _COLD_GRACE_S).
        self._warm_ranks: set = set()

    # -- checkpoint configuration ------------------------------------------

    def configure_ckpt(self, directory, every: int = 1, keep: int = 3
                       ) -> None:
        """Point the fleet at a checkpoint directory (per-rank subdirs
        ``rank{r}/``) with snapshot period ``every`` epochs. Must run
        before the first command spawns the workers — the directory rides
        in the spawn manifest."""
        if self._started:
            raise RuntimeError(
                "configure_ckpt must be called before the fleet starts")
        self._ckpt = {"dir": str(directory), "every": int(every),
                      "keep": int(keep)}

    def _rank_managers(self) -> Dict[int, CheckpointManager]:
        assert self._ckpt is not None
        return {r: CheckpointManager(Path(self._ckpt["dir"]) / f"rank{r}",
                                     keep=self._ckpt["keep"])
                for r in range(self.nprocs)}

    def _latest_common_step(self) -> Optional[int]:
        if self._ckpt is None:
            return None
        return latest_common_step(self._rank_managers())

    def restore_from_ckpt(self) -> int:
        """Explicit resume: restore every rank from the newest step all
        ranks hold a valid checkpoint for. Aborts cleanly (fleet down,
        segments unlinked) when no common valid step exists."""
        if self._ckpt is None:
            raise RuntimeError("restore_from_ckpt needs configure_ckpt "
                               "first (no checkpoint directory)")
        self._ensure_started()
        step = self._latest_common_step()
        if step is None:
            self._abort("resume requested but no checkpoint step is valid "
                        f"on every rank under {self._ckpt['dir']}")
        try:
            self._send({"cmd": "restore", "step": step}, "restore",
                       range(self.nprocs))
            reps = self._gather(_PARENT_WAIT_S, "restore")
        except _WorkerFailure as f:
            self._abort(f"restore failed: {f}")
        self.epoch = int(reps[0]["epoch"])
        return step

    # -- lifecycle ---------------------------------------------------------

    def _spawn_rank(self, r: int) -> None:
        """Spawn (or respawn) one rank against the already-published
        segments, with the thread env partitioned across ranks."""
        threads = max(1, (os.cpu_count() or 1) // self.nprocs)
        pinned = {**{k: str(threads) for k in _THREAD_ENV},
                  "JAX_PLATFORMS": _FLEET_PLATFORM}
        saved = {k: os.environ.get(k) for k in pinned}
        os.environ.update(pinned)
        try:
            parent_conn, child_conn = self._ctx.Pipe()
            p = self._ctx.Process(
                target=_worker_entry,
                args=(r, self.nprocs, self._manifest, child_conn,
                      self._generation),
                daemon=True)
            p.start()
            child_conn.close()
            self._procs[r] = p
            self._conns[r] = parent_conn
            self._warm_ranks.discard(r)
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    def _install_signal_cleanup(self) -> None:
        """SIGINT/SIGTERM tear the fleet down and unlink both segments
        before the default disposition runs (atexit alone never fires on
        SIGTERM). Chained to any previously-installed handler."""
        if self._signals_installed:
            return
        for sig in (signal.SIGINT, signal.SIGTERM):
            prev = signal.getsignal(sig)

            def _handler(signum, frame, prev=prev):
                self.close(force=True)
                if callable(prev) and prev not in (signal.SIG_IGN,
                                                   signal.SIG_DFL):
                    prev(signum, frame)
                else:
                    signal.signal(signum, signal.SIG_DFL)
                    os.kill(os.getpid(), signum)

            try:
                signal.signal(sig, _handler)
            except ValueError:
                return  # not the main thread; atexit still covers segments
        self._signals_installed = True

    def _ensure_started(self) -> None:
        if self._started:
            return
        self.token = run_token()
        self._arena, self._mb, frag = publish_store(
            self.token, self._arrays, self._op_table, nprocs=self.nprocs)
        meta = dict(self._meta)
        if self._ckpt is not None:
            meta["ckpt"] = self._ckpt
        self._manifest = {"spec": self.spec.to_dict(), "meta": meta, **frag}
        self._ctx = mp.get_context("spawn")
        self._procs = [None] * self.nprocs
        self._conns = [None] * self.nprocs
        for r in range(self.nprocs):
            self._spawn_rank(r)
        self._started = True
        self._install_signal_cleanup()
        try:
            reps = self._gather(_PARENT_WAIT_S, "startup")
        except _WorkerFailure as f:
            self._abort(f"startup failed: {f}"
                        + "".join(f"\n  rank {r}: {e}"
                                  for r, e in f.errors.items()))
        self.ready_stats = [reps[r] for r in range(self.nprocs)]

    def _abort(self, msg: str) -> None:
        if self._mb is not None:
            self._mb.abort()
        self.close(force=True)
        raise RuntimeError(f"multiproc run aborted: {msg}")

    # -- detection + recovery ----------------------------------------------

    def _gather(self, timeout: float, what: str,
                ranks: Optional[Sequence[int]] = None, hb_s: float = 0.0,
                ok_status: Tuple[str, ...] = ("ok",)) -> Dict[int, dict]:
        """Collect one reply per rank; raise :class:`_WorkerFailure` the
        moment any awaited rank proves dead, hung (heartbeat frozen past
        ``hb_s``; 0 disables) or failing (reply outside ``ok_status``)."""
        ranks = list(range(self.nprocs)) if ranks is None else list(ranks)
        t0 = time.monotonic()
        deadline = t0 + timeout
        replies: Dict[int, dict] = {}
        pending = set(ranks)
        hb_last: Dict[int, Tuple[int, float]] = {}
        if hb_s > 0 and self._mb is not None:
            hbs = self._mb.heartbeats()
            hb_last = {r: (hbs[r], t0) for r in pending if r < len(hbs)}

        def fail(rs, kind):
            raise _WorkerFailure(
                rs, kind, pending=pending, detect_s=time.monotonic() - t0)

        while pending:
            for r in sorted(pending):
                try:
                    if self._conns[r] is not None and self._conns[r].poll(0.05):
                        replies[r] = self._conns[r].recv()
                        pending.discard(r)
                except (EOFError, OSError):
                    fail([r], "dead")
            dead = [r for r in pending
                    if self._procs[r] is None
                    or not self._procs[r].is_alive()]
            if dead:
                fail(dead, "dead")
            if hb_last:
                now = time.monotonic()
                hbs = self._mb.heartbeats()
                hung = []
                for r in sorted(pending & set(hb_last)):
                    v, t = hb_last[r]
                    limit = (hb_s if r in self._warm_ranks
                             else max(hb_s, _COLD_GRACE_S))
                    if hbs[r] != v:
                        hb_last[r] = (hbs[r], now)
                    elif now - t > limit:
                        hung.append(r)
                if hung:
                    fail(hung, "hung")
            if time.monotonic() > deadline:
                fail(sorted(pending), "hung")
        bad = [r for r in ranks if replies[r].get("status") not in ok_status]
        if bad:
            raise _WorkerFailure(
                bad, "failing", detect_s=time.monotonic() - t0,
                errors={r: str(replies[r].get("error", "no detail"))
                        for r in bad})
        return replies

    def _send(self, msg: dict, what: str, ranks: Sequence[int]) -> None:
        sent: List[int] = []
        for r in ranks:
            try:
                self._conns[r].send(msg)
            except (BrokenPipeError, OSError, AttributeError):
                raise _WorkerFailure([r], "dead", pending=sent)
            sent.append(r)

    def _command(self, msg: dict, what: str,
                 timeout: float = _PARENT_WAIT_S,
                 supervised: bool = False) -> List[dict]:
        """Send ``msg`` to every rank and gather replies; with
        ``supervised`` any detected failure runs the recovery protocol and
        the command retries from the restored state."""
        self._ensure_started()
        hb_s = float(self.spec.exec.heartbeat_s) if supervised else 0.0
        while True:
            try:
                self._send(msg, what, range(self.nprocs))
                reps = self._gather(timeout, what, hb_s=hb_s)
                self._warm_ranks.update(range(self.nprocs))
                return [reps[r] for r in range(self.nprocs)]
            except _WorkerFailure as f:
                if not supervised:
                    self._abort(
                        f"{f} during {what}"
                        + "".join(f"\n  rank {r}: {e}"
                                  for r, e in f.errors.items()))
                self._handle_failure(f, what)

    def _handle_failure(self, f: _WorkerFailure, what: str) -> None:
        """The recovery protocol (see class docstring). Raises via
        :meth:`_abort` once the restart budget is exhausted or when the
        recovery itself trips over another failure."""
        if self._recovering:
            self._abort(f"nested failure during recovery: {f}")
        if self._ckpt is None:
            # No checkpointing -> nothing to resume from. Respawning would
            # silently restart training at epoch 0, so keep the original
            # fail-fast contract: abort the fleet, unlink every segment.
            self._abort(
                f"ranks {f.ranks} {f.kind} during {what} and no checkpoint "
                f"directory is configured (pass ckpt_dir / --ckpt-dir to "
                f"enable recovery)"
                + "".join(f"\n  rank {r}: {e}"
                          for r, e in f.errors.items()))
        self.restarts += 1
        event = {"epoch": self.epoch, "during": what, "ranks": f.ranks,
                 "kind": f.kind, "detect_s": round(f.detect_s, 3),
                 "restarts": self.restarts}
        if self.restarts > self.spec.exec.max_restarts:
            self.recovery_events.append({**event, "action": "abort"})
            self._abort(
                f"ranks {f.ranks} {f.kind} during {what}; restart budget "
                f"exhausted (max_restarts={self.spec.exec.max_restarts})"
                + "".join(f"\n  rank {r}: {e}"
                          for r, e in f.errors.items()))
        self._recovering = True
        try:
            failed = set(f.ranks)
            # 1. Quiesce: survivors blocked on the wire unwind via the
            #    RECOVER control word and reply; drain until every still-
            #    pending survivor has reported (ok / recover / error) or
            #    proven itself failed too.
            self._mb.recover()
            drain = set(f.pending) - failed
            while drain:
                try:
                    self._gather(_RECOVER_DRAIN_S, "recovery drain",
                                 ranks=sorted(drain),
                                 ok_status=("ok", "recover", "error"))
                    drain = set()
                except _WorkerFailure as f2:
                    failed |= set(f2.ranks)
                    drain = set(f2.pending) - failed
            # 2. Reap the failed ranks (kill is idempotent on the dead).
            for r in sorted(failed):
                p = self._procs[r]
                if p is not None:
                    p.kill()
                    p.join(timeout=10.0)
                if self._conns[r] is not None:
                    try:
                        self._conns[r].close()
                    except OSError:
                        pass
            # 3. The wire is quiet: zero every seq/heartbeat/control word.
            self._mb.reset_counts()
            # 4. Respawn against the existing segments (no republish).
            self._generation += 1
            for r in sorted(failed):
                self._spawn_rank(r)
            self._gather(_PARENT_WAIT_S, "respawn startup",
                         ranks=sorted(failed))
            # 5. Everyone restores the newest common valid checkpoint
            #    (None -> from-scratch reinit at epoch 0).
            step = self._latest_common_step()
            self._send({"cmd": "restore", "step": step}, "restore",
                       range(self.nprocs))
            reps = self._gather(_PARENT_WAIT_S, "restore")
            self.epoch = int(reps[0]["epoch"])
            self.recovery_events.append({
                **event, "action": "respawn", "respawned": sorted(failed),
                "restore_step": step, "resume_epoch": self.epoch})
        except _WorkerFailure as f2:
            self._abort(f"recovery from ({f}) failed: {f2}")
        finally:
            self._recovering = False

    def close(self, force: bool = False) -> None:
        if self._conns and not force:
            for c in self._conns:
                if c is None:
                    continue
                try:
                    c.send({"cmd": "stop"})
                except (BrokenPipeError, OSError, ValueError):
                    pass
        for p in self._procs:
            if p is not None:
                p.join(timeout=2.0 if force else 15.0)
        for p in self._procs:
            if p is not None and p.is_alive():
                p.terminate()
                p.join(timeout=5.0)
        for c in self._conns:
            if c is None:
                continue
            try:
                c.close()
            except OSError:
                pass
        self._procs, self._conns = [], []
        for seg in (self._mb, self._arena):
            if seg is not None:
                seg.close()
        self._mb = self._arena = None
        self._started = False

    def __enter__(self) -> "MultiprocRuntime":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- trainer-shaped interface -----------------------------------------

    def train_epoch(self) -> Dict[str, float]:
        reps = self._command({"cmd": "epoch"}, "train epoch",
                             supervised=True)
        # The workers own the epoch counter (a recovery mid-command winds
        # it back to the restored step); the parent just mirrors it.
        self.epoch = int(reps[0]["epoch"])
        self.epoch_stats.append({
            "epoch": self.epoch,
            "epoch_s": max(r["epoch_s"] for r in reps),
            "wait_s": [r["wait_s"] for r in reps],
            "wire_bytes": [r["wire_bytes"] for r in reps]})
        return {"loss": float(reps[0]["loss"]),
                "train_acc": float(reps[0]["train_acc"]),
                "epoch_s": float(self.epoch_stats[-1]["epoch_s"])}

    def evaluate(self) -> float:
        reps = self._command({"cmd": "eval"}, "evaluate", supervised=True)
        return float(reps[0]["eval_acc"])

    def fit(self, epochs: int, log_every: int = 0) -> List[Dict]:
        history = []
        # while (not for-range): a mid-run recovery winds self.epoch back
        # to the restored checkpoint, and the re-trained epochs must still
        # land the run at `epochs` total.
        while self.epoch < epochs:
            m = self.train_epoch()
            if log_every and (self.epoch % log_every == 0
                              or self.epoch == epochs):
                m["eval_acc"] = self.evaluate()
                m["epoch"] = self.epoch
                history.append(m)
        return history

    def summary(self) -> dict:
        out = {"mode": "multiproc", "nprocs": self.nprocs,
               "platform": _FLEET_PLATFORM,
               "token": self.token, "parent_rss": rss_bytes(),
               "epoch_stats": self.epoch_stats, **self.dry_plan()}
        if self._started:
            out["ranks"] = self._command({"cmd": "summary"}, "summary")
        return out

    def dry_plan(self) -> dict:
        """Store/mailbox accounting without publishing segments or
        spawning processes (the matrix dry-run hook for multiproc specs,
        standing in for ``.lower()``)."""
        table, total = ShmArena.layout(self._arrays)
        layout = plan_mailbox(self._op_table, nprocs=self.nprocs)
        return {"store_bytes": int(total), "store_arrays": len(table),
                "mailbox_bytes": int(layout["bytes"]),
                "mailbox_ops": len(self._op_table)}

    def lower_step(self, key=None):
        raise NotImplementedError(
            "mode='multiproc' executes eagerly across processes; there is "
            "no single lowered module (HLO rules skip this backend)")
