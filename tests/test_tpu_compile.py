"""The main path's Pallas kernels compile for a TPU v5e, with no chip.

JAX describes a ``v5e:2x2`` topology and the TPU compiler, which is
installed with jaxlib, compiles each kernel for one of its chips. That
refuses what interpret mode accepts: unaligned slices, blocks the tiling
cannot hold, more VMEM than a kernel may use. Shapes are one worker's
share of the chip smoke's graph (131,072 nodes in 4 partitions).

The topology is described inside a fixture, never at import, so that
every pytest-xdist worker collects the same tests and only the worker
running this file loads the TPU library.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import repro.kernels.seg_aggregate as sa
from repro.kernels import DeviceBucketedEll, bucketed_aggregate
from repro.kernels.quant_pack import dequant_unpack, quant_pack
from repro.kernels.seg_aggregate import DeviceEllBucket

N = 36864                               # source rows of one worker
ROWS = {1: 16, 8: 1256, 64: 472}        # destination rows of its K buckets


@pytest.fixture(scope="module")
def topo(tmp_path_factory):
    from jax.experimental import topologies
    # The TPU library writes its logs under /tmp unless told otherwise.
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", str(tmp_path_factory.mktemp("tpu_logs")))
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernel(monkeypatch):
    """Force the compiled kernel: ``default_backend()`` is the CPU here, so
    the policy would pick the reference and the kernel the interpreter."""
    monkeypatch.setattr(sa, "seg_aggregate",
                        functools.partial(sa.seg_aggregate, interpret=False))


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("workers", [0, 4])
@pytest.mark.parametrize("k", [1, 8, 64])
@pytest.mark.parametrize("f", [256, 100])
def test_seg_aggregate_compiles(one_chip, f, k, workers):
    """One bucket of the smoke's layout; ``workers=4`` vmaps it over the
    four workers the one-chip path stacks (folded into one kernel call)."""
    fn = functools.partial(sa.seg_aggregate, interpret=False)
    lead = (workers,) if workers else ()
    if workers:
        fn = jax.vmap(fn)
    r = ROWS[k]
    _compile(fn, one_chip, (lead + (N, f), jnp.float32),
             (lead + (r, k), jnp.int32), (lead + (r, k), jnp.float32))


def _layout(ks, lead=()):
    return DeviceBucketedEll(tuple(
        DeviceEllBucket(
            rows=jax.ShapeDtypeStruct(lead + (ROWS[k],), jnp.int32),
            idx=jax.ShapeDtypeStruct(lead + (ROWS[k], k), jnp.int32),
            w=jax.ShapeDtypeStruct(lead + (ROWS[k], k), jnp.float32))
        for k in ks))


@pytest.mark.parametrize("workers", [0, 4])
def test_bucketed_aggregate_grad_compiles(one_chip, compiled_kernel, workers):
    """Forward and the custom VJP's reverse-graph pass both run the kernel
    under ``jax.grad``, with and without the worker vmap."""
    lead = (workers,) if workers else ()
    put = lambda t: jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), t)
    ell, ell_t = put(_layout(ROWS, lead)), put(_layout(ROWS, lead))

    def loss(x, ell, ell_t):
        agg = lambda x, e, et: bucketed_aggregate(x, e, et, use_kernel=True)
        if workers:
            agg = jax.vmap(agg)
        return (agg(x, ell, ell_t) ** 2).sum()

    x = jax.ShapeDtypeStruct(lead + (N, 256), jnp.float32, sharding=one_chip)
    text = jax.jit(jax.grad(loss)).lower(x, ell, ell_t).compile().as_text()
    # 3 buckets forward + 3 on the reverse layout in the backward.
    assert text.count('custom_call_target="tpu_custom_call"') >= 6


# GAT's attention at the published widths: 4 heads of 256 (hidden layers)
# and 6 heads of 47, padded to 128 lanes each (output layer).
@pytest.mark.parametrize("heads,dh", [(4, 256), (6, 47)])
@pytest.mark.parametrize("k", [1, 64])
def test_seg_aggregate_heads_compiles(one_chip, heads, dh, k):
    fn = functools.partial(sa.seg_aggregate, interpret=False)
    r = ROWS[k]
    _compile(fn, one_chip, ((N, heads * dh), jnp.float32), ((r, k), jnp.int32),
             ((r, k, heads), jnp.float32))


@pytest.mark.parametrize("heads,dh", [(4, 256), (6, 47)])
@pytest.mark.parametrize("k", [1, 64])
def test_seg_sddmm_compiles(one_chip, heads, dh, k):
    fn = functools.partial(sa.seg_sddmm, heads=heads, interpret=False)
    r = ROWS[k]
    text = _compile(fn, one_chip, ((r, heads * dh), jnp.float32),
                    ((N, heads * dh), jnp.float32), ((r, k), jnp.int32),
                    ((r, k), jnp.float32))
    # A trace finds a kernel by its instruction's name (bench/trace_reduce.py
    # matches by substring): this one must not read as the aggregation.
    kernels = set(re.findall(r"%(seg_[a-z]+)[.\d]* = ", text))
    assert kernels == {"seg_sddmm"}


def test_gat_attention_grad_compiles(one_chip, monkeypatch):
    """The attention op under ``jax.grad``: the weighted kernel over the
    forward and reverse layouts and the SDDMM pass, with the kernels."""
    from repro.core.layers import gat_attention
    monkeypatch.setattr(sa, "seg_sddmm",
                        functools.partial(sa.seg_sddmm, interpret=False))
    monkeypatch.setattr(sa, "seg_aggregate",
                        functools.partial(sa.seg_aggregate, interpret=False))
    put = lambda t: jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), t)
    ell, ell_t = put(_layout(ROWS)), put(_layout(ROWS))
    slot_t = put(tuple(jax.ShapeDtypeStruct((ROWS[k], k), jnp.int32) for k in ROWS))
    heads, f = 4, 1024

    def loss(wx, es, ed, ell, ell_t, slot_t):
        out = gat_attention(wx, es, ed, ell, ell_t, slot_t, use_kernel=True)
        return (out ** 2).sum()

    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shape(N, f), shape(N, heads), shape(N, heads), ell, ell_t, slot_t
    ).compile().as_text()
    # 3 buckets forward, 3 over the reverse layout, 3 SDDMM.
    assert text.count('custom_call_target="tpu_custom_call"') >= 9
    assert "seg_sddmm" in text


@pytest.mark.parametrize("bits", [2, 4, 8])
def test_quant_pack_compiles(one_chip, bits):
    """The Int2/4/8 wire pack and unpack at a halo stage's wire rows."""
    rows, feat = 4096, 256
    _compile(functools.partial(quant_pack, bits=bits, interpret=False),
             one_chip, ((rows, feat), jnp.float32), ((rows, feat), jnp.float32))
    _compile(functools.partial(dequant_unpack, bits=bits, feat=feat,
                               interpret=False), one_chip,
             ((rows, feat * bits // 32), jnp.int32),
             ((rows // 4,), jnp.float32), ((rows // 4,), jnp.float32))


def test_lane_major_roundtrip():
    """The wrapper's lane-major layout and its inverse are exact."""
    from repro.kernels.quant_pack import _lane_major
    a = np.arange(8 * 32, dtype=np.float32).reshape(8, 32)
    t = np.asarray(_lane_major(jnp.asarray(a), 16))
    assert t.shape == (16, 4, 2, 2)
    assert t[3, 1, 1, 0] == a[4 * 1 + 1, 0 * 16 + 3]
    back = t.transpose(2, 1, 3, 0).reshape(8, 32)
    np.testing.assert_array_equal(back, a)
