import os

# Keep CPU test runs deterministic and quiet.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# A small pool of virtual host devices so shard_map parity tests (e.g.
# delayed-comm vmap-vs-shard_map in test_exchange_schedule.py) can build
# real worker meshes in-process. Must happen before the jax backend
# initializes; the 512-device production flag stays confined to
# launch/dryrun.py (exercised via subprocess).
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def copies_by_rule():
    """Row copies the aggregation kernel starts over a stacked bucketed
    layout, one launch per worker, counted tile by tile and column by
    column: slot chunk KC the smallest power of two at or above K (at most
    16), row tile 1024 / KC, and in each tile's column the row after the
    last nonzero weight, rounded up to a group of 8."""
    def count(ell) -> int:
        total = 0
        for b in ell.buckets:
            for w in np.asarray(b.w).reshape(-1, *b.w.shape[-2:]):
                r, k = w.shape
                tile = 1024 // min(16, 1 << (k - 1).bit_length())
                for r0 in range(0, r, tile):
                    for c in range(k):
                        rows = np.flatnonzero(w[r0:r0 + tile, c])
                        if rows.size:
                            total += -(-(int(rows[-1]) + 1) // 8) * 8
        return total
    return count
