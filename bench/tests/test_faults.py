"""A whole run on the CPU with the timed path broken underneath: ``correct``
must come out false for each fault the single-worker cell can have, and
true for the unbroken program. The chip check is skipped; everything else
is the run as the cell makes it, at a small graph and the cell's limits.

The exchange between chips cannot be left out on this cell: one worker
holds the whole graph and sends nothing.
"""

import jax.numpy as jnp
import pytest

NODES = 2048


def _run(setup):
    from bench import run as R
    return R.run(setup, 2**31 + 7, 0.5, False, require_tpu=False)


def _unchanged_state(grads, state, params, lr, **kw):
    return params, state


def _half_batch(real):
    def loss_and_metrics(logits, labels, loss_mask):
        keep = jnp.arange(loss_mask.shape[0]) % 2 == 0
        return real(logits, labels, loss_mask & keep)
    return loss_and_metrics


def _altered_rows(real):
    def bucket_matvec(x, b, kernel):
        out = real(x, b, kernel)
        lost = (jnp.arange(out.shape[0]) % 16 == 0)[:, None]
        return jnp.where(lost, 0.0, out)
    return bucket_matvec


def test_sound_run_is_correct(tiny_setup):
    result = _run(tiny_setup(NODES))
    assert result["correct"], result["checks"]


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch", "altered_rows"])
def test_fault_is_caught(tiny_setup, monkeypatch, fault):
    from repro.core import model, trainer
    from repro.kernels import seg_aggregate
    if fault == "unchanged_state":
        monkeypatch.setattr(trainer, "adamw_update", _unchanged_state)
    elif fault == "half_batch":
        monkeypatch.setattr(model, "loss_and_metrics",
                            _half_batch(model.loss_and_metrics))
    else:
        monkeypatch.setattr(seg_aggregate, "_bucket_matvec",
                            _altered_rows(seg_aggregate._bucket_matvec))
    result = _run(tiny_setup(NODES))
    assert not result["correct"], result["checks"]
