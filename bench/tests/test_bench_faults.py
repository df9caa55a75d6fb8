"""A run whose timed path is broken underneath must read not correct: the
chip checks are skipped, the rest of the run is the command's own. Each
fault a cell can have is planted once: a step that leaves its state
unchanged, half of the batch left out with the rest scaled to keep its
weight, and an answer altered where it is produced. (Every cell runs on
one chip, so no exchange between chips can be left out.)"""
import math

import jax.numpy as jnp
import pytest

from repro.core import CholFactor

from bench.tests import tiny

_update = CholFactor.update
_guarded = CholFactor.downdate_guarded


def _half(V):
    return V[..., :max(V.shape[-1] // 2, 1)] * math.sqrt(2.0)


def dense_unchanged(mp):
    mp.setattr(CholFactor, "update", lambda self, V: self)
    mp.setattr(CholFactor, "downdate_guarded",
               lambda self, V: (self, jnp.isfinite(jnp.sum(V))))


def dense_half_batch(mp):
    mp.setattr(CholFactor, "update", lambda self, V: _update(self, _half(V)))
    mp.setattr(CholFactor, "downdate_guarded",
               lambda self, V: _guarded(self, _half(V)))


def dense_altered(mp):
    def update(self, V):
        out = _update(self, V)
        bump = 1e-2 * jnp.max(jnp.abs(out.data))
        return out.replace(data=out.data.at[..., 0, -1].add(bump))

    mp.setattr(CholFactor, "update", update)


@pytest.mark.parametrize("cell", ["gp.n5000.k16", "gp.n5000.k1"])
@pytest.mark.parametrize("plant", [dense_unchanged, dense_half_batch,
                                   dense_altered])
def test_dense_fault_is_caught(monkeypatch, plant, cell):
    plant(monkeypatch)
    line, _, _ = tiny.run(cell)
    assert line["correct"] is False, line["checks"]
