"""The served per-user LinUCB fleet against the plain reference.

``StreamService``/``FactorStore`` with the fused kernel (interpreted on
the CPU) serve a closed loop of Zipf-drawn users: every tick reads all
decisions through ``solve_many``, pushes one reward row per decision
(the head user overflows the coalesce width, so width flushes fire
inside the tick), forces a flush and advances the clock, which expires
the rows flushed ``WINDOW`` ticks earlier. ``repro.stream.ref`` follows
the same tick with explicit ``A_u``/``b_u``. Both are given the arms the
served path chose; every decision's UCB scores and, at the end, every
user's factor are compared.
"""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.stream import FactorStore, StreamService
from repro.stream.ref import LinUCBRef

USERS, D, K, TICKS, WINDOW = 64, 36, 8, 40, 8
DECISIONS, WIDTH, ALPHA = 32, 4, 1.0

# f32 throughout. The served factor is built by rank-1 updates and
# guarded downdates through the fused kernel, the reference refactorizes
# A_u afresh; both sit at float32 roundoff (~6e-8) amplified by A_u's
# conditioning (eigenvalues from 1 to under 20 here). Measured on this
# data: 5.9e-6 for the factor (largest entry error over largest entry,
# per user) and 5.5e-6 for the scores. The tolerances leave about 10x
# and 20x room and stay far below what a wrong window gives (one row too
# many or too few moves a factor entry by ~1e-1) or bf16 storage (~4e-3).
FACTOR_RTOL = 5e-5
# A score is theta.z plus alpha times sqrt(z^T A^-1 z); scores here are
# below 2 in size, so an absolute tolerance is a relative one.
SCORE_ATOL = 1e-4


def _features(rng, users, pool):
    """(m, D, K) candidates: outer products of a per-decision user vector
    (5 soft cluster memberships and a constant) with each pool article."""
    mu = np.random.default_rng(7).normal(size=(USERS, 5))
    x = mu[users] + 0.5 * rng.normal(size=(len(users), 5))
    x = np.exp(x) / np.exp(x).sum(-1, keepdims=True)
    x = np.concatenate([x, np.ones((len(users), 1))], -1)
    z = np.einsum("mi,kj->mijk", x, pool).reshape(len(users), D, K)
    return z.astype(np.float32)


def _pool(rng):
    y = np.exp(rng.normal(size=(K, 5)))
    y = y / y.sum(-1, keepdims=True)
    return np.concatenate([y, np.ones((K, 1))], -1)


@jax.jit
def _scores(sol, Z):
    with jax.default_matmul_precision("highest"):
        theta, S = sol[:, :, 0], sol[:, :, 1:]
        return (jnp.einsum("mdk,md->mk", Z, theta)
                + ALPHA * jnp.sqrt(jnp.sum(Z * S, axis=1)))


def _serve():
    """Run the loop. Returns the service, the reference, each tick's
    ``(tick, users, served scores, reference scores)`` and the number of
    width flushes that fired inside ticks."""
    store = FactorStore(D, capacity=USERS, ladder=(USERS,), width=WIDTH,
                        backend="fused", interpret=True, read_cols=(K + 1,))
    svc = StreamService(store, window=WINDOW)
    svc.admit_many(range(USERS))
    ref = LinUCBRef(D, window=WINDOW, alpha=ALPHA)
    rng = np.random.default_rng(2024)
    p = 1.0 / np.arange(1, USERS + 1)          # Zipf s = 1 over the users
    p /= p.sum()
    theta_true = rng.normal(size=(USERS, D)) / 6
    b = np.zeros((USERS, D))
    expiring = collections.defaultdict(list)
    out = []
    widths_seen = 0
    for t in range(TICKS):
        if t % 5 == 0:
            pool = _pool(rng)
        users = rng.choice(USERS, size=DECISIONS, p=p)
        Z = _features(rng, users, pool)
        rhs = np.concatenate([b[users][:, :, None], Z], axis=2)
        served = np.asarray(_scores(svc.solve_many(users, rhs), Z))
        want = np.stack([np.asarray(ref.scores(u, Z[i]))
                         for i, u in enumerate(users)])
        out.append((t, users, served, want))
        arms = served.argmax(1)
        for i, u in enumerate(users):
            z = Z[i, :, arms[i]]
            r = float(rng.random() < 1 / (1 + np.exp(-theta_true[u] @ z)))
            rep = svc.push(u, z)
            widths_seen += rep is not None and not rep.empty
            ref.reward(u, z, r)
            b[u] += r * z
            expiring[t + WINDOW].append((u, r * z))
        svc.flush(force=True)
        ref.flush()
        svc.tick()
        ref.advance()
        for u, rz in expiring.pop(t + 1, []):
            b[u] -= rz
    return svc, ref, out, widths_seen


@pytest.fixture(scope="module")
def served():
    return _serve()


def test_width_flushes_fire_inside_a_tick(served):
    *_, widths_seen = served
    assert widths_seen > 0


def test_every_decisions_scores_match_the_reference(served):
    _, _, out, _ = served
    worst = max(float(np.max(np.abs(s - w))) for _, _, s, w in out)
    assert worst < SCORE_ATOL, worst


def test_every_factor_matches_the_reference(served):
    svc, ref, _, _ = served
    data = np.asarray(svc.store.factor.data)
    held = 0
    for u in range(USERS):
        want = np.asarray(ref.factor(u))
        got = data[svc.store.slot(u)]
        err = np.max(np.abs(got - want)) / np.max(np.abs(want))
        assert err < FACTOR_RTOL, (u, err)
        held += not np.allclose(want, np.eye(D))
    assert held > USERS // 4     # the window holds rows of many users
