"""Correctness of the paper's pipeline: distributed pieces vs dense oracle,
plus hypothesis property tests on the system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import (kmeans as km, lanczos as lz, laplacian as lp,
                        similarity as sim, spectral)
from repro.data import synthetic


# ---------------------------------------------------------------------------
# schedule properties (the paper's load-balance claim, exactly)
# ---------------------------------------------------------------------------

@given(st.integers(1, 64), st.integers(2, 500))
@settings(max_examples=60, deadline=None)
def test_schedule_balanced_and_complete(m, n):
    """Every device gets exactly 2m+1 tiles (paper's i/n-i+1 pairing) and
    every block pair (p<=q in original order) is computed exactly once."""
    sched = sim.make_schedule(n, m)
    assert sched.table.shape == (m, 2 * m + 1, 3)
    # completeness: each unordered original-block pair exactly once
    seen = set()
    orig_of_perm = sched.perm[::sched.b] // sched.b
    for d in range(m):
        own = [d, 2 * m - 1 - d]
        for p_local, q, _ in sched.table[d]:
            op = own[p_local]
            oq = orig_of_perm[q]
            pair = (min(op, oq), max(op, oq))
            assert op <= oq
            assert pair not in seen
            seen.add(pair)
    B = 2 * m
    assert len(seen) == B * (B + 1) // 2
    # permutation is a bijection
    assert np.array_equal(np.sort(sched.perm), np.arange(sched.n_pad))


@given(st.integers(4, 60), st.integers(1, 4), st.floats(0.3, 3.0))
@settings(max_examples=25, deadline=None)
def test_similarity_matrix_properties(n, d, sigma):
    """S is symmetric, entries in [0, 1] (exp underflows to 0.0 for far
    pairs in f32), diagonal exactly 1."""
    x = jax.random.normal(jax.random.PRNGKey(n), (n, d))
    S = np.asarray(sim.dense_similarity(x, sigma))
    assert np.allclose(S, S.T, atol=1e-6)
    assert (S >= 0).all() and (S <= 1 + 1e-6).all()
    assert np.allclose(np.diag(S), 1.0, atol=1e-6)


def test_laplacian_psd_and_trivial_eigvec():
    x, _ = synthetic.blobs(60, 3, seed=1)
    S = np.asarray(sim.dense_similarity(jnp.asarray(x), 1.0))
    L = np.asarray(lp.dense_lsym(jnp.asarray(S)))
    w = np.linalg.eigvalsh(L)
    assert w.min() > -1e-4, "L_sym must be PSD"
    assert w.max() < 2 + 1e-4, "L_sym spectrum lies in [0, 2]"
    d = S.sum(1)
    v = np.sqrt(d) / np.linalg.norm(np.sqrt(d))
    assert np.linalg.norm(L @ v) < 1e-4, "D^{1/2}1 is the 0-eigenvector"


# ---------------------------------------------------------------------------
# Lanczos
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,k", [(50, 3), (120, 5)])
def test_lanczos_matches_eigh(n, k):
    key = jax.random.PRNGKey(0)
    A = jax.random.normal(key, (n, n))
    A = (A + A.T) / 2

    state = lz.block_lanczos(lambda V: A @ V, n, min(n - 1, 40), key,
                             block_size=1)
    evals, vecs = lz.block_ritz_pairs(state)
    top = np.asarray(evals[-k:])
    want = np.linalg.eigvalsh(np.asarray(A))[-k:]
    np.testing.assert_allclose(top, want, atol=1e-3, rtol=1e-3)
    # Ritz vectors are orthonormal (full reorthogonalization works)
    V = np.asarray(vecs[:, -k:])
    np.testing.assert_allclose(V.T @ V, np.eye(k), atol=1e-3)


def test_lanczos_smallest_of_lsym_via_shift():
    x, _ = synthetic.blobs(90, 3, spread=0.1, seed=2)
    S = sim.dense_similarity(jnp.asarray(x), 1.0)
    L = lp.dense_lsym(S)
    mm = lp.make_dense_shifted_matmat(S)
    state = lz.block_lanczos(mm, 90, 60, jax.random.PRNGKey(1), block_size=1)
    vals, vecs = lz.block_topk_of_shifted(state, 3)
    want = np.linalg.eigvalsh(np.asarray(L))[:3]
    np.testing.assert_allclose(np.asarray(vals), want, atol=2e-3)
    res = lz.residuals(mm, vals, vecs, shift=2.0)
    assert float(jnp.max(res)) < 1e-2


def test_lanczos_checkpoint_resume_identical():
    """run(20) == run(10); checkpoint; run(10) at width 1 —
    fault-tolerance invariant."""
    n = 64
    key = jax.random.PRNGKey(3)
    A = jax.random.normal(key, (n, n))
    A = (A + A.T) / 2
    mm = lambda V: A @ V                                      # noqa: E731
    full = lz.block_run(mm, lz.init_block_state(n, 20, key, 1), 20)
    half = lz.block_run(mm, lz.init_block_state(n, 20, key, 1), 10)
    resumed = lz.block_run(mm, half, 10)
    np.testing.assert_allclose(np.asarray(full.A), np.asarray(resumed.A),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(full.V), np.asarray(resumed.V), atol=1e-5)


def _dense_similarity(n, seed):
    x, _ = synthetic.blobs(n, 3, spread=0.1, seed=seed)
    return sim.dense_similarity(jnp.asarray(x), 1.0)


@pytest.mark.parametrize("width", [1, 3])
def test_dense_operators_share_one_compiled_recurrence(width):
    """Two dense operators over different matrices of one shape advance
    through ONE compiled loop, and that cache does not keep a dropped
    operator's matrix alive."""
    import gc
    import weakref

    from repro.cluster.operator import NormalizedOperator

    n, steps = 44 + width, 6 + width      # shapes no other test compiles
    valid = jnp.ones((n,), jnp.float32)
    key = jax.random.PRNGKey(0)

    def advance(S):
        mm, inv = lp.make_dense_operator(S, valid)
        assert isinstance(mm, jax.tree_util.Partial)
        op = NormalizedOperator(matmat=mm, valid=valid, inv_sqrt=inv, n=n,
                                n_pad=n, mesh=None)
        return lz.block_run(op.matmat, lz.init_block_state(
            n, steps, key, width), steps)

    before = lz._block_steps_jit._cache_size()
    S1, S2 = _dense_similarity(n, 1), _dense_similarity(n, 2)
    out1, out2 = advance(S1), advance(S2)
    assert lz._block_steps_jit._cache_size() == before + 1
    assert not np.array_equal(np.asarray(out1.V), np.asarray(out2.V))

    ref = weakref.ref(S1)
    del S1
    gc.collect()
    assert ref() is None, "the jit cache kept a dropped operator's matrix"


@pytest.mark.parametrize("width", [1, 3])
def test_closure_operator_keeps_the_eager_recurrence(width):
    """A plain callable is never handed to the shared jitted loop (its
    cache does not grow), and gives the state the same operator gives as
    data."""
    n, steps = 40 + width, 5 + width
    S = _dense_similarity(n, 3)
    valid = jnp.ones((n,), jnp.float32)
    key = jax.random.PRNGKey(1)
    mm, _ = lp.make_dense_operator(S, valid)

    before = lz._block_steps_jit._cache_size()
    state0 = lz.init_block_state(n, steps, key, width)
    eager = lz.block_run(lambda V: mm(V), state0, steps)
    assert lz._block_steps_jit._cache_size() == before
    jitted = lz.block_run(mm, state0, steps)
    assert lz._block_steps_jit._cache_size() == before + 1
    for f in ("step", "V", "A", "B"):
        np.testing.assert_array_equal(np.asarray(getattr(eager, f)),
                                      np.asarray(getattr(jitted, f)))


# ---------------------------------------------------------------------------
# k-means
# ---------------------------------------------------------------------------

@given(st.integers(20, 100), st.integers(2, 5))
@settings(max_examples=15, deadline=None)
def test_kmeans_inertia_monotone(n, k):
    y = jax.random.normal(jax.random.PRNGKey(n * k), (n, 4))
    centers = km.kmeans_plusplus_init(y, k, jax.random.PRNGKey(0))
    valid = jnp.ones((n,))
    inertias = []
    state = km.KMeansState(it=jnp.zeros((), jnp.int32), centers=centers,
                           shift=jnp.asarray(jnp.inf))
    for _ in range(8):
        _, _, inertia = km._update(y, valid, state.centers)
        inertias.append(float(inertia))
        state = km.lloyd_step(y, valid, state)
    assert all(b <= a + 1e-4 for a, b in zip(inertias, inertias[1:])), inertias


def test_kmeans_recovers_blobs():
    x, truth = synthetic.blobs(120, 3, spread=0.05, seed=4)
    labels, _ = km.kmeans(jnp.asarray(x), 3, jax.random.PRNGKey(1))
    labels = np.asarray(labels)
    from itertools import permutations
    acc = max(np.mean(np.array([p[t] for t in truth]) == labels)
              for p in permutations(range(3)))
    assert acc > 0.98


# ---------------------------------------------------------------------------
# end-to-end
# ---------------------------------------------------------------------------

def test_fit_dense_rings():
    pts, truth = synthetic.rings(300, 2, seed=0)
    res = spectral.fit_dense(jnp.asarray(pts), spectral.SpectralConfig(
        k=2, sigma=0.25, kmeans_iters=40, seed=0))
    labels = np.asarray(res.labels)
    acc = max(np.mean(labels == truth), np.mean(labels == 1 - truth))
    assert acc > 0.95


def test_fit_distributed_matches_dense_single_device():
    pts, truth = synthetic.blobs(100, 3, seed=5)
    cfg = spectral.SpectralConfig(k=3, sigma=1.0, lanczos_steps=40, seed=0)
    res_d = spectral.fit_dense(jnp.asarray(pts), cfg)
    res = spectral.fit(jnp.asarray(pts), cfg)   # mesh = all local devices (1)
    np.testing.assert_allclose(np.asarray(res.eigenvalues),
                               np.asarray(res_d.eigenvalues), atol=1e-3)
    from itertools import permutations
    labels = np.asarray(res.labels)
    acc = max(np.mean(np.array([p[t] for t in truth]) == labels)
              for p in permutations(range(3)))
    assert acc == 1.0


def test_fit_from_similarity_graph():
    edges, truth = synthetic.synthetic_graph(n=160, n_edges=900, k=3, seed=0)
    from repro.data.graph_file import adjacency_dense
    S = adjacency_dense(160, edges)
    res = spectral.fit_from_similarity(jnp.asarray(S), spectral.SpectralConfig(
        k=3, lanczos_steps=48, seed=0))
    labels = np.asarray(res.labels)
    from itertools import permutations
    acc = max(np.mean(np.array([p[t] for t in truth]) == labels)
              for p in permutations(range(3)))
    assert acc > 0.9, acc
