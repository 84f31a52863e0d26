"""The block-operator contract across the refactored stack.

* property: ``op.matmat(V)`` equals its column-stacked width-1 products
  for EVERY registered affinity backend (the interface every eigensolver
  leans on);
* block Lanczos: oracle agreement, pass accounting, resumable state;
* Chebyshev-Davidson: eigenvalue agreement with the exact ``eigh`` oracle
  on the paper's synthetic blobs;
* estimator/CLI: the new backends are selectable end-to-end;
* seeding: the jax and numpy D^2-sampling twins agree statistically.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.cluster import SpectralClustering, ari
from repro.cluster.affinity import AFFINITIES
from repro.core import (chebdav as cd, lanczos as lz, laplacian as lp,
                        seeding, similarity as sim)
from repro.data import synthetic
from repro.distrib import mesh_utils

# every affinity must satisfy the matmat == stacked width-1 matmat law
BACKENDS = ("dense", "triangular", "compact", "precomputed", "knn-topt",
            "ooc-topt", "fused-rbf")


_OPERATORS: dict = {}


def _operator(backend: str):
    if backend not in _OPERATORS:
        pts, _ = synthetic.blobs(42, 3, dim=3, seed=11)
        x = jnp.asarray(pts)
        est = SpectralClustering(3, sigma=1.0, sparsify_t=8, chunk_size=16,
                                 seed=0)
        mesh = mesh_utils.local_mesh("rows")
        arg = sim.dense_similarity(x, 1.0) if backend == "precomputed" else x
        _OPERATORS[backend] = AFFINITIES.get(backend)(
            est, arg, jnp.asarray(1.0), mesh)
    return _OPERATORS[backend]


@pytest.fixture(scope="module", autouse=True)
def _close_cached_operators():
    """The cached ``ooc-topt`` operator owns a shard-prefetch pool: close
    it when the module ends, so no worker thread outlives these tests."""
    yield
    for op in _OPERATORS.values():
        if op.close is not None:
            op.close()


@settings(max_examples=12, deadline=None)
@given(st.integers(0, len(BACKENDS) - 1), st.integers(1, 5),
       st.integers(0, 2**16))
def test_matmat_equals_stacked_matvec(backend_idx, width, seed):
    op = _operator(BACKENDS[backend_idx])
    V = jax.random.normal(jax.random.PRNGKey(seed), (op.n_pad, width))
    got = np.asarray(op.matmat(V))
    want = np.concatenate([np.asarray(op.matmat(V[:, j:j + 1]))
                           for j in range(width)], axis=1)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    assert got.shape == (op.n_pad, width)


def test_materialize_assembles_matmat_through_identity_blocks():
    """An operator without ``dense`` materializes A through ``matmat``
    applied to identity column blocks, the last one narrower."""
    from repro.cluster.operator import NormalizedOperator
    n = 12
    A = np.random.RandomState(0).randn(n, n).astype(np.float32)
    A = A + A.T
    widths = []

    def matmat(V):
        widths.append(V.shape[1])
        return jnp.asarray(A) @ V

    op = NormalizedOperator(
        matmat=matmat, valid=jnp.ones((n,)), inv_sqrt=jnp.ones((n,)), n=n,
        n_pad=n, mesh=None)
    np.testing.assert_allclose(np.asarray(op.materialize(block=5)), A,
                               rtol=1e-4, atol=1e-5)
    assert widths == [5, 5, 2]


def test_operator_requires_some_product():
    from repro.cluster.operator import NormalizedOperator
    with pytest.raises(TypeError, match="matmat"):
        NormalizedOperator(valid=jnp.ones((4,)), inv_sqrt=jnp.ones((4,)),
                           n=4, n_pad=4, mesh=None)


# ---------------------------------------------------------------------------
# block Lanczos
# ---------------------------------------------------------------------------

def _dense_op(n=96, k=3, seed=3):
    pts, truth = synthetic.blobs(n, k, dim=4, spread=0.6, seed=seed)
    S = sim.dense_similarity(jnp.asarray(pts), 1.0)
    valid = jnp.ones((n,), jnp.float32)
    matmat, _ = lp.make_dense_operator(S, valid)
    A = lp.dense_shifted_matrix(S, valid)
    return matmat, A, truth


@pytest.mark.parametrize("block_size", [1, 2, 4, 8])
def test_block_lanczos_matches_eigh(block_size):
    matmat, A, _ = _dense_op()
    n = A.shape[0]
    steps = max(1, 48 // block_size)
    state = lz.block_lanczos(matmat, n, steps, jax.random.PRNGKey(0),
                             block_size=block_size)
    vals, vecs = lz.block_topk_of_shifted(state, 3)
    evals_A = np.asarray(jnp.linalg.eigh(A)[0])
    want = (2.0 - evals_A[-3:])[::-1]
    np.testing.assert_allclose(np.asarray(vals), want, atol=1e-4)
    # Ritz vectors are true eigenvectors: small residuals
    res = lz.residuals(matmat, vals, vecs, shift=2.0)
    assert float(jnp.max(res)) < 1e-3
    B = np.asarray(state.B)
    assert np.all(B[0] == 0.0)
    assert np.all(np.diagonal(B, axis1=1, axis2=2) >= 0.0)   # QR sign-fixed


def test_block_lanczos_resumable_checkpoint_state():
    matmat, A, _ = _dense_op(n=64)
    n = A.shape[0]
    key = jax.random.PRNGKey(5)
    full = lz.block_run(matmat, lz.init_block_state(n, 10, key, 4), 10)
    half = lz.block_run(matmat, lz.init_block_state(n, 10, key, 4), 5)
    resumed = lz.block_run(matmat, half, 5)
    np.testing.assert_allclose(np.asarray(full.A), np.asarray(resumed.A),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(full.B), np.asarray(resumed.B),
                               rtol=1e-4, atol=1e-5)


def test_block_basis_orthonormal():
    matmat, A, _ = _dense_op(n=80)
    n = A.shape[0]
    b, s = 4, 8
    state = lz.block_lanczos(matmat, n, s, jax.random.PRNGKey(2),
                             block_size=b)
    V = np.asarray(state.V)[: s * b]          # filled basis rows
    G = V @ V.T
    np.testing.assert_allclose(G, np.eye(s * b), atol=1e-4)


@pytest.mark.parametrize("data", ["points", "graph"])
def test_lanczos_is_block_lanczos_at_width_one(data):
    """The "lanczos" eigensolver is "block-lanczos" at b = 1: the same
    start block, recurrence and Ritz step, so the same eigenvalues,
    embedding and labels, bit for bit, on a points fit and a graph fit."""
    from repro.data import graph_file
    if data == "points":
        pts, _ = synthetic.blobs(60, 3, dim=3, spread=0.6, seed=4)
        x = jnp.asarray(pts)
        kw = dict(affinity="dense")

        def fit(est):
            return est.fit(x)
    else:
        edges, _ = synthetic.synthetic_graph(120, 260, k=3, seed=4)
        adj = graph_file.adjacency_sparse(120, edges)
        kw = {}

        def fit(est):
            return est.fit_graph(adj)
    one = fit(SpectralClustering(3, eigensolver="lanczos", sigma=1.0,
                                 lanczos_steps=24, seed=0, **kw))
    blk = fit(SpectralClustering(3, eigensolver="block-lanczos",
                                 block_size=1, sigma=1.0, lanczos_steps=24,
                                 seed=0, **kw))
    assert one.info_["matrix_passes"] == blk.info_["matrix_passes"] == 24
    for attr in ("eigenvalues_", "embedding_", "labels_"):
        np.testing.assert_array_equal(np.asarray(getattr(one, attr)),
                                      np.asarray(getattr(blk, attr)))


# ---------------------------------------------------------------------------
# Chebyshev-Davidson
# ---------------------------------------------------------------------------

def test_chebdav_matches_eigh_oracle_on_paper_blobs():
    """The satellite oracle: "chebdav" matches "eigh" eigenvalues to 1e-4
    on the paper's synthetic blobs."""
    pts, _ = synthetic.blobs(120, 3, dim=2, spread=0.15, seed=0)
    x = jnp.asarray(pts)
    eigh_est = SpectralClustering(3, affinity="dense", eigensolver="eigh",
                                  sigma=1.0, seed=0).fit(x)
    chb = SpectralClustering(3, affinity="dense", eigensolver="chebdav",
                             sigma=1.0, seed=0).fit(x)
    np.testing.assert_allclose(np.asarray(chb.eigenvalues_),
                               np.asarray(eigh_est.eigenvalues_), atol=1e-4)
    assert ari(np.asarray(eigh_est.labels_), np.asarray(chb.labels_)) >= 0.95
    assert chb.info_["matrix_passes"] > 0
    assert chb.info_["max_residual"] < 1e-4


def test_chebdav_counts_passes_and_filter_amplifies():
    matmat, A, _ = _dense_op(n=64)
    n = A.shape[0]
    res = cd.chebdav(matmat, n, 3, jax.random.PRNGKey(0), block_size=3,
                     degree=8)
    assert res.passes > 0 and res.iters >= 1
    evals_A = np.asarray(jnp.linalg.eigh(A)[0])
    np.testing.assert_allclose(np.asarray(res.evals), evals_A[-3:][::-1],
                               atol=1e-4)
    # the filter really does amplify the wanted end relative to the damp
    # interval: a random block gains alignment with the top eigenvector
    top = jnp.linalg.eigh(A)[1][:, -1]
    X = jax.random.normal(jax.random.PRNGKey(1), (n, 2))
    X = X / jnp.linalg.norm(X, axis=0, keepdims=True)
    Y = cd.chebyshev_filter(matmat, X, 10, 0.0, 1.2, 2.0)
    Y = Y / jnp.maximum(jnp.linalg.norm(Y, axis=0, keepdims=True), 1e-30)
    before = float(jnp.max(jnp.abs(top @ X)))
    after = float(jnp.max(jnp.abs(top @ Y)))
    assert after > before


# ---------------------------------------------------------------------------
# estimator / engine / CLI integration
# ---------------------------------------------------------------------------

def test_block_size_clamped_and_validated():
    pts, _ = synthetic.blobs(40, 3, seed=1)
    est = SpectralClustering(3, eigensolver="block-lanczos", block_size=64,
                             sigma=1.0, seed=0).fit(jnp.asarray(pts))
    assert est.info_["block_size"] == 40          # clamped to n_pad
    with pytest.raises(ValueError, match="block_size must be positive"):
        SpectralClustering(3, eigensolver="block-lanczos",
                           block_size=0, sigma=1.0).fit(jnp.asarray(pts))
    with pytest.raises(ValueError, match="cheb_degree"):
        SpectralClustering(3, eigensolver="chebdav", cheb_degree=0)


@pytest.mark.parametrize("solver", ["block-lanczos", "chebdav"])
def test_new_eigensolvers_end_to_end(solver):
    pts, truth = synthetic.blobs(90, 3, seed=7)
    est = SpectralClustering(3, affinity="triangular", eigensolver=solver,
                             sigma=1.0, lanczos_steps=40, seed=0)
    est.fit(jnp.asarray(pts))
    assert ari(truth, np.asarray(est.labels_)) >= 0.95
    assert est.info_["matrix_passes"] > 0
    if solver == "block-lanczos":
        # ceil(40 / 8) block steps — 8x fewer passes than scalar lanczos
        assert est.info_["matrix_passes"] == 5


def test_block_lanczos_cuts_engine_shard_gets():
    """The spill-traffic claim: one eigensolve's shard-store gets drop by
    ~the block width when each CSR shard is pulled once per block."""
    from repro import engine
    from repro.cluster.eigensolvers import EIGENSOLVERS
    from repro.data.chunked import ArrayChunks

    pts, _ = synthetic.blobs(200, 3, dim=4, spread=0.8, seed=0)
    plan = engine.JobPlan(n=200, chunk_size=50, t=8, k=3, sigma=1.0)
    graph, _ = engine.build_graph(ArrayChunks(pts, 50), plan)
    op = engine.make_normalized_operator(graph)
    gets = {}
    for solver in ("lanczos", "block-lanczos"):
        est = SpectralClustering(3, eigensolver=solver, sigma=1.0,
                                 lanczos_steps=32, block_size=8, seed=0)
        graph._drain_prefetch()          # settle the async warm-start get
        before = graph.store.stats["gets"]
        _, Z, info = EIGENSOLVERS.get(solver)(est, op, jax.random.PRNGKey(0))
        jax.block_until_ready(Z)
        graph._drain_prefetch()          # ...so both counts are exact
        gets[solver] = graph.store.stats["gets"] - before
    # 32 scalar passes vs ceil(32/8)=4 block passes over 4 shards; each
    # eigensolve pays one extra warm-start get (129 vs 17), so the
    # reduction bound is 7x, not the asymptotic 8x
    assert gets["lanczos"] >= 7 * gets["block-lanczos"] > 0


def test_cli_chebdav_selectable(capsys):
    from repro.launch import spectral_job
    spectral_job.main(["--blobs", "60", "--k", "3", "--affinity", "dense",
                       "--eigensolver", "chebdav", "--cheb-degree", "8"])
    out = capsys.readouterr().out
    assert "eigensolver=chebdav" in out
    assert "matrix_passes=" in out


# ---------------------------------------------------------------------------
# shared k-means++ seeding (the dedupe satellite)
# ---------------------------------------------------------------------------

def test_seeding_twins_share_behaviour():
    """Both substrates pick k distinct, well-spread centers from the same
    blob data, and the numpy twin is what the engine imports."""
    from repro.engine import kmeans as skm
    assert skm._kmeanspp is seeding.kmeans_plusplus_np

    pts, truth = synthetic.blobs(120, 3, dim=2, spread=0.05, seed=2)
    got_np = seeding.kmeans_plusplus_np(pts.astype(np.float64), 3,
                                        np.random.RandomState(0))
    got_jx = np.asarray(seeding.kmeans_plusplus_init(
        jnp.asarray(pts), 3, jax.random.PRNGKey(0)))
    centers = pts[np.arange(120) % 3 == 0].mean(axis=0)  # sanity anchor
    del centers
    for got in (got_np, got_jx):
        # one seed per blob: nearest true blob center of each pick differs
        blob_means = np.stack([pts[truth == c].mean(axis=0)
                               for c in range(3)])
        d = ((got[:, None, :] - blob_means[None]) ** 2).sum(-1)
        assert sorted(np.argmin(d, axis=1).tolist()) == [0, 1, 2]


def test_weighted_seeding_never_picks_masked_rows():
    y = np.zeros((10, 2), np.float64)
    y[5:] = 100.0                      # masked-out far rows
    w = np.array([1.0] * 5 + [0.0] * 5)
    centers = seeding.kmeans_plusplus_np(y, 3, np.random.RandomState(1), w)
    assert np.all(centers < 50.0)
    got = np.asarray(seeding.kmeans_plusplus_init(
        jnp.asarray(y, jnp.float32), 3, jax.random.PRNGKey(4),
        weights=jnp.asarray(w, jnp.float32)))
    assert np.all(got < 50.0)
