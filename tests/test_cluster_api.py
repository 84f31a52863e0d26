"""The unified estimator API: backend registries, oracle agreement,
precomputed round-trip, out-of-sample prediction, and legacy-shim parity."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import (AFFINITIES, ASSIGNERS, EIGENSOLVERS,
                           SpectralClustering)
from repro.core import similarity as sim, spectral
from repro.data import synthetic
from repro.data.graph_file import adjacency_dense, parse_topology, write_topology


def _perm_acc(labels, truth, k):
    from itertools import permutations
    labels = np.asarray(labels)
    return max(np.mean(np.array([p[t] for t in truth]) == labels)
               for p in permutations(range(k)))


# ---------------------------------------------------------------------------
# registry behaviour
# ---------------------------------------------------------------------------

def test_registry_unknown_backend_messages():
    with pytest.raises(ValueError, match=r"unknown affinity backend 'rbf\?'"):
        SpectralClustering(3, affinity="rbf?")
    with pytest.raises(ValueError, match="unknown eigensolver backend"):
        SpectralClustering(3, eigensolver="power-iteration")
    with pytest.raises(ValueError, match="unknown assigner backend"):
        SpectralClustering(3, assigner="gonzalez")
    # the error names what IS registered
    with pytest.raises(ValueError, match="triangular"):
        SpectralClustering(3, affinity="nope")


def test_registry_contents_and_custom_registration():
    assert set(AFFINITIES.names()) >= {"dense", "triangular", "compact",
                                       "precomputed", "knn-topt"}
    assert set(EIGENSOLVERS.names()) >= {"eigh", "lanczos"}
    assert set(ASSIGNERS.names()) >= {"lloyd", "minibatch"}

    @ASSIGNERS.register("test-constant")
    def constant_assigner(est, Y, valid, key, mesh):
        return jnp.zeros((Y.shape[0],), jnp.int32), jnp.zeros(
            (est.k, Y.shape[1]), Y.dtype)

    try:
        pts, _ = synthetic.blobs(24, 2, seed=0)
        est = SpectralClustering(2, assigner="test-constant", sigma=1.0)
        est.fit(jnp.asarray(pts))
        assert np.asarray(est.labels_).max() == 0
        with pytest.raises(ValueError, match="already registered"):
            ASSIGNERS.register("test-constant")(constant_assigner)
    finally:
        ASSIGNERS._entries.pop("test-constant", None)


def test_precomputed_rejects_nonsquare():
    with pytest.raises(ValueError, match="square"):
        SpectralClustering(2, affinity="precomputed").fit(jnp.ones((4, 3)))


def test_predict_before_fit_raises():
    with pytest.raises(ValueError, match="not .*fitted"):
        SpectralClustering(2).predict(jnp.ones((3, 2)))


# ---------------------------------------------------------------------------
# legacy parity / oracle agreement
# ---------------------------------------------------------------------------

def test_estimator_matches_legacy_fit_bit_for_bit():
    """The acceptance invariant: triangular/lanczos/lloyd reproduces the
    legacy spectral.fit pipeline exactly (same RNG discipline, same ops)."""
    pts, _ = synthetic.blobs(100, 3, seed=5)
    x = jnp.asarray(pts)
    cfg = spectral.SpectralConfig(k=3, sigma=1.0, lanczos_steps=40, seed=0)
    with pytest.deprecated_call():
        res = spectral.fit(x, cfg)
    est = SpectralClustering(3, affinity="triangular", eigensolver="lanczos",
                             assigner="lloyd", sigma=1.0, lanczos_steps=40,
                             seed=0).fit(x)
    np.testing.assert_array_equal(np.asarray(res.labels),
                                  np.asarray(est.labels_))
    np.testing.assert_array_equal(np.asarray(res.embedding),
                                  np.asarray(est.embedding_))
    np.testing.assert_array_equal(np.asarray(res.eigenvalues),
                                  np.asarray(est.eigenvalues_))


def test_estimator_agrees_with_dense_oracle_blobs():
    pts, truth = synthetic.blobs(90, 3, seed=7)
    x = jnp.asarray(pts)
    oracle = SpectralClustering(3, affinity="dense", eigensolver="eigh",
                                sigma=1.0, seed=0).fit(x)
    dist = SpectralClustering(3, affinity="triangular", eigensolver="lanczos",
                              sigma=1.0, lanczos_steps=40, seed=0).fit(x)
    np.testing.assert_allclose(np.asarray(dist.eigenvalues_),
                               np.asarray(oracle.eigenvalues_), atol=1e-3)
    assert _perm_acc(oracle.labels_, truth, 3) == 1.0
    assert _perm_acc(dist.labels_, truth, 3) == 1.0


def test_estimator_agrees_with_dense_oracle_rings():
    pts, truth = synthetic.rings(300, 2, seed=0)
    x = jnp.asarray(pts)
    for backend in ({"affinity": "dense", "eigensolver": "eigh"},
                    {"affinity": "triangular", "eigensolver": "lanczos",
                     "lanczos_steps": 64}):
        est = SpectralClustering(2, sigma=0.25, kmeans_iters=40, seed=0,
                                 **backend).fit(x)
        labels = np.asarray(est.labels_)
        acc = max(np.mean(labels == truth), np.mean(labels == 1 - truth))
        assert acc > 0.95, (backend, acc)


# ---------------------------------------------------------------------------
# every combination of registered backends runs end-to-end
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("affinity,eigensolver,assigner", list(
    itertools.product(["dense", "triangular", "compact", "precomputed",
                       "knn-topt"],
                      ["eigh", "lanczos"], ["lloyd", "minibatch"])))
def test_backend_combinations_end_to_end(affinity, eigensolver, assigner):
    pts, truth = synthetic.blobs(60, 3, seed=2)
    x = jnp.asarray(pts)
    arg = sim.dense_similarity(x, 1.0) if affinity == "precomputed" else x
    est = SpectralClustering(3, affinity=affinity, eigensolver=eigensolver,
                             assigner=assigner, sigma=1.0, lanczos_steps=40,
                             seed=0).fit(arg)
    assert np.asarray(est.labels_).shape == (60,)
    assert np.asarray(est.embedding_).shape == (60, 3)
    assert _perm_acc(est.labels_, truth, 3) > 0.9
    evals = np.asarray(est.eigenvalues_)
    assert (evals > -1e-3).all() and (evals < 2 + 1e-3).all()


# ---------------------------------------------------------------------------
# precomputed affinity round-trip on the §5 topology format
# ---------------------------------------------------------------------------

def test_precomputed_topology_graph_roundtrip(tmp_path):
    edges, truth = synthetic.synthetic_graph(n=160, n_edges=900, k=3, seed=0)
    path = str(tmp_path / "topo.txt")
    write_topology(path, 160, edges)
    n, edges_back = parse_topology(path)
    assert n == 160
    S = adjacency_dense(n, edges_back)
    est = SpectralClustering(3, affinity="precomputed", lanczos_steps=48,
                             seed=0).fit(jnp.asarray(S))
    assert _perm_acc(est.labels_, truth, 3) > 0.9
    # fit() with affinity="precomputed" and fit_affinity() are the same path
    est2 = SpectralClustering(3, affinity="triangular", lanczos_steps=48,
                              seed=0).fit_affinity(jnp.asarray(S))
    np.testing.assert_array_equal(np.asarray(est.labels_),
                                  np.asarray(est2.labels_))


@pytest.mark.parametrize("n_edges,components", [(900, 1), (60, None)])
def test_graph_reference_counts_zero_eigenvalues(n_edges, components):
    """The float64 graph witness: one zero L_sym eigenvalue per connected
    component, as a dense float64 eigh of the same graph shows."""
    from repro.cluster.reference import graph_components
    edges, _ = synthetic.synthetic_graph(n=160, n_edges=n_edges, k=3, seed=0)
    S = adjacency_dense(160, edges, np.float64)
    inv = 1.0 / np.sqrt(S.sum(axis=1))
    L = np.eye(160) - S * inv[:, None] * inv[None, :]
    zeros = int(np.sum(np.abs(np.linalg.eigvalsh(L)) < 1e-9))
    assert graph_components(160, edges) == zeros
    if components is not None:
        assert zeros == components


def test_graph_lanczos_reference_matches_the_lanczos_solver():
    """The float64 recurrence and the estimator's float32 "lanczos" solver
    agree where 48 steps converge (a connected 160-vertex graph)."""
    from repro.cluster.reference import graph_lanczos_reference
    edges, _ = synthetic.synthetic_graph(n=160, n_edges=900, k=3, seed=0)
    est = SpectralClustering(3, affinity="precomputed", lanczos_steps=48,
                             seed=0).fit(jnp.asarray(adjacency_dense(160,
                                                                     edges)))
    _, k_lan, _ = jax.random.split(jax.random.PRNGKey(0), 3)
    v0 = np.asarray(jax.random.normal(k_lan, (est.info_["n_pad"],),
                                      jnp.float32), np.float64)
    ref = graph_lanczos_reference(160, edges, v0, 48, 3)
    np.testing.assert_allclose(np.asarray(est.eigenvalues_), ref, atol=1e-5)


# ---------------------------------------------------------------------------
# out-of-sample transform / predict
# ---------------------------------------------------------------------------

def test_predict_heldout_points():
    rng = np.random.RandomState(0)
    pts, truth = synthetic.blobs(120, 3, spread=0.08, seed=4)
    x = jnp.asarray(pts)
    est = SpectralClustering(3, affinity="triangular", sigma=1.0,
                             lanczos_steps=40, seed=0).fit(x)

    # training points map back to their own clusters
    self_pred = np.asarray(est.predict(x))
    assert np.mean(self_pred == np.asarray(est.labels_)) > 0.97

    # held-out points drawn near training points inherit their cluster
    idx = rng.choice(120, size=30, replace=False)
    held = pts[idx] + rng.randn(30, pts.shape[1]).astype(np.float32) * 0.01
    pred = np.asarray(est.predict(jnp.asarray(held)))
    assert np.mean(pred == np.asarray(est.labels_)[idx]) > 0.9

    emb = np.asarray(est.transform(jnp.asarray(held)))
    assert emb.shape == (30, 3)
    np.testing.assert_allclose(np.linalg.norm(emb, axis=1), 1.0, atol=1e-4)


def test_predict_jitted_by_a_caller_multiplies_in_float32():
    """The estimator's float32 matrix products reach a program a caller
    jits around ``predict`` (as ClusterServer does): on a TPU the default
    would be one bfloat16 pass."""
    pts, _ = synthetic.blobs(64, 2, seed=3)
    x = jnp.asarray(pts)
    est = SpectralClustering(2, affinity="dense", eigensolver="eigh").fit(x)
    text = jax.jit(lambda xb: est.predict(xb)).lower(x[:16]).as_text()
    dots = [line for line in text.splitlines() if "dot_general" in line]
    assert dots
    assert all("precision = [HIGHEST, HIGHEST]" in line for line in dots)


def test_precomputed_fit_cannot_predict():
    pts, _ = synthetic.blobs(40, 2, seed=1)
    S = sim.dense_similarity(jnp.asarray(pts), 1.0)
    est = SpectralClustering(2, affinity="precomputed").fit(S)
    with pytest.raises(ValueError, match="precomputed"):
        est.predict(jnp.asarray(pts))


# ---------------------------------------------------------------------------
# mini-batch assigner quality
# ---------------------------------------------------------------------------

def test_minibatch_assigner_recovers_blobs():
    pts, truth = synthetic.blobs(200, 3, spread=0.05, seed=9)
    est = SpectralClustering(3, affinity="dense", eigensolver="eigh",
                             assigner="minibatch", sigma=1.0,
                             minibatch_size=64, seed=0).fit(jnp.asarray(pts))
    assert _perm_acc(est.labels_, truth, 3) > 0.97
