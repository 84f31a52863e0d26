"""The graph path kept sparse: ``graph_file.adjacency_sparse`` and the
``graph`` affinity against the dense adjacency and the ``precomputed``
operator built from it, and ``spectral_job --graph`` against the dense
fit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import SpectralClustering, ari
from repro.data import graph_file, synthetic

# a repeated pair (2, 3) whose last weight wins, a pair given in both
# orientations, a self-edge (6, 6), and vertex 10 with no edge at all
SMALL_N = 11
SMALL_EDGES = [[0, 1, 1], [2, 3, 2], [1, 4, 3], [5, 6, 1], [6, 6, 9],
               [2, 3, 5], [4, 1, 3], [7, 8, 2], [8, 9, 1], [0, 9, 4],
               [3, 5, 1]]

_OPERATOR_CHECK = f"""
import numpy as np, jax, jax.numpy as jnp
from repro.cluster import SpectralClustering
from repro.cluster.affinity import AFFINITIES, operator_from_dense
from repro.data import graph_file
from repro.distrib import mesh_utils

n, edges = {SMALL_N}, np.array({SMALL_EDGES})
mesh = mesh_utils.local_mesh("rows")
est = SpectralClustering(3)
adj = graph_file.adjacency_sparse(n, edges)
assert adj.nnz < adj.weights.size, "the rows must carry spare slots"
op = AFFINITIES.get("graph")(est, jax.device_put(adj), None, mesh)
ref = operator_from_dense(jnp.asarray(graph_file.adjacency_dense(n, edges)),
                          n, mesh)
assert (op.n, op.n_pad) == (ref.n, ref.n_pad)
# the same degrees (row sums of small integers, exact); D^-1/2 within
# float32 rounding, as one program computes it and not eager steps
np.testing.assert_array_equal(np.asarray(adj.weights).sum(axis=1),
                              graph_file.adjacency_dense(n, edges).sum(axis=1))
np.testing.assert_allclose(np.asarray(op.inv_sqrt), np.asarray(ref.inv_sqrt),
                           rtol=2.4e-7, atol=0)
np.testing.assert_array_equal(np.asarray(op.valid), np.asarray(ref.valid))
# spare slots pointing elsewhere change nothing; twice as many change
# nothing but the order of a sum
spare = adj.weights == 0
moved = graph_file.SparseAdjacency(
    cols=np.where(spare, (adj.cols + 3) % n, adj.cols),
    weights=adj.weights, nnz=adj.nnz)
wider = graph_file.SparseAdjacency(
    cols=np.concatenate([adj.cols, adj.cols], axis=1),
    weights=np.concatenate([adj.weights, 0 * adj.weights], axis=1),
    nnz=adj.nnz)
op_moved = AFFINITIES.get("graph")(est, moved, None, mesh)
op_wider = AFFINITIES.get("graph")(est, wider, None, mesh)
for b in (1, 8):
    V = jax.random.normal(jax.random.PRNGKey(b), (op.n_pad, b), jnp.float32)
    got, want = np.asarray(op.matmat(V)), np.asarray(ref.matmat(V))
    rel = np.abs(got - want).max() / np.abs(want).max()
    assert rel <= 1e-6, (b, rel)
    np.testing.assert_array_equal(np.asarray(op_moved.matmat(V)), got)
    np.testing.assert_allclose(np.asarray(op_wider.matmat(V)), got,
                               rtol=1e-6, atol=1e-7)
np.testing.assert_allclose(np.asarray(op.dense()), np.asarray(ref.dense()),
                           rtol=5e-7, atol=0)
print("OK", mesh_utils.mesh_size(mesh))
"""


@pytest.mark.parametrize("n_devices", [1, 4])
def test_graph_operator_matches_dense_operator(n_devices, subproc):
    """Degrees, scales, ``matmat`` at widths 1 and 8 (within 1e-6
    relative) and ``dense()`` of the sparse operator equal the
    ``precomputed`` operator's over ``adjacency_dense``, up to float32
    rounding, on one device and on a mesh of four."""
    out = subproc(_OPERATOR_CHECK, n_devices=n_devices)
    assert f"OK {n_devices}" in out


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_adjacency_sparse_is_adjacency_dense(seed):
    """The rows hold exactly the dense matrix, columns ascending, in a
    width of a multiple of 8 with weight-0 spare slots; repeated pairs
    (in either orientation, with other weights) and self-edges
    included."""
    rng = np.random.default_rng(seed)
    n = 40
    edges = np.stack([rng.integers(0, n, 150), rng.integers(0, n, 150),
                      rng.integers(1, 5, 150)], axis=1)
    adj = graph_file.adjacency_sparse(n, edges)
    rows = np.repeat(np.arange(n), adj.width)
    got = np.zeros((n, n), np.float32)
    np.add.at(got, (rows, adj.cols.ravel()), adj.weights.ravel())
    dense = graph_file.adjacency_dense(n, edges)
    np.testing.assert_array_equal(got, dense)
    fill = (adj.weights != 0).sum(axis=1)
    assert adj.nnz == np.count_nonzero(dense)
    assert adj.width % 8 == 0 and fill.max() <= adj.width < fill.max() + 8
    for i in range(n):
        assert np.all(np.diff(adj.cols[i, :fill[i]]) > 0)
        assert np.count_nonzero(adj.weights[i, fill[i]:]) == 0


def test_graph_fit_rejects_a_dense_matrix():
    with pytest.raises(ValueError, match="SparseAdjacency"):
        SpectralClustering(2, affinity="graph").fit(jnp.eye(4))


def test_graph_fit_with_eigh_materializes_on_device():
    """``eigh`` on a graph fit gets the dense shifted matrix from the
    nonzeros and agrees with ``eigh`` on the dense adjacency."""
    edges, _ = synthetic.synthetic_graph(n=60, n_edges=200, k=2, seed=3)
    adj = graph_file.adjacency_sparse(60, edges)
    got = SpectralClustering(2, affinity="graph", eigensolver="eigh",
                             seed=0).fit(adj)
    want = SpectralClustering(2, affinity="precomputed", eigensolver="eigh",
                              seed=0).fit(jnp.asarray(
                                  graph_file.adjacency_dense(60, edges)))
    assert got.info_["affinity"] == "graph"
    np.testing.assert_allclose(np.asarray(got.eigenvalues_),
                               np.asarray(want.eigenvalues_), atol=1e-6)
    assert ari(np.asarray(got.labels_), np.asarray(want.labels_)) == 1.0


def test_graph_job_matches_dense_fit(tmp_path):
    """``spectral_job --graph`` on a planted graph: the eigenvalues of the
    dense ``precomputed`` fit within 1e-5, and the same partition."""
    from repro import obs
    from repro.launch import spectral_job

    n = 160
    edges, truth = synthetic.synthetic_graph(n=n, n_edges=900, k=3, seed=0)
    path = str(tmp_path / "topo.txt")
    graph_file.write_topology(path, n, edges)
    before = obs.snapshot().get("affinity.graph_fits", {}).get("value", 0)
    est = spectral_job.main(["--graph", path, "--k", "3"])
    dense = SpectralClustering(3, affinity="precomputed", lanczos_steps=48,
                               seed=0).fit(jnp.asarray(
                                   graph_file.adjacency_dense(n, edges)))
    assert est.info_["affinity"] == "graph"
    assert est.info_["matrix_passes"] == 48
    assert obs.snapshot()["affinity.graph_fits"]["value"] == before + 1
    np.testing.assert_allclose(np.asarray(est.eigenvalues_),
                               np.asarray(dense.eigenvalues_), atol=1e-5)
    assert ari(np.asarray(est.labels_), np.asarray(dense.labels_)) == 1.0
    assert ari(np.asarray(est.labels_), truth) > 0.9
    assert est._eigvecs.shape == (n, 3)
    assert est.centers_.shape == (3, 3)
