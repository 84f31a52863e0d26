"""Out-of-core engine (repro.engine) + §5 metrics + chunked data readers.

The load-bearing test is the oracle agreement: the engine's map/shuffle/
reduce graph must reproduce the in-memory ``knn-topt`` backend — same
top-t similarity graph (up to threshold ties), same labels up to
permutation (checked with the paper's ARI/NMI metrics).
"""
from __future__ import annotations

import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.cluster import SpectralClustering, ari, nmi, purity
from repro.core import similarity as sim
from repro.data import synthetic
from repro.data.chunked import ArrayChunks, BlobChunks
from repro.engine.plan import JobPlan, chunk_ranges
from repro.engine.store import ShardStore


# ---------------------------------------------------------------------------
# metrics (paper §5): closed-form cases
# ---------------------------------------------------------------------------

def test_metrics_perfect_and_permuted():
    a = np.array([0, 0, 1, 1, 2, 2])
    b = np.array([2, 2, 0, 0, 1, 1])        # same partition, renamed
    for m in (ari, nmi, purity):
        assert m(a, a) == pytest.approx(1.0)
        assert m(a, b) == pytest.approx(1.0)


def test_metrics_disagreement_is_low():
    a = np.array([0, 0, 0, 1, 1, 1])
    b = np.array([0, 1, 0, 1, 0, 1])        # orthogonal split
    assert ari(a, b) < 0.4
    assert nmi(a, b) < 0.4
    assert purity(a, b) == pytest.approx(4 / 6)


def test_metrics_match_sklearn_when_available():
    sk = pytest.importorskip("sklearn.metrics")
    rng = np.random.RandomState(0)
    for _ in range(5):
        a = rng.randint(0, 4, 60)
        b = rng.randint(0, 3, 60)
        assert ari(a, b) == pytest.approx(sk.adjusted_rand_score(a, b))
        assert nmi(a, b) == pytest.approx(
            sk.normalized_mutual_info_score(a, b))


# ---------------------------------------------------------------------------
# shard store: budget, spill, reload
# ---------------------------------------------------------------------------

def test_shard_store_spill_and_reload_roundtrip(tmp_path):
    store = ShardStore(memory_budget=3000, spill_dir=str(tmp_path))
    blocks = {f"blk/{i}": {"x": np.arange(i, i + 256, dtype=np.float32),
                           "y": np.full(8, i, np.int64)}
              for i in range(6)}               # ~1KB each >> 3KB budget
    for key, arrays in blocks.items():
        store.put(key, arrays)
    assert store.ram_bytes <= 3000
    spilled = store.spilled_keys()
    assert spilled, "budget should have forced spills"
    assert all(os.path.exists(os.path.join(
        str(tmp_path), k.replace("/", "__") + ".bin")) for k in spilled)
    for key, arrays in blocks.items():         # reload == original, any order
        got = store.get(key)
        for name, a in arrays.items():
            np.testing.assert_array_equal(got[name], a)
    assert store.stats["loads"] > 0


def test_shard_store_unlimited_never_spills(tmp_path):
    store = ShardStore(memory_budget=None, spill_dir=str(tmp_path))
    for i in range(5):
        store.put(f"k{i}", {"a": np.zeros(1000, np.float32)})
    assert store.stats["spills"] == 0 and not store.spilled_keys()


def test_shard_store_get_keeps_larger_than_budget_entry(tmp_path):
    # regression: _enforce_budget(keep=key) used to spill the just-loaded
    # entry whenever it was the only resident one, so every get() of a
    # larger-than-budget shard reloaded and re-dropped it while the spill
    # counter inflated with entries that were already on disk
    # (async_spill off: this test pins the synchronous loads/spills
    # accounting; the async variants live in test_engine_async.py)
    store = ShardStore(memory_budget=100, spill_dir=str(tmp_path),
                       async_spill=False)
    a = {"x": np.arange(200, dtype=np.float32)}     # 800 B >> budget
    b = {"x": np.zeros(200, np.float32)}
    store.put("a", a)                               # spilled on put
    store.put("b", b)
    assert store.stats["spills"] == 2               # two first-time writes
    got = store.get("a")                            # reload over budget
    np.testing.assert_array_equal(got["x"], a["x"])
    assert "a" in store._ram, "get() must keep the entry it just loaded"
    store.get("a")                                  # second get: RAM hit
    assert store.stats["loads"] == 1, "resident entry reloaded from disk"
    # the one reload never re-wrote the spill file or counted as a fresh spill
    assert store.stats["spills"] == 2
    assert store.stats["drops"] == 0


def test_shard_store_redrop_counts_as_drop_not_spill(tmp_path):
    # a reloaded entry evicted AGAIN (to make room for another get) is a
    # drop — its spill file is already current — not a new spill
    store = ShardStore(memory_budget=900, spill_dir=str(tmp_path),
                       async_spill=False)
    blocks = {k: {"x": np.full(200, i, np.float32)}   # 800 B each
              for i, k in enumerate("abc")}
    for k, v in blocks.items():
        store.put(k, v)
    store.get("a")                # evicts c (first-time spill); all on disk
    spills0 = store.stats["spills"]
    bytes0 = store.stats["bytes_spilled"]
    assert spills0 == 3
    store.get("b")                                  # evicts a -> drop
    store.get("c")                                  # evicts b -> drop
    assert store.stats["drops"] == 2
    assert store.stats["spills"] == spills0, "re-drop counted as spill"
    assert store.stats["bytes_spilled"] == bytes0
    for k, v in blocks.items():                     # data still intact
        np.testing.assert_array_equal(store.get(k)["x"], v["x"])


def test_shard_store_delete_removes_spill_file(tmp_path):
    store = ShardStore(memory_budget=10, spill_dir=str(tmp_path),
                       async_spill=False)
    store.put("a", {"x": np.zeros(100)})       # immediately over budget
    (path,) = [os.path.join(str(tmp_path), "a.bin")]
    assert os.path.exists(path)
    store.delete("a")
    assert not os.path.exists(path) and "a" not in store


# ---------------------------------------------------------------------------
# graph build: oracle agreement with the in-memory top-t graph
# ---------------------------------------------------------------------------

def _oracle_topt(pts: np.ndarray, sigma: float, t: int) -> np.ndarray:
    S = sim.rbf_kernel(jnp.asarray(pts), jnp.asarray(pts), sigma)
    return np.asarray(sim.sparsify_topt(S, t))


@pytest.mark.parametrize("n,chunk", [
    (120, 40),     # divides evenly
    (130, 40),     # ragged last chunk
    (90, 128),     # chunk size >= n (single chunk)
    (64, 1),       # degenerate 1-row chunks
])
def test_engine_graph_matches_in_memory_topt(n, chunk):
    rng = np.random.RandomState(1)
    pts = rng.randn(n, 3).astype(np.float32)
    plan = JobPlan(n=n, chunk_size=chunk, t=5, k=2, sigma=1.0)
    graph, sigma = engine.build_graph(ArrayChunks(pts, chunk), plan)
    np.testing.assert_allclose(graph.to_dense(), _oracle_topt(pts, 1.0, 5),
                               atol=1e-5)
    # degrees accumulated by the reduce tasks match the materialized graph
    np.testing.assert_allclose(graph.deg, graph.to_dense().sum(axis=1),
                               rtol=1e-5)


def test_engine_matvec_streams_shards_correctly():
    rng = np.random.RandomState(2)
    pts = rng.randn(75, 4).astype(np.float32)
    plan = JobPlan(n=75, chunk_size=20, t=6, k=2, sigma=0.8)
    graph, _ = engine.build_graph(ArrayChunks(pts, 20), plan)
    v = rng.randn(75).astype(np.float32)
    np.testing.assert_allclose(graph.matmat(v[:, None])[:, 0],
                               graph.to_dense() @ v,
                               rtol=1e-4, atol=1e-5)


def test_engine_graph_identical_under_spilling(tmp_path):
    pts, _ = synthetic.blobs(150, 3, dim=3, seed=4)
    plan_ram = JobPlan(n=150, chunk_size=48, t=8, k=3, sigma=1.0)
    plan_ooc = JobPlan(n=150, chunk_size=48, t=8, k=3, sigma=1.0,
                       memory_budget=16 * 1024, spill_dir=str(tmp_path))
    g_ram, _ = engine.build_graph(ArrayChunks(pts, 48), plan_ram)
    g_ooc, _ = engine.build_graph(ArrayChunks(pts, 48), plan_ooc)
    assert g_ooc.stats_snapshot()["store_bytes_spilled"] > 0
    np.testing.assert_array_equal(g_ram.to_dense(), g_ooc.to_dense())


# ---------------------------------------------------------------------------
# end-to-end: ooc-topt vs knn-topt label agreement (ARI/NMI), spill forced
# ---------------------------------------------------------------------------

def test_ooc_topt_agrees_with_knn_topt(tmp_path):
    # spread 0.8 keeps the blobs weakly connected: distinct small
    # eigenvalues, so Lanczos resolves the same subspace on both paths
    # (perfectly separated blobs give an exactly-degenerate null space
    # where *any* eigensolver's basis is arbitrary).
    pts, _ = synthetic.blobs(240, 3, dim=4, spread=0.8, seed=0)
    x = jnp.asarray(pts)
    ref = SpectralClustering(k=3, affinity="knn-topt", sparsify_t=10,
                             sigma=1.0, seed=0, lanczos_steps=96).fit(x)
    ooc = SpectralClustering(k=3, affinity="ooc-topt", sparsify_t=10,
                             sigma=1.0, seed=0, chunk_size=64,
                             lanczos_steps=96, memory_budget=32 * 1024,
                             spill_dir=str(tmp_path)).fit(x)
    la, lb = np.asarray(ref.labels_), np.asarray(ooc.labels_)
    assert ari(la, lb) >= 0.95
    assert nmi(la, lb) >= 0.95
    eng = ooc.info_["engine"]
    assert eng["store_bytes_spilled"] > 0          # budget forced spills
    assert eng["map_tasks"] == 4 * 5 // 2          # 4 chunks -> 10 tiles
    np.testing.assert_allclose(np.asarray(ref.eigenvalues_),
                               np.asarray(ooc.eigenvalues_), atol=1e-3)


def test_run_job_full_pipeline_and_streaming_assigner():
    reader = BlobChunks(300, 3, chunk_size=90, dim=4, spread=0.8, seed=1)
    plan = JobPlan(n=300, chunk_size=90, t=10, k=3, sigma=1.0, seed=0,
                   lanczos_steps=96, kmeans_rounds=30)
    res = engine.run_job(plan, reader)
    assert res.labels.shape == (300,)
    assert ari(reader.all_labels(), res.labels) >= 0.95
    assert res.stats["nnz"] > 0 and res.stats["reduce_tasks"] == 4

    # the registry "streaming" assigner reproduces sane labels too
    est = SpectralClustering(k=3, affinity="ooc-topt", assigner="streaming",
                             sparsify_t=10, sigma=1.0, seed=0, chunk_size=90,
                             lanczos_steps=96)
    x = np.concatenate([reader[c] for c in range(len(reader))])
    est.fit(jnp.asarray(x))
    assert ari(reader.all_labels(), np.asarray(est.labels_)) >= 0.95


def test_ooc_topt_multi_device_uneven_n(subproc):
    # n=242 not divisible by 4 devices: the operator must pad to the mesh
    # multiple like every other affinity or the estimator's shard_map
    # stages reject the uneven rows
    out = subproc("""
import numpy as np, jax.numpy as jnp
from repro.cluster import SpectralClustering, ari
from repro.data import synthetic
from repro.distrib import mesh_utils
pts, truth = synthetic.blobs(242, 3, dim=4, spread=0.8, seed=0)
mesh = mesh_utils.local_mesh("rows")
assert mesh_utils.mesh_size(mesh) == 4
est = SpectralClustering(k=3, affinity="ooc-topt", sparsify_t=10, sigma=1.0,
                         seed=0, chunk_size=64, lanczos_steps=96,
                         mesh=mesh).fit(jnp.asarray(pts))
assert ari(truth, np.asarray(est.labels_)) >= 0.95
print("OK")
""", n_devices=4)
    assert "OK" in out


def test_operator_padding_matches_unpadded():
    rng = np.random.RandomState(3)
    pts = rng.randn(50, 3).astype(np.float32)
    plan = JobPlan(n=50, chunk_size=16, t=6, k=2, sigma=1.0)
    graph, _ = engine.build_graph(ArrayChunks(pts, 16), plan)
    op = engine.make_normalized_operator(graph)
    op_pad = engine.make_normalized_operator(graph, pad_to=56)
    assert op_pad.n_pad == 56 and op.n_pad == 50
    v = rng.randn(56).astype(np.float32)
    got = np.asarray(op_pad.matmat(jnp.asarray(v[:, None])))[:, 0]
    ref = np.asarray(op.matmat(jnp.asarray(v[:50, None])))[:, 0]
    np.testing.assert_allclose(got[:50], ref, rtol=1e-5, atol=1e-6)
    assert np.all(got[50:] == 0)                  # pad rows stay null
    A = np.asarray(op_pad.dense())
    np.testing.assert_allclose(A[:50, :50], np.asarray(op.dense()),
                               rtol=1e-5, atol=1e-6)
    # the traced-callback matmat above immortalizes its closure (and so
    # the graph) in jax's dispatch cache: close the shared prefetch pool
    # explicitly, as every non-test operator consumer does
    op.close()


def test_engine_eigh_backend_uses_dense_fallback():
    pts, _ = synthetic.blobs(96, 2, dim=3, spread=0.8, seed=5)
    ooc = SpectralClustering(k=2, affinity="ooc-topt", eigensolver="eigh",
                             sparsify_t=8, sigma=1.0, seed=0,
                             chunk_size=32).fit(jnp.asarray(pts))
    ref = SpectralClustering(k=2, affinity="knn-topt", eigensolver="eigh",
                             sparsify_t=8, sigma=1.0, seed=0).fit(
                                 jnp.asarray(pts))
    assert ari(np.asarray(ref.labels_), np.asarray(ooc.labels_)) >= 0.95


# ---------------------------------------------------------------------------
# chunked readers + plan edge cases
# ---------------------------------------------------------------------------

def test_streaming_kmeans_tolerates_coincident_points():
    # degenerate sample: fewer distinct points than k must not crash the
    # ++ init (d2 goes all-zero -> weight-uniform fallback)
    y = np.repeat(np.array([[0.0, 0.0], [1.0, 1.0]]), 10, axis=0)
    labels, centers = engine.streaming_kmeans(
        lambda c: y, 1, k=5, rounds=5, seed=0)
    assert labels.shape == (20,) and centers.shape == (5, 2)


def test_shard_store_owned_tempdir_removed_on_close():
    store = ShardStore(memory_budget=10)          # own temp dir
    store.put("a", {"x": np.zeros(100)})          # spills immediately
    d = store.spill_dir
    assert os.path.isdir(d)
    store.close()
    assert not os.path.exists(d)


def test_chunk_ranges_boundaries():
    assert chunk_ranges(10, 4) == [(0, 4), (4, 8), (8, 10)]
    assert chunk_ranges(10, 100) == [(0, 10)]    # chunk >= n clamps
    assert chunk_ranges(1, 1) == [(0, 1)]
    with pytest.raises(ValueError):
        chunk_ranges(0, 4)


def test_blob_chunks_deterministic_random_access():
    r = BlobChunks(100, 4, chunk_size=30, seed=7)
    c2a = r[2]
    _ = r[0], r[3], r[1]
    np.testing.assert_array_equal(r[2], c2a)     # pure re-generation
    assert sum(len(r[c]) for c in range(len(r))) == 100
    assert len(r.all_labels()) == 100


def test_array_chunks_matches_source():
    x = np.random.RandomState(0).randn(55, 3).astype(np.float32)
    r = ArrayChunks(x, 20)
    np.testing.assert_array_equal(np.concatenate([r[c] for c in range(3)]), x)
