"""The fused matrix-free affinity stack.

* hypothesis property: the flash-style fused RBF matmat kernel matches the
  materialized reference ``diag(rs) S diag(cs) V`` product — uneven n vs
  tile size, padding rows, f32 and (looser) bf16 compute;
* operator law: the ``fused-rbf`` NormalizedOperator agrees with the
  ``dense`` backend's operator on shared rows, including zero-degree rows;
* estimator/CLI: fused-rbf is selectable end to end and reports the
  matrix-free stats (``matrix_passes`` / ``bytes_streamed``);
* engine routing: the planner sends fits-in-memory-but-dense-doesn't jobs
  to the fused path instead of spilling CSR shards;
* engine prefetch: shard readahead overlaps compute and reports
  ``prefetch_hits``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.cluster import SpectralClustering, ari
from repro.cluster.affinity import AFFINITIES
from repro.data import synthetic
from repro.distrib import mesh_utils
from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# kernel-level property: fused == materialized reference
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.integers(5, 80), st.integers(1, 70), st.integers(1, 6),
       st.integers(1, 9), st.integers(0, 2**16))
def test_fused_matmat_matches_reference_f32(n, m, d, b, seed):
    """<= 1e-4 agreement in f32 at any (n, m) — including shapes far from
    the 32-row tiles used here, so the zero-padded tail rows/cols are
    exercised on both sides of the product."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (n, d))
    y = jax.random.normal(ks[1], (m, d))
    V = jax.random.normal(ks[2], (m, b))
    rs = jax.random.uniform(ks[3], (n,))
    cs = jax.random.uniform(ks[3], (m,), minval=0.1)
    got = np.asarray(ops.fused_rbf_matmat(x, y, V, 0.9, rs, cs,
                                          bm=32, bn=32, interpret=True))
    want = np.asarray(ref.fused_rbf_matmat(x, y, V, 0.9, rs, cs))
    assert got.shape == (n, b)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@settings(max_examples=6, deadline=None)
@given(st.integers(8, 70), st.integers(1, 5), st.integers(0, 2**16))
def test_fused_matmat_bf16_loose_bound(n, b, seed):
    """bf16 compute perturbs only the tile entries (accumulation stays
    f32): the error bound is the bf16 epsilon times the row mass, far
    looser than f32 but still a few decimal digits."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    x = jax.random.normal(ks[0], (n, 4))
    V = jax.random.normal(ks[1], (n, b))
    ones = jnp.ones((n,))
    got = np.asarray(ops.fused_rbf_matmat(x, x, V, 1.0, ones, ones, bm=32,
                                          bn=32, compute_dtype="bf16",
                                          interpret=True))
    want = np.asarray(ref.fused_rbf_matmat(x, x, V, 1.0, ones, ones))
    scale = np.abs(want).max() + 1.0
    np.testing.assert_allclose(got / scale, want / scale, atol=4e-2)


def test_compute_dtype_resolution_and_validation():
    from repro.kernels.fused_rbf_matmat import resolve_compute_dtype
    assert resolve_compute_dtype(None) == jnp.float32
    assert resolve_compute_dtype("float32") == jnp.float32
    assert resolve_compute_dtype("bf16") == jnp.bfloat16
    assert resolve_compute_dtype(jnp.bfloat16) == jnp.bfloat16
    with pytest.raises(ValueError, match="compute_dtype"):
        resolve_compute_dtype("fp8")
    with pytest.raises(ValueError, match="compute_dtype"):
        SpectralClustering(2, affinity="fused-rbf", compute_dtype="int8")


# ---------------------------------------------------------------------------
# operator law: fused-rbf == dense backend (padding + zero-degree rows)
# ---------------------------------------------------------------------------

def _blob_x(n=97, d=4):
    pts, _ = synthetic.blobs(n, 3, dim=d, spread=0.8, seed=0)
    return jnp.asarray(pts)


def test_fused_operator_matches_dense_operator():
    x = _blob_x()
    n = int(x.shape[0])
    mesh = mesh_utils.local_mesh("rows")
    est = SpectralClustering(3, sigma=1.0)
    op_f = AFFINITIES.get("fused-rbf")(est, x, jnp.asarray(1.0), mesh)
    op_d = AFFINITIES.get("dense")(est, x, jnp.asarray(1.0), mesh)
    assert op_f.n == n and op_f.n_pad % 128 == 0      # tile-padded
    V = jax.random.normal(jax.random.PRNGKey(1), (op_f.n_pad, 4))
    got = np.asarray(op_f.matmat(V))
    want = np.asarray(op_d.matmat(V[:op_d.n_pad]))
    np.testing.assert_allclose(got[:n], want[:n], rtol=1e-4, atol=1e-4)
    # padding rows live in the operator's null space
    assert np.abs(got[n:]).max() < 1e-5
    # and the eigh-oracle materializer agrees on the shared block
    A_f = np.asarray(op_f.materialize())
    A_d = np.asarray(op_d.materialize())
    np.testing.assert_allclose(A_f[:n, :n], A_d[:n, :n],
                               rtol=1e-4, atol=1e-4)


def test_fused_operator_zero_degree_and_isolated_rows():
    """Zero-degree (padding) rows must be pinned out of the S-term exactly
    like the dense backend's, and an isolated outlier point (off-diagonal
    similarity underflows to 0, degree = the RBF self-similarity 1) must
    reduce to the same detached 2x-identity row on both paths."""
    x = np.array(_blob_x(40))
    x[7] = 1e4                    # off-diagonal similarity underflows to 0
    x = jnp.asarray(x)
    mesh = mesh_utils.local_mesh("rows")
    est = SpectralClustering(3, sigma=1.0)
    op_f = AFFINITIES.get("fused-rbf")(est, x, jnp.asarray(1.0), mesh)
    op_d = AFFINITIES.get("dense")(est, x, jnp.asarray(1.0), mesh)
    # padding rows: degree 0 -> D^{-1/2} pinned to 0 (masked_inv_sqrt)
    assert np.abs(np.asarray(op_f.inv_sqrt[40:])).max() == 0.0
    assert float(op_f.valid[40:].max()) == 0.0
    V = jax.random.normal(jax.random.PRNGKey(2), (op_f.n_pad, 3))
    got = np.asarray(op_f.matmat(V))
    want = np.asarray(op_d.matmat(V[:op_d.n_pad]))
    np.testing.assert_allclose(got[:40], want[:40], rtol=1e-4, atol=1e-4)
    # the detached point sees only its self-similarity: A row = 2 * I row
    np.testing.assert_allclose(got[7], 2.0 * np.asarray(V)[7], rtol=1e-4,
                               atol=1e-4)
    assert np.abs(got[40:]).max() < 1e-5        # padding stays null


# ---------------------------------------------------------------------------
# estimator + CLI
# ---------------------------------------------------------------------------

def test_estimator_fused_matches_dense_labels():
    pts, _ = synthetic.blobs(200, 3, dim=4, spread=0.8, seed=0)
    x = jnp.asarray(pts)
    kw = dict(sigma=1.0, seed=0, lanczos_steps=96)
    dense = SpectralClustering(3, affinity="dense", **kw).fit(x)
    fused = SpectralClustering(3, affinity="fused-rbf", **kw).fit(x)
    assert ari(np.asarray(dense.labels_), np.asarray(fused.labels_)) >= 0.99
    np.testing.assert_allclose(np.asarray(dense.eigenvalues_),
                               np.asarray(fused.eigenvalues_), atol=1e-3)
    stats = fused.info_["engine"]               # operator build stats
    assert stats["matrix_passes"] >= 96         # degree pass + lanczos
    assert stats["bytes_streamed"] > 0
    assert stats["affinity_peak_bytes"] < stats["dense_equiv_bytes"]

    bf16 = SpectralClustering(3, affinity="fused-rbf", compute_dtype="bf16",
                              **kw).fit(x)
    assert ari(np.asarray(dense.labels_), np.asarray(bf16.labels_)) >= 0.99
    assert bf16.info_["engine"]["compute_dtype"] == "bfloat16"


@pytest.mark.parametrize("affinity", ["fused-rbf", "dense"])
def test_estimator_matches_float64_numpy_reference(affinity):
    """The system against the plain float64 reference (dense RBF, eigh,
    Lloyd) on the blob shape the chip smoke runs at n=262,144."""
    from repro.cluster.reference import spectral_reference
    pts, truth = synthetic.blobs(512, 8, dim=8, spread=0.6, seed=0)
    est = SpectralClustering(8, affinity=affinity,
                             eigensolver="block-lanczos").fit(
        jnp.asarray(pts))
    labels, evals = spectral_reference(pts, 8, float(est.sigma_))
    assert ari(truth, labels) == 1.0
    assert ari(labels, np.asarray(est.labels_)) >= 0.99
    np.testing.assert_allclose(np.asarray(est.eigenvalues_), evals,
                               atol=1e-4)


def test_eigh_reports_matrix_passes():
    pts, _ = synthetic.blobs(48, 2, dim=3, seed=1)
    est = SpectralClustering(2, affinity="dense", eigensolver="eigh",
                             sigma=1.0).fit(jnp.asarray(pts))
    # the dense factorization sweeps the padded matrix ~n_pad times
    assert est.info_["matrix_passes"] == est.info_["n_pad"]


def test_cli_fused_rbf_selectable(capsys):
    from repro.launch import spectral_job
    spectral_job.main(["--blobs", "80", "--k", "3", "--affinity", "fused-rbf",
                       "--compute-dtype", "bf16", "--eigensolver",
                       "block-lanczos", "--block-size", "4"])
    out = capsys.readouterr().out
    assert "affinity=fused-rbf" in out
    assert "compute_dtype=bfloat16" in out
    assert "bytes_streamed=" in out


# ---------------------------------------------------------------------------
# engine routing + prefetch
# ---------------------------------------------------------------------------

def test_route_path_budget_rules():
    from repro import engine
    from repro.engine.plan import route_path
    # dense fits the budget -> classic ooc (nothing would spill anyway)
    small = engine.JobPlan(n=64, chunk_size=32, path="auto",
                           memory_budget=1 << 20)
    assert route_path(small, d=4) == "ooc"
    # points fit, dense S doesn't -> fused
    mid = engine.JobPlan(n=2048, chunk_size=512, path="auto",
                         memory_budget=1 << 20)       # 1 MiB << 16 MiB S
    assert route_path(mid, d=4) == "fused"
    # not even the points fit -> ooc shards
    big = engine.JobPlan(n=2048, chunk_size=512, path="auto",
                         memory_budget=8 * 1024)
    assert route_path(big, d=4) == "ooc"
    # no budget -> historical in-RAM ooc; forced paths always win
    assert route_path(engine.JobPlan(n=2048, path="auto"), d=4) == "ooc"
    forced = engine.JobPlan(n=64, path="fused", memory_budget=1 << 20)
    assert route_path(forced, d=4) == "fused"
    with pytest.raises(ValueError, match="path"):
        engine.JobPlan(n=10, path="dense")


def test_run_job_routes_to_fused_and_clusters():
    from repro import engine
    from repro.data.chunked import BlobChunks
    n = 768
    reader = BlobChunks(n, 3, chunk_size=256, dim=4, spread=0.8, seed=0)
    budget = 256 * 1024            # points 12 KiB fit; dense S 2.25 MiB not
    plan = engine.JobPlan(n=n, chunk_size=256, k=3, sigma=1.0, seed=0,
                          path="auto", memory_budget=budget,
                          lanczos_steps=96, kmeans_rounds=30)
    res = engine.run_job(plan, reader)
    assert res.stats["path"] == "fused"
    assert res.graph is None                       # no CSR shards built
    assert res.stats["matrix_passes"] > 0
    assert res.stats["affinity_peak_bytes"] <= budget
    assert ari(reader.all_labels(), res.labels) >= 0.95


@pytest.mark.parametrize("error,reroutes", [
    (jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: out of HBM"), True),
    (MemoryError(), True),
    (jax.errors.JaxRuntimeError(
        "INVALID_ARGUMENT: Mosaic failed to compile TPU kernel"), False),
    (jax.errors.JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem while "
        "allocating on stack for %fused_rbf_matmat"), False),
    (NotImplementedError("unsupported lowering"), False),
])
def test_auto_fused_reroutes_only_out_of_memory(monkeypatch, error,
                                                reroutes):
    """An auto-routed fused job that runs out of host or device memory
    degrades to the ooc pipeline; a kernel that fails to lower or
    compile, VMEM overflow included, propagates."""
    from repro import engine, obs
    from repro.data.chunked import BlobChunks
    from repro.engine import runner

    def fail(plan, reader):
        raise error

    monkeypatch.setattr(runner, "_run_fused", fail)
    n = 256
    reader = BlobChunks(n, 3, chunk_size=64, dim=4, spread=0.8, seed=0)
    plan = engine.JobPlan(n=n, chunk_size=64, t=8, k=3, sigma=1.0, seed=0,
                          path="auto", memory_budget=64 * 1024,
                          lanczos_steps=48, kmeans_rounds=10)
    assert engine.plan.route_path(plan, 4) == "fused"
    before = obs.counter("engine.path_fallbacks").value
    if reroutes:
        res = engine.run_job(plan, reader)
        assert res.stats["path"] == "ooc"
        assert res.stats["path_fallback"].startswith("fused->ooc")
        assert obs.counter("engine.path_fallbacks").value == before + 1
    else:
        with pytest.raises(type(error)):
            engine.run_job(plan, reader)
        assert obs.counter("engine.path_fallbacks").value == before


def test_shard_prefetch_hits_and_stats(tmp_path):
    from repro import engine
    from repro.data.chunked import ArrayChunks
    pts, _ = synthetic.blobs(200, 3, dim=4, spread=0.8, seed=0)
    plan = engine.JobPlan(n=200, chunk_size=25, t=8, k=3, sigma=1.0,
                          memory_budget=16 * 1024, spill_dir=str(tmp_path))
    graph, _ = engine.build_graph(ArrayChunks(pts, 25), plan)
    assert graph.stats_snapshot()["store_bytes_spilled"] > 0
    V = np.random.RandomState(0).randn(200, 4).astype(np.float32)
    got = graph.matmat(V)
    np.testing.assert_allclose(got, graph.to_dense() @ V, rtol=1e-4,
                               atol=1e-5)          # prefetch changes nothing
    snap = graph.stats_snapshot()
    assert snap["prefetch_hits"] + snap["prefetch_misses"] == 8
    # the cross-call warm start overlaps the CALLER's work between passes
    # (the eigensolver's Rayleigh-Ritz step); emulate that gap so the
    # shard-0 readahead deterministically lands before the next call
    import time
    for _ in range(3):
        time.sleep(0.05)
        graph.matmat(V)
    assert graph.stats_snapshot()["prefetch_hits"] > 0


def test_prefetch_stats_reach_estimator_info(tmp_path):
    pts, _ = synthetic.blobs(160, 3, dim=4, spread=0.8, seed=0)
    est = SpectralClustering(k=3, affinity="ooc-topt", sparsify_t=8,
                             sigma=1.0, seed=0, chunk_size=40,
                             lanczos_steps=48,
                             memory_budget=16 * 1024,
                             spill_dir=str(tmp_path)).fit(jnp.asarray(pts))
    eng = est.info_["engine"]
    # hit/miss accounting is plumbed end to end; whether a toy problem's
    # inter-pass gap beats the shard-load latency is timing-dependent, so
    # hits > 0 is asserted where timing is controlled (the direct graph
    # test above and the fused_sweep benchmark)
    assert eng["prefetch_hits"] + eng["prefetch_misses"] > 0
    assert eng["store_bytes_spilled"] > 0
