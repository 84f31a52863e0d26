"""The kernel schedule layer + autotuner (repro.tune).

Contracts:
  * Schedule round-trips through its dict form; unknown fields and bad
    dtypes fail loudly.
  * Legality checks fire BEFORE lowering: non-sublane tiles, lane-width
    violations on the compiled path, col-major on reducing kernels,
    scratch on non-reducing kernels, VMEM-budget blowouts — each a
    one-line ScheduleError naming the kernel.
  * schedule=None through the public ops wrappers is bit-for-bit the old
    keyword-tile behavior; any legal explicit schedule matches the
    default within 1e-4 (f32).
  * The JSON cache round-trips schedules per (kernel, shape bucket,
    device, dtype), tolerates corrupt files, merges on write, excludes
    the matmat width b from its keys, and honors REPRO_SCHEDULE_CACHE.
  * autotune() always includes the default among its candidates (tuned
    <= default by construction), persists the winner, and short-circuits
    on a cache hit; schedule="auto" consumes the cached winner.
  * The estimator accepts schedule=, records what ran in info_, and
    persists the setting through save/load.
"""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.block_matvec import check_tiles
from repro.tune import (KERNELS, Schedule, ScheduleCache, ScheduleError,
                        autotune, bucket, cache_key, candidates,
                        default_cache, resolve, spec)


def _pts(n, d, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(n, d)
                       .astype(np.float32))


# ---------------------------------------------------------------------------
# Schedule value semantics


def test_schedule_roundtrip():
    s = Schedule(bm=256, bn=128, compute_dtype="bfloat16", acc="scratch")
    assert Schedule.from_dict(s.to_dict()) == s
    # None fields are dropped from the dict form
    assert "interpret" not in Schedule(bm=8).to_dict()


def test_schedule_rejects_unknown_fields_and_bad_dtype():
    with pytest.raises(ScheduleError, match="unknown schedule field"):
        Schedule.from_dict({"bm": 128, "tile_rows": 4})
    with pytest.raises(ScheduleError, match="compute_dtype"):
        Schedule.from_dict({"compute_dtype": "fp8"})
    # short dtype aliases normalize
    assert Schedule.from_dict({"compute_dtype": "bf16"}).compute_dtype \
        == "bfloat16"


def test_every_kernel_default_is_legal():
    for name, sp in KERNELS.items():
        sp.check(sp.default.replace(interpret=True))


# ---------------------------------------------------------------------------
# Legality checks (satellite: clear errors instead of Pallas lowering blowups)


def test_check_tiles_rejects_non_sublane_multiples():
    with pytest.raises(ValueError, match="multiple of 8"):
        check_tiles(30, 64, interpret=True)
    with pytest.raises(ValueError, match="multiple of 8"):
        check_tiles(64, 12, interpret=True)
    check_tiles(32, 64, interpret=True)        # legal in interpret mode


def test_check_tiles_enforces_lane_width_when_compiled():
    # bn is the reduction/lane-side tile: 64 is sublane-legal but not a
    # lane multiple, so the compiled path must refuse it with a clear
    # message (the old behavior was an opaque Mosaic lowering error)
    with pytest.raises(ValueError, match="lane width"):
        check_tiles(128, 64, interpret=False)
    check_tiles(128, 64, interpret=True)


def test_ops_block_matmat_bad_tile_is_clear_error():
    A, V = _pts(64, 64), _pts(64, 4, seed=1)
    with pytest.raises(ScheduleError, match="block_matmat.*bm=30"):
        ops.block_matmat(A, V, schedule=Schedule(bm=30, bn=32))


def test_colmajor_illegal_for_reducing_kernels():
    with pytest.raises(ScheduleError, match="col-major"):
        spec("block_matmat").check(
            Schedule(bm=8, bn=8, grid_order="col-major", interpret=True))
    # ...but legal for the write-once rbf_similarity grid
    spec("rbf_similarity").check(
        Schedule(bm=8, bn=8, grid_order="col-major", interpret=True))


def test_scratch_illegal_for_nonreducing_kernels():
    with pytest.raises(ScheduleError, match="scratch"):
        spec("rbf_similarity").check(
            Schedule(bm=8, bn=8, acc="scratch", interpret=True))


def test_compute_dtype_only_on_fused_kernels():
    with pytest.raises(ScheduleError, match="compute_dtype"):
        spec("block_matmat").check(
            Schedule(bm=8, bn=8, compute_dtype="bfloat16", interpret=True))


def test_vmem_budget_rejects_giant_tiles():
    with pytest.raises(ScheduleError, match="VMEM"):
        spec("rbf_similarity").check(
            Schedule(bm=4096, bn=4096, interpret=True),
            n=8192, m=8192, d=64)


def test_kmeans_assign_has_no_bn():
    with pytest.raises(ScheduleError, match="no bn"):
        spec("kmeans_assign").check(
            Schedule(bm=512, bn=64, interpret=True))


def test_resolve_counts_interpret_mode():
    """Every resolution is counted by kernel and interpret mode, so a chip
    run can assert that no kernel fell back to the interpreter."""
    from repro import obs
    key = "tune.resolved{interpret=%s,kernel=block_matmat}"
    before = {m: obs.counter(key % m).value for m in (True, False)}
    resolve("block_matmat", None, interpret=True, n=256, m=512, b=8)
    resolve("block_matmat", None, interpret=False, n=256, m=512, b=8)
    resolve("block_matmat", None, interpret=False, n=256, m=512, b=8)
    assert obs.counter(key % True).value - before[True] == 1
    assert obs.counter(key % False).value - before[False] == 2


# ---------------------------------------------------------------------------
# Schedule-aware entry points: default equivalence


def test_schedule_none_is_bitwise_default():
    x, y = _pts(100, 6), _pts(72, 6, seed=1)
    a = ops.rbf_similarity(x, y, 1.3)
    b = ops.rbf_similarity(x, y, 1.3, schedule=None)
    c = ops.rbf_similarity(x, y, 1.3, schedule="default")
    assert (np.asarray(a) == np.asarray(b)).all()
    assert (np.asarray(a) == np.asarray(c)).all()


def test_explicit_schedules_match_reference():
    x, y, V = _pts(96, 5), _pts(80, 5, seed=1), _pts(80, 4, seed=2)
    want = np.asarray(ref.rbf_similarity(x, y, 0.9)) @ np.asarray(V)
    for s in (Schedule(bm=32, bn=32),
              Schedule(bm=64, bn=16, acc="scratch"),
              Schedule(bm=16, bn=48, compute_dtype="f32")):
        got = np.asarray(ops.fused_rbf_matmat(x, y, V, 0.9, schedule=s))
        np.testing.assert_allclose(got, want, atol=1e-4)


def test_grid_order_swap_is_exact():
    x, y = _pts(64, 4), _pts(96, 4, seed=1)
    a = ops.rbf_similarity(x, y, 1.1, schedule=Schedule(bm=16, bn=32))
    b = ops.rbf_similarity(
        x, y, 1.1, schedule=Schedule(bm=16, bn=32, grid_order="col-major"))
    assert (np.asarray(a) == np.asarray(b)).all()


def test_partial_schedule_inherits_call_site_defaults():
    s, source = resolve("fused_rbf_matmat", Schedule(compute_dtype="bf16"),
                        bm=128, bn=128, n=256, m=256, d=8, b=8)
    assert source == "explicit"
    assert (s.bm, s.bn, s.compute_dtype) == (128, 128, "bfloat16")
    assert s.interpret is not None      # auto-detected


# ---------------------------------------------------------------------------
# Persistent cache


def test_bucket_rounds_to_next_pow2():
    assert [bucket(v) for v in (1, 2, 3, 1000, 1024, 1025)] \
        == [1, 2, 4, 1024, 1024, 2048]


def test_cache_roundtrip_and_bucketing(tmp_path):
    c = ScheduleCache(str(tmp_path / "sched.json"))
    s = Schedule(bm=256, bn=128, acc="scratch")
    c.put("block_matmat", s, n=1000, m=1000, wall_us=12.5)
    # same bucket (1024) regardless of exact n/m; b is not in the key
    got = c.get("block_matmat", n=700, m=513, b=99)
    assert got == s
    assert c.get("block_matmat", n=5000, m=5000) is None
    assert c.stats == {"hits": 1, "misses": 1, "puts": 1}
    rec = c.entry("block_matmat", n=1024, m=1024)
    assert rec["wall_us"] == 12.5


def test_cache_key_excludes_batch_width():
    k1 = cache_key("block_matmat", device="cpu", n=100, m=100, b=1)
    k2 = cache_key("block_matmat", device="cpu", n=100, m=100, b=64)
    assert k1 == k2
    with pytest.raises(ValueError, match="missing"):
        cache_key("block_matmat", device="cpu", n=100)


def test_cache_tolerates_corrupt_and_foreign_files(tmp_path):
    p = tmp_path / "sched.json"
    p.write_text("{ not json")
    c = ScheduleCache(str(p))
    assert c.get("block_matmat", n=64, m=64) is None
    c.put("block_matmat", Schedule(bm=64, bn=128), n=64, m=64)
    assert c.get("block_matmat", n=64, m=64) is not None
    # a future-version file reads as empty, not as an error
    p.write_text(json.dumps({"version": 999, "entries": {"x": {}}}))
    assert c.keys() == []


def test_cache_write_is_atomic_and_merges(tmp_path):
    p = str(tmp_path / "sched.json")
    a, b = ScheduleCache(p), ScheduleCache(p)
    a.put("block_matmat", Schedule(bm=64, bn=128), n=64, m=64)
    b.put("rbf_similarity", Schedule(bm=32, bn=128), n=64, m=64, d=8)
    # second writer re-read before merging: both entries survive
    assert len(ScheduleCache(p).keys()) == 2
    assert not [f for f in os.listdir(tmp_path) if ".tmp" in f]


def test_default_cache_follows_env_var(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_SCHEDULE_CACHE", str(tmp_path / "env.json"))
    assert default_cache().path == str(tmp_path / "env.json")


# ---------------------------------------------------------------------------
# "auto" resolution + autotuner


def test_auto_miss_falls_back_to_default(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_SCHEDULE_CACHE", str(tmp_path / "none.json"))
    s, source = resolve("block_matmat", "auto", bm=256, bn=512,
                        n=64, m=64, b=4)
    assert source == "auto-default"
    assert (s.bm, s.bn) == (256, 512)


def test_auto_hit_uses_cached_schedule(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_SCHEDULE_CACHE", str(tmp_path / "c.json"))
    default_cache().put("block_matmat", Schedule(bm=64, bn=128), n=64, m=64)
    s, source = resolve("block_matmat", "auto", bm=256, bn=512,
                        n=64, m=64, b=4)
    assert source == "cache"
    assert (s.bm, s.bn) == (64, 128)
    A, V = _pts(64, 64), _pts(64, 4, seed=1)
    got = ops.block_matmat(A, V, schedule="auto")
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(A) @ np.asarray(V), atol=1e-4)


def test_candidates_include_default_first():
    cands = candidates("block_matmat", quick=True, n=512, m=512, b=8)
    assert cands[0] == spec("block_matmat").default
    assert len(cands) > 1
    assert len(set(cands)) == len(cands)


def test_autotune_quick_writes_cache_and_hits(tmp_path):
    c = ScheduleCache(str(tmp_path / "tuned.json"))
    rep = autotune("block_matmat", 128, b=4, cache=c, quick=True)
    assert not rep["cache_hit"]
    assert rep["best_us"] <= rep["default_us"] + 1e-9
    assert rep["rows"] and all("wall_us" in r for r in rep["rows"])
    assert c.get("block_matmat", n=128, m=128) is not None
    rep2 = autotune("block_matmat", 128, b=4, cache=c, quick=True)
    assert rep2["cache_hit"] and rep2["best"] == rep["best"]


# ---------------------------------------------------------------------------
# Estimator wiring


def test_estimator_validates_schedule_eagerly():
    from repro.cluster import SpectralClustering
    with pytest.raises(ScheduleError):
        SpectralClustering(3, schedule={"bogus_field": 1})
    SpectralClustering(3, schedule="auto")      # accepted


def test_estimator_records_schedule_in_info(tmp_path, monkeypatch):
    from repro.cluster import SpectralClustering
    from repro.data import synthetic

    pts, _ = synthetic.blobs(96, 3, dim=4, spread=0.6, seed=0)
    sched = {"bm": 32, "bn": 32}
    est = SpectralClustering(3, affinity="fused-rbf", sigma=1.0, seed=0,
                             lanczos_steps=24, schedule=sched)
    est.fit(jnp.asarray(pts))
    rec = est.info_["schedule"]
    assert rec["source"] == "explicit"
    assert rec["value"]["bm"] == 32
    assert est.info_["engine"]["schedule"]["bm"] == 32
    # transform over the fused path records its serving-side schedule
    est.transform_path = "fused"
    est.transform(jnp.asarray(pts[:16]))
    assert est.info_["transform"]["schedule"]["bm"] == 32


def test_estimator_auto_consumes_tuned_cache(tmp_path, monkeypatch):
    from repro.cluster import SpectralClustering
    from repro.data import synthetic

    monkeypatch.setenv("REPRO_SCHEDULE_CACHE", str(tmp_path / "t.json"))
    pts, _ = synthetic.blobs(96, 3, dim=4, spread=0.6, seed=0)
    n = 96
    default_cache().put("fused_rbf_matmat", Schedule(bm=32, bn=32),
                        n=n, m=n, d=4)
    est = SpectralClustering(3, affinity="fused-rbf", sigma=1.0, seed=0,
                             lanczos_steps=24, schedule="auto")
    est.fit(jnp.asarray(pts))
    rec = est.info_["schedule"]
    assert rec["source"] == "cache"
    assert rec["value"]["bm"] == 32


def test_schedule_survives_save_load(tmp_path):
    from repro.cluster import SpectralClustering
    from repro.data import synthetic

    pts, _ = synthetic.blobs(64, 2, dim=4, spread=0.6, seed=0)
    est = SpectralClustering(2, affinity="fused-rbf", sigma=1.0, seed=0,
                             lanczos_steps=16,
                             schedule=Schedule(bm=32, bn=32))
    est.fit(jnp.asarray(pts))
    est.save(str(tmp_path / "model"))
    est2 = SpectralClustering.load(str(tmp_path / "model"))
    assert est2.schedule == {"bm": 32, "bn": 32,
                             "grid_order": "row-major", "acc": "inplace"}
    q = jnp.asarray(pts[:8] + 0.01)
    assert (np.asarray(est.predict(q)) == np.asarray(est2.predict(q))).all()
