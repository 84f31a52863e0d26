"""The main-path Pallas kernels compile for a TPU v5e at the widths
``chip_smoke.py`` runs them.

Nothing here runs a kernel: each test lowers one kernel for the first chip
of a described (not attached) ``v5e:2x2`` topology and asserts that the
compiled program holds the Mosaic kernel (``tpu_custom_call``).  This
catches what interpret mode cannot: block shapes the TPU tiling refuses,
and VMEM overuse.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler library, and the test
workers each import this file.
"""
import functools
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (
    block_matvec,
    fused_rbf_matmat as frm,
    kmeans_assign,
    rbf_similarity,
)
from repro.tune.schedule import KERNELS, VMEM_BYTES, resolve

N = 262_144      # the fused fit's n (configs/spectral_paper.PRODUCTION_N)
D = 8            # blob width of the fit and serve phases
B = 8            # block-Lanczos width, and k for the serve embedding
TILE = 256       # fused_rbf_matmat.default_tile at this n
QUERIES = 1024   # ClusterServer batch_rows in the serve phase
CHUNK = 4096     # the out-of-core phase's --chunk-size (one map tile)
EMBED_N = 65_536  # the embedding cell's rows (mteb-embed-d4096)
EMBED_D = 4_096   # their width, in bf16
EMBED_B = 64      # its block-Lanczos width
GRAPH_N = 10_029  # the paper graph's vertices (paper-graph-10k)
GRAPH_WIDTH = 16  # its ELL width: a largest degree of 9 to 16


def _trace_patterns():
    """The benchmark's patterns for the fused kernel's device events
    (``perfbench/trace_reduce.KERNELS["fused_rbf"]``), read from the file
    so that this test needs no package path."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "perfbench", "trace_reduce.py")
    spec = importlib.util.spec_from_file_location("perfbench_trace", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # its dataclass looks itself up
    spec.loader.exec_module(mod)
    return mod.KERNELS["fused_rbf"]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # any failure means there is no compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without the chip: keep it out (the reset
    # drops a cache an earlier test of this process may have opened)
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_topology_is_v5e(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"


@pytest.mark.parametrize("compute_dtype,acc", [("float32", "inplace"),
                                               ("bfloat16", "scratch")])
def test_fused_rbf_matmat_compiles(one_chip, compute_dtype, acc):
    fn = functools.partial(
        frm.fused_rbf_matmat, bm=TILE, bn=TILE, compute_dtype=compute_dtype,
        acc=acc, interpret=False)
    f32 = jnp.float32
    hlo = _compile(lambda x, V, s, r: fn(x, x, V, s, r, r), one_chip,
                   ((N, D), f32), ((N, B), f32), ((), f32), ((N,), f32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("b", [1, EMBED_B, EMBED_B + 1])
def test_d_tiled_fused_rbf_matmat_compiles_for_embeddings(one_chip, b):
    """bf16 rows of width 4,096 at the schedule layer's default tiles:
    the VMEM model admits them, Mosaic compiles them, and the lowered
    kernel carries a name the benchmark's trace reduction finds.  Width
    b + 1 is the degree pass that carries the first block step."""
    shape = dict(n=EMBED_N, m=EMBED_N, d=EMBED_D, b=b, itemsize=2)
    tile = frm.default_tile(EMBED_N, EMBED_D, 2)
    sched, _ = resolve("fused_rbf_matmat", None, bm=tile, bn=tile,
                       interpret=False, **shape)
    assert KERNELS["fused_rbf_matmat"].vmem_model(sched, **shape) \
        <= VMEM_BYTES
    fn = functools.partial(frm.fused_rbf_matmat, bm=sched.bm, bn=sched.bn,
                           bd=sched.bd, acc=sched.acc, interpret=False)
    f32 = jnp.float32
    hlo = _compile(lambda x, V, s, r: fn(x, x, V, s, r, r), one_chip,
                   ((EMBED_N, EMBED_D), jnp.bfloat16), ((EMBED_N, b), f32),
                   ((), f32), ((EMBED_N,), f32))
    assert "tpu_custom_call" in hlo
    assert any(p in hlo for p in _trace_patterns())


def test_fused_nystrom_matmat_compiles(one_chip):
    fn = functools.partial(frm.fused_nystrom_matmat, bm=TILE, bn=TILE,
                           interpret=False)
    f32 = jnp.float32
    hlo = _compile(lambda q, y, Z, s, cs, cv: fn(q, y, Z, s, cs, cv),
                   one_chip, ((QUERIES, D), f32), ((N, D), f32),
                   ((N, B), f32), ((), f32), ((N,), f32), ((N,), f32))
    assert "tpu_custom_call" in hlo


def test_rbf_similarity_compiles(one_chip):
    sched = KERNELS["rbf_similarity"].default
    fn = functools.partial(rbf_similarity.rbf_similarity, bm=sched.bm,
                           bn=sched.bn, interpret=False)
    f32 = jnp.float32
    hlo = _compile(fn, one_chip, ((CHUNK, 2), f32), ((CHUNK, 2), f32),
                   ((), f32))
    assert "tpu_custom_call" in hlo


def test_block_matmat_compiles(one_chip):
    sched = KERNELS["block_matmat"].default
    fn = functools.partial(block_matvec.block_matmat, bm=sched.bm,
                           bn=sched.bn, interpret=False)
    f32 = jnp.float32
    hlo = _compile(fn, one_chip, ((8192, 8192), f32), ((8192, B), f32))
    assert "tpu_custom_call" in hlo


def test_kmeans_assign_compiles_at_schedule_default(one_chip):
    bm = KERNELS["kmeans_assign"].default.bm
    fn = functools.partial(kmeans_assign.kmeans_assign, bm=bm,
                           interpret=False)
    f32 = jnp.float32
    hlo = _compile(fn, one_chip, ((N, D), f32), ((B, D), f32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("b", [1, 8])
def test_graph_pass_gathers_rows_of_eight_lanes(one_chip, b):
    """The sparse graph pass at the paper graph's size gathers rows of at
    least 8 lanes, even for a single Lanczos vector: XLA would shrink a
    zero-padded gather to single values, which the chip gathers about
    three times slower, and the optimization barrier keeps it wide."""
    import re

    from repro.core import laplacian as lp
    f32 = jnp.float32
    hlo = _compile(lp._sparse_matmat, one_chip,
                   ((GRAPH_N, GRAPH_WIDTH), jnp.int32),
                   ((GRAPH_N, GRAPH_WIDTH), f32), ((GRAPH_N,), f32),
                   ((GRAPH_N, b), f32))
    shapes = re.findall(r"= f32\[([\d,]+)\][^ ]* gather\(", hlo)
    assert shapes, "the pass holds no gather"
    for shape in shapes:
        assert [int(d) for d in shape.split(",")] == \
            [GRAPH_N, GRAPH_WIDTH, max(b, 8)], shape
