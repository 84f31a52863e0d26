"""Parser/writer for the paper's §5.1 topology text format:

    t <graph-label>
    v <id> <label>
    e <src> <dst> <weight>

The parsed graph feeds ``SpectralClustering.fit_graph`` as the nonzero
list of its adjacency (:func:`adjacency_sparse`): the paper clusters
graph vertices directly, and the (n, n) matrix is never built.
:func:`adjacency_dense` builds that matrix for callers who want
``affinity="precomputed"``.

The parser streams the file in ~1 MiB line batches and converts each batch
to integers with one numpy tokenize/reshape instead of per-line Python
tuple appends, so multi-GB edge lists parse without a Python-object blowup;
:func:`iter_topology_edges` exposes the same batches as a generator for
consumers (the out-of-core engine) that never want the whole edge array.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Iterator, Optional

import jax
import numpy as np

_READ_HINT = 1 << 20  # ~1 MiB of lines per batch


def _parse_tagged_batch(lines: list[str], width: int,
                        default_last: int) -> np.ndarray:
    """Tokenize same-tag lines ('e i j w' / 'v i l') in one numpy pass.

    ``width`` counts the integer fields; the last one defaults to
    ``default_last`` when omitted.  Falls back to a row loop only for
    batches that mix both arities (rare; the fast reshape handles the
    uniform case).
    """
    if not lines:
        return np.empty((0, width), np.int64)
    toks = np.array("".join(lines).split())
    nrows = len(lines)
    if toks.size == nrows * (width + 1):          # tag + all fields
        return toks.reshape(nrows, width + 1)[:, 1:].astype(np.int64)
    if toks.size == nrows * width:                # tag + fields-but-last
        out = np.empty((nrows, width), np.int64)
        out[:, :-1] = toks.reshape(nrows, width)[:, 1:].astype(np.int64)
        out[:, -1] = default_last
        return out
    rows = []                                     # mixed arities
    for ln in lines:
        parts = ln.split()
        vals = [int(p) for p in parts[1:width + 1]]
        if len(vals) < width - 1:                 # only the last field may
            raise ValueError(                     # be omitted
                f"malformed topology line {ln.strip()!r}: expected "
                f"{width} or {width - 1} fields after the tag")
        vals += [default_last] * (width - len(vals))
        rows.append(vals)
    return np.asarray(rows, np.int64).reshape(-1, width)


def _tag(line: str) -> str:
    """First whitespace-separated token ('' for blank lines) — tags must
    match exactly, so ' v 1 0' still parses and 'edge ...' stays ignored."""
    parts = line.split(None, 1)
    return parts[0] if parts else ""


def _batched_lines(path: str) -> Iterator[tuple[np.ndarray, np.ndarray]]:
    """Yields (vertex (b, 2) [id, label], edge (b, 3) [src, dst, w]) batches."""
    with open(path) as f:
        while True:
            lines = f.readlines(_READ_HINT)
            if not lines:
                return
            v_lines = [ln for ln in lines if _tag(ln) == "v"]
            e_lines = [ln for ln in lines if _tag(ln) == "e"]
            yield (_parse_tagged_batch(v_lines, 2, 0),
                   _parse_tagged_batch(e_lines, 3, 1))


def iter_topology_edges(path: str) -> Iterator[np.ndarray]:
    """Stream (b, 3) int64 [src, dst, weight] edge batches (for consumers
    that never materialize the full edge list)."""
    for _verts, edges in _batched_lines(path):
        if len(edges):
            yield edges


def parse_topology(path: str, with_labels: bool = False):
    """Returns (num_vertices, edges (m, 3) int64 [src, dst, weight]) — and,
    with ``with_labels=True``, a third (num_vertices,) int64 vertex-label
    array (0 for vertices the file never declares)."""
    n = 0
    edge_batches = []
    vert_batches = []
    for verts, edges in _batched_lines(path):
        if len(verts):
            n = max(n, int(verts[:, 0].max()) + 1)
            if with_labels:
                vert_batches.append(verts)
        if len(edges):
            n = max(n, int(edges[:, :2].max()) + 1)
            edge_batches.append(edges)
    all_edges = (np.concatenate(edge_batches) if edge_batches
                 else np.empty((0, 3), np.int64))
    if not with_labels:
        return n, all_edges
    labels = np.zeros(n, np.int64)
    for verts in vert_batches:
        labels[verts[:, 0]] = verts[:, 1]
    return n, all_edges, labels


def write_topology(path: str, n: int, edges: np.ndarray, label: int = 0,
                   vertex_labels: Optional[np.ndarray] = None):
    """Inverse of :func:`parse_topology`: vertex labels round-trip (the old
    writer hardcoded ``v {i} 0``, losing them)."""
    if vertex_labels is None:
        vertex_labels = np.zeros(n, np.int64)
    vertex_labels = np.asarray(vertex_labels, np.int64)
    if vertex_labels.shape != (n,):
        raise ValueError(
            f"vertex_labels must be ({n},), got {vertex_labels.shape}")
    with open(path, "w") as f:
        f.write(f"t # {label}\n")
        for i in range(n):
            f.write(f"v {i} {vertex_labels[i]}\n")
        for i, j, w in np.asarray(edges).reshape(-1, 3):
            f.write(f"e {i} {j} {w}\n")


def adjacency_dense(n: int, edges: np.ndarray, dtype=np.float32) -> np.ndarray:
    A = np.zeros((n, n), dtype)
    A[edges[:, 0], edges[:, 1]] = edges[:, 2]
    A[edges[:, 1], edges[:, 0]] = edges[:, 2]
    np.fill_diagonal(A, 1.0)
    return A


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("cols", "weights"), meta_fields=("nnz",))
@dataclasses.dataclass(frozen=True)
class SparseAdjacency:
    """The nonzeros of a graph's symmetric adjacency with unit
    self-loops, row by row (ELL): row ``i``'s ``s``-th nonzero is
    ``A[i, cols[i, s]] = weights[i, s]``, columns ascending.  Every row
    has ``width`` slots, the fullest row's count rounded up to a
    multiple of 8 (the chip's sublanes); a row's spare slots carry
    weight 0 (and its own column), so graphs of one size whose largest
    degrees are close share one compiled program.  ``nnz`` counts the
    graph's nonzeros.  A pytree: ``jax.device_put`` moves the two arrays
    and keeps ``nnz``."""
    cols: Any
    weights: Any
    nnz: int

    @property
    def n(self) -> int:
        return int(self.cols.shape[0])

    @property
    def width(self) -> int:
        return int(self.cols.shape[1])


def adjacency_sparse(n: int, edges: np.ndarray) -> SparseAdjacency:
    """:func:`adjacency_dense`'s matrix as its nonzeros, built without
    it.  The same semantics: both directions of every edge, a repeated
    pair once with the weight the dense assignment leaves (the last one
    written), and the diagonal 1.0, a self-edge's weight included."""
    edges = np.asarray(edges).reshape(-1, 3)
    src, dst = edges[:, 0].astype(np.int64), edges[:, 1].astype(np.int64)
    # adjacency_dense writes every (src, dst), then every (dst, src):
    # in that order, the last write of each position is the one kept
    rows = np.concatenate([src, dst])
    cols = np.concatenate([dst, src])
    w = np.concatenate([edges[:, 2], edges[:, 2]]).astype(np.float32)
    off = rows != cols
    rows = np.concatenate([rows[off], np.arange(n)])
    cols = np.concatenate([cols[off], np.arange(n)])
    w = np.concatenate([w[off], np.ones(n, np.float32)])
    # np.unique keeps each position's first occurrence in the reversed
    # order (its last write) and sorts by row, then column
    _, first = np.unique((rows * n + cols)[::-1], return_index=True)
    keep = len(rows) - 1 - first
    rows, cols, w = rows[keep], cols[keep], w[keep]
    count = np.bincount(rows, minlength=n)
    width = -(-int(count.max(initial=1)) // 8) * 8
    slot = np.arange(len(rows)) - (np.cumsum(count) - count)[rows]
    ell_cols = np.repeat(np.arange(n, dtype=np.int32)[:, None], width, 1)
    ell_w = np.zeros((n, width), np.float32)
    ell_cols[rows, slot] = cols
    ell_w[rows, slot] = w
    return SparseAdjacency(cols=ell_cols, weights=ell_w, nnz=len(rows))
