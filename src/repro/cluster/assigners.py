"""Assigner backends: phase 3 (k-means on the spectral embedding) as
pluggable strategies.

Signature:

    backend(est, Y, valid, key, mesh) -> (labels_pad, centers)

``Y`` is the row-normalized (n_pad, k) embedding, row-sharded over the
mesh and still in the affinity backend's row order; ``labels_pad`` must
match that order (the estimator unpermutes).

Backends:
  lloyd      full distributed Lloyd (paper §4.3.3 MapReduce rounds), in
             the spans ``fit.assign.seed`` (k-means++ seeding) and
             ``fit.assign.lloyd`` (the rounds), each ending when its
             device work has.
  minibatch  Sculley-style mini-batch Lloyd — O(batch) per round instead
             of O(n); the large-n assigner.
  streaming  the engine's chunked mini-batch Lloyd: consumes embedding
             rows chunk by chunk (one chunk = one mini-batch round), the
             phase-3 pairing for the out-of-core ``ooc-topt`` affinity.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.cluster.registry import Registry
from repro.core import kmeans as km
from repro.core.seeding import kmeans_plusplus_init

ASSIGNERS = Registry("assigner")


@ASSIGNERS.register("lloyd")
def lloyd_assigner(est, Y, valid, key, mesh):
    # the seeding distributed_kmeans would do itself, with the same key
    with obs.span("fit.assign.seed"):
        centers0 = jax.block_until_ready(kmeans_plusplus_init(
            jnp.asarray(Y), est.k, key, weights=valid))
    with obs.span("fit.assign.lloyd"):
        labels_pad, state = jax.block_until_ready(km.distributed_kmeans(
            Y, valid, est.k, key, mesh, iters=est.kmeans_iters,
            centers0=centers0))
    return labels_pad, state.centers


@ASSIGNERS.register("minibatch")
def minibatch_assigner(est, Y, valid, key, mesh):
    return km.minibatch_kmeans(jnp.asarray(Y), valid, est.k, key,
                               iters=est.kmeans_iters,
                               batch=est.minibatch_size)


@ASSIGNERS.register("streaming")
def streaming_assigner(est, Y, valid, key, mesh):
    from repro.data.chunked import chunk_ranges
    from repro.engine import streaming_kmeans

    Yh = np.asarray(Y, np.float64)
    vh = np.asarray(valid, np.float64)
    ranges = chunk_ranges(Yh.shape[0], est.chunk_size or 4096)
    labels, centers = streaming_kmeans(
        lambda c: Yh[ranges[c][0]:ranges[c][1]], len(ranges), est.k,
        rounds=est.kmeans_iters, seed=est.seed,
        valid_chunk=lambda c: vh[ranges[c][0]:ranges[c][1]])
    return jnp.asarray(labels), jnp.asarray(centers, Y.dtype)
