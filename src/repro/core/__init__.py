# The paper's primary contribution: parallel spectral clustering
# (similarity -> Lanczos eigenvectors -> k-means), distributed over a
# device mesh via shard_map: the paper's Hadoop workers become the mesh's
# devices, each holding a row block of the similarity.
#
# The public entry point is repro.cluster.SpectralClustering (pluggable
# affinity/eigensolver/assigner backends); the functions re-exported here
# are deprecated shims kept for existing callers.
from repro.core.spectral import (  # noqa: F401
    SpectralConfig,
    SpectralResult,
    fit,
    fit_dense,
    fit_from_similarity,
)
