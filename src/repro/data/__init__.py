from repro.data.chunked import ArrayChunks, BlobChunks
from repro.data.graph_file import parse_topology, write_topology
from repro.data.points_file import load_points
from repro.data.synthetic import (blobs, lm_batches, rings,
                                  synthetic_graph)
