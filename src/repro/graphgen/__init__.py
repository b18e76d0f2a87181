"""Graph data substrate: generators, eulerizer, partitioner, sampler."""
from .rmat import rmat_graph
from .eulerize import eulerize, eulerian_rmat, largest_component
from .kronecker import eulerian_kronecker, kronecker_graph
from .partition import partition_vertices

__all__ = ["rmat_graph", "eulerize", "eulerian_rmat", "largest_component",
           "kronecker_graph", "eulerian_kronecker", "partition_vertices"]
