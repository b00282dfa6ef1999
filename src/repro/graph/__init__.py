from repro.graph.csr import CSRGraph, edge_cut, within_cut_fraction
from repro.graph.generators import (SBMSpec, CoPurchaseSpec, make_dataset,
                                    stochastic_block_model, copurchase_graph)
from repro.graph.partition import (partition_graph, metis_like_partition,
                                   random_partition, PartitionStats,
                                   PARTITIONER_VERSION, graph_fingerprint,
                                   default_partition_cache_dir)
from repro.graph.datasets import (REAL_DATASETS, load_dataset, cache_root,
                                  dataset_meta)
from repro.graph.normalization import (normalize_dense, normalize_csr,
                                       normalized_dense_block)

__all__ = [
    "CSRGraph", "edge_cut", "within_cut_fraction",
    "SBMSpec", "CoPurchaseSpec", "make_dataset", "stochastic_block_model",
    "copurchase_graph",
    "partition_graph", "metis_like_partition", "random_partition",
    "PartitionStats", "PARTITIONER_VERSION", "graph_fingerprint",
    "default_partition_cache_dir",
    "REAL_DATASETS", "load_dataset", "cache_root", "dataset_meta",
    "normalize_dense", "normalize_csr", "normalized_dense_block",
]
