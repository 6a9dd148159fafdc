"""Core vector-join library (the paper's contribution)."""
from repro.core.graph import (BuildStats, build_index, build_merged_index,
                              exact_knn)
from repro.core.join import (cascade_join_pairs, exact_join_pairs,
                             theta_band, vector_join)
from repro.core.ood import predict_ood
from repro.core.types import (GraphIndex, JoinConfig, JoinResult, JoinStats,
                              TraversalConfig, recall, METHODS, NO_NODE)

__all__ = [
    "BuildStats", "build_index", "build_merged_index", "exact_knn",
    "cascade_join_pairs", "exact_join_pairs", "theta_band", "vector_join",
    "predict_ood",
    "GraphIndex", "JoinConfig", "JoinResult", "JoinStats",
    "TraversalConfig", "recall", "METHODS", "NO_NODE",
]
