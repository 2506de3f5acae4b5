"""DRIM-ANN core: cluster-based ANNS engine (IVF-PQ/OPQ) for TPU meshes.

Public API re-exports — the stable surface examples and tests use.
"""

from repro.core.kmeans import kmeans, kmeans_multi, l2_sq, assign_chunked
from repro.core.pq import (PQCodebook, OPQCodebook, train_pq, train_opq,
                           encode_pq, decode_pq)
from repro.core.ivf import IVFPQIndex, PaddedClusters, build_ivfpq, pad_clusters
from repro.core.mutable_index import Index, MutationStats
from repro.core.adc import (build_lut, build_lut_batch, build_lut_direct,
                            scan_codes, scan_codes_onehot, adc_distances,
                            QuantizedLUT, quantize_lut, dequantize_lut,
                            scan_codes_quantized,
                            scan_codes_onehot_quantized,
                            adc_distances_quantized)
from repro.core.multiplierless import (make_square_lut, square_via_lut,
                                       quantize_codebook,
                                       build_lut_multiplierless,
                                       build_lut_int_reference,
                                       scan_codes_int, quantize_residual)
from repro.core.dpq import train_dpq
from repro.core.topk import topk_smallest, merge_topk
from repro.core.search import (SearchParams, search_ivfpq, exact_search,
                               recall_at_k, cluster_locate)

__all__ = [
    "kmeans", "kmeans_multi", "l2_sq", "assign_chunked",
    "PQCodebook", "OPQCodebook", "train_pq", "train_opq", "encode_pq",
    "decode_pq",
    "IVFPQIndex", "PaddedClusters", "build_ivfpq", "pad_clusters",
    "Index", "MutationStats",
    "build_lut", "build_lut_batch", "build_lut_direct", "scan_codes",
    "scan_codes_onehot", "adc_distances",
    "QuantizedLUT", "quantize_lut", "dequantize_lut",
    "scan_codes_quantized", "scan_codes_onehot_quantized",
    "adc_distances_quantized",
    "make_square_lut", "square_via_lut", "quantize_codebook",
    "build_lut_multiplierless", "build_lut_int_reference", "scan_codes_int",
    "quantize_residual",
    "train_dpq",
    "topk_smallest", "merge_topk",
    "SearchParams", "search_ivfpq", "exact_search", "recall_at_k",
    "cluster_locate",
]
