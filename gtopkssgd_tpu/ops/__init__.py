"""Device-side sparse primitives: top-k selection and sparse-set algebra.

TPU-native replacement for the reference's reliance on the `torch.topk` CUDA
kernel (used in compression.py::TopKCompressor.compress of hclhkbu/gtopkssgd)
and on numpy-side sparse merging inside allreducer.py::gtopk_sparse_allreduce.
Everything here is shape-static and jit-friendly.
"""

from gtopkssgd_tpu.ops.topk import (
    topk_abs,
    blockwise_topk_abs,
    approx_topk_abs,
    threshold_topk_abs,
    simrecall_topk_abs,
    twostage_topk_abs,
    bucketize_counts,
    select_topk,
    select_tau,
    select_tau_leaves,
    approx_bin_size,
    bin_maxima,
    k_for_density,
    merge_sparse_sets,
    scatter_add_dense,
    membership_mask,
    SENTINEL_DTYPE,
    TWOSTAGE_OVERSAMPLE,
)

__all__ = [
    "topk_abs",
    "blockwise_topk_abs",
    "approx_topk_abs",
    "threshold_topk_abs",
    "simrecall_topk_abs",
    "twostage_topk_abs",
    "bucketize_counts",
    "select_topk",
    "select_tau",
    "select_tau_leaves",
    "approx_bin_size",
    "bin_maxima",
    "k_for_density",
    "merge_sparse_sets",
    "scatter_add_dense",
    "membership_mask",
    "SENTINEL_DTYPE",
    "TWOSTAGE_OVERSAMPLE",
]
