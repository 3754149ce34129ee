"""Pallas TPU kernels for the compute hot spots (see DESIGN.md §3).

- `pairwise_argmin`    — nearest-center search (Lloyd / k-means++ / acceptance)
- `d2_update`          — fused D^2 weight maintenance for one new center
- `tree_sep_update`    — MULTITREEOPEN's per-tree weight sweep
- `*_tiles` variants   — same sweeps plus the per-tile weight sums that feed
                         the coarse `TiledSampleTree` heap (the incremental
                         per-center sample-structure update)
- `lsh_bucket_min`     — monotone-LSH nearest-bucket query (Algorithm 4's
                         acceptance test: nearest colliding opened center)
- `lsh_bucket_accept`  — same query + fused acceptance-probability epilogue
- `flash_attention`    — fused online-softmax attention (the memory-roofline
                         lever for the dense train/prefill cells, §Perf)

Each kernel has a `pl.pallas_call` + BlockSpec implementation, a jit'd
wrapper, and a pure-jnp oracle in `ref.py`; tests sweep shapes and dtypes
in interpret mode.
"""

from repro.kernels.ops import (
    LSH_MISS,
    d2_update,
    d2_update_tiles,
    default_interpret,
    lsh_bucket_accept,
    lsh_bucket_min,
    pairwise_argmin,
    split_codes_u64,
    tree_sep_update,
    tree_sep_update_tiles,
)

__all__ = [
    "LSH_MISS",
    "d2_update",
    "d2_update_tiles",
    "default_interpret",
    "lsh_bucket_accept",
    "lsh_bucket_min",
    "pairwise_argmin",
    "split_codes_u64",
    "tree_sep_update",
    "tree_sep_update_tiles",
]
