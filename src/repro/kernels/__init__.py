"""Pallas TPU kernels (validated in interpret mode on CPU):
  flash_attention   sliding-window causal flash attention (long-context path)
  paged_decode      paged flash-decode over the serving KV pools
  population_select O(M) Gumbel-top-d cohort selection
  robust_pipeline   the one Eq.-11 aggregation kernel family: median
                    reference + cosine-gate partials in one streaming pass,
                    gated robust combine in a second, blocked pairwise
                    distances for Krum, cohort axis on the grid.  Streams
                    pytrees leaf-wise (segment-table grid, no flatten
                    concatenate) through a per-leaf block loader — dense
                    leaves, or int8 wire codes dequantized in VMEM — and
                    shard-locally under shard_map (psum'd partials); block
                    size autotuned per backend
"""
