"""TPU Pallas kernels for the FUnc-SNE framework.

Each kernel package provides:
  kernel.py -- ``pl.pallas_call`` + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    -- jit'd public wrapper with backend selection
               ('pallas' on TPU, 'interpret' for CPU validation, 'xla' pure-jnp)
  ref.py    -- pure-jnp oracle used by tests and as the XLA fallback

Kernels (the compute hot-spots the paper optimises on GPU, re-tiled for TPU):
  pairwise_sqdist  -- blocked ||q - c||^2 for KNN candidate scoring (HD hot spot)
  knn_merge        -- merge-fused refinement: candidate scoring + in-register
                      dedup + stable top-K merge in one launch (no selection
                      epilogue, no top_k sort, no (B, C, K) dedup broadcast)
  ne_forces        -- fused variable-tail attraction/repulsion force evaluation
  flash_attention  -- causal GQA flash attention (LM prefill hot spot)

The two NE kernels each come in two flavours: the pre-gather form takes
already-gathered (B, C, M) / (B, K, d) operands, and the gather-fused form
(``*_gather``, the only form the step runs) takes *indices* and DMAs only
the needed rows in-kernel
(source matrix stays in HBM/ANY, read as lane-padded rows; index slabs
staged into SMEM by the pipeline).  At d <= 4, when the embedding fits
a VMEM budget, ``ne_forces_gather``'s edge mode and the candidate-fused
merge hold it packed in VMEM for the launch and read rows on-core
instead (``pairwise_sqdist.kernel.row_source``); the merge also holds
its activity flags there (``knn_merge.ops.merge_sources``).
``ne_forces_gather`` additionally offers a scatter-fused output mode
(``scatter_fused=True``): per-edge forces and their symmetric
reactions are index-binned in-kernel into
per-segment (N, d) displacement fields (reference on
``jax.ops.segment_sum``), so the per-edge tensors never round-trip
through HBM.  The edge-emitting force epilogue is the default and
scatter fusion (funcsne §Perf H14) an option.  The pre-gather forms and
the operand-taking ``knn_merge_pallas`` no longer run in the step; they
serve tests, ``core/baselines.py`` and the benchmark scripts.

``backend.resolve`` maps ``backend="auto"`` to 'pallas' on a TPU and
'xla' elsewhere for every ``ops.py`` wrapper.
"""
