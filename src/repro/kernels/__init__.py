"""TPU Pallas kernels for the LM substrate's compute hot-spots.

The paper (AARC) has no kernel-level contribution -- these kernels
belong to the *framework* layer the paper's technique configures:

  flash_attention/  causal GQA FlashAttention (online softmax, 128-
                    aligned BlockSpec VMEM tiling, kv-block grid walk)
  ssd_scan/         Mamba2 SSD chunked scan (two-pass: intra-chunk +
                    state-apply kernels around a tiny host scan)
  rmsnorm/          fused residual-add + RMSNorm

Each package: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
public wrapper), ref.py (pure-jnp oracle). Kernels target TPU; CPU CI
validates them in ``interpret=True`` mode against the oracle.
"""
