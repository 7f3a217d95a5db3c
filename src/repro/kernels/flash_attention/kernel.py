"""Causal GQA FlashAttention — TPU Pallas kernel.

Design (TPU-native, not a CUDA port):
  * grid = (batch, q_heads, num_q_blocks, num_kv_blocks); the kv-block
    dim is minor-most, so on TPU it iterates sequentially per core and
    the fp32 online-softmax accumulators live in VMEM *scratch* that
    persists across kv steps (the TPU analogue of a CUDA thread-block's
    shared-memory accumulator).
  * BlockSpecs tile q/o to (block_q, head_dim) and k/v to
    (block_kv, head_dim) VMEM windows; head_dim is the 128-lane minor
    axis and block sizes are multiples of 128 for MXU alignment.
  * GQA is folded into the k/v index_map (q-head -> kv-head), so no
    head-replication traffic ever leaves HBM.
  * Causality: fully-masked kv blocks are skipped via ``pl.when``
    (predication — the TPU grid cannot early-exit), diagonal blocks get
    an in-register triangular mask.

The fp32 softmax accumulators give the same numerics as the XLA
reference up to one ulp-level reduction-order difference.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, block_q: int, block_kv: int, causal: bool,
                  num_kv_blocks: int):
    iq = pl.program_id(2)
    ikv = pl.program_id(3)

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    kv_start = ikv * block_kv

    # a causal block is live unless every key is strictly in the future
    live = jnp.logical_or(not causal,
                          kv_start <= q_start + block_q - 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                # (bkv, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bkv)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_kv), 0)
            cols = kv_start + jax.lax.broadcasted_iota(jnp.int32,
                                                       (block_q, block_kv), 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + \
            jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))
        m_scr[...] = m_new

    @pl.when(ikv == num_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0, :, :] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                         causal: bool = True, block_q: int = 128,
                         block_kv: int = 128,
                         scale: Optional[float] = None,
                         interpret: bool = False) -> jnp.ndarray:
    """q: (b, h, s, d); k/v: (b, hkv, s, d) with h % hkv == 0."""
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    assert h % hkv == 0, f"GQA requires h % hkv == 0, got {h}/{hkv}"
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    assert sq % block_q == 0 and skv % block_kv == 0
    nq, nkv = sq // block_q, skv // block_kv
    scale = d ** -0.5 if scale is None else scale

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_kv=block_kv,
        causal=causal, num_kv_blocks=nkv)

    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda ib, ih, iq, ikv: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda ib, ih, iq, ikv, hkv=hkv, h=h:
                         (ib, ih * hkv // h, ikv, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda ib, ih, iq, ikv, hkv=hkv, h=h:
                         (ib, ih * hkv // h, ikv, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda ib, ih, iq, ikv: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),      # running max m
            pltpu.VMEM((block_q,), jnp.float32),      # running sum l
            pltpu.VMEM((block_q, d), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
