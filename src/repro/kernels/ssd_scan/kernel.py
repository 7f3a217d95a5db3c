"""Mamba2 SSD chunked scan — TPU Pallas kernels (two-pass design).

The GPU SSD kernel fuses a warp-level associative scan; the TPU
adaptation splits the work by arithmetic intensity:

  pass 1  ``_intra_kernel``   grid (batch, chunk, head): dense Q×Q
          decay-weighted matmuls on the MXU produce the *intra-chunk*
          output and each chunk's state summary S_c.
  host    a tiny ``lax.scan`` over seq/chunk steps combines the chunk
          summaries into incoming states h_{c-1} (O(c·h·n·p) work —
          bandwidth-trivial, latency-bound, pointless to kernelize).
  pass 2  ``_inter_kernel``   grid (batch, chunk, head): applies the
          incoming state through C·h_{c-1}·exp(cum) and adds the intra
          output.

Arrays are head-major ((b, c, h, q, p), and cum/dt as (b, c, h, q)) so
each grid step works on 2-D tiles whose last two block dims are whole
array dims, as the TPU tiling requires; the head axis is a grid axis,
never a vector axis. chunk=128 keeps the (q × q) decay matrix
MXU-aligned. Accumulation is fp32 throughout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


#: the TPU's default f32 matmul takes one bf16 pass, which loses the
#: decay/dt weighting's low bits; fp32 accumulation needs fp32 operands
_HIGHEST = jax.lax.Precision.HIGHEST


def _intra_kernel(xh_ref, bmt_ref, cm_ref, cum_col_ref, cum_row_ref,
                  dt_row_ref, y_ref, s_ref):
    """One (batch, chunk, head) cell.

    xh: (q, p); bmt: (n, q) = Bᵀ; cm: (q, n); cum as a column (q, 1)
    and a row (1, q): inclusive cumsum of dt*A (log-decay); dt: (1, q).
    Outputs: y (q, p) intra-chunk, s (n, p) chunk summary.
    """
    xh = xh_ref[0, 0, 0].astype(jnp.float32)
    bmt = bmt_ref[0, 0].astype(jnp.float32)
    cm = cm_ref[0, 0].astype(jnp.float32)
    cum_col = cum_col_ref[0, 0, 0]
    cum_row = cum_row_ref[0, 0, 0]
    dt_row = dt_row_ref[0, 0, 0]
    q = xh.shape[0]

    # decay matrix L[i, j] = exp(cum_i - cum_j), lower-triangular
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tril = cols <= rows
    l_mat = jnp.where(tril, jnp.exp(jnp.where(tril, cum_col - cum_row,
                                              0.0)), 0.0)
    # G[i, j] = C_i · B_j, weighted by the decay and dt_j
    m_mat = jnp.dot(cm, bmt, precision=_HIGHEST,
                    preferred_element_type=jnp.float32) * l_mat * dt_row
    # y[i, p] = Σ_j m[i, j] x[j, p]
    y_ref[0, 0, 0] = jnp.dot(m_mat, xh, precision=_HIGHEST,
                             preferred_element_type=jnp.float32
                             ).astype(y_ref.dtype)

    # chunk summary S_c[n, p] = Σ_j exp(cum_q - cum_j) dt_j B_j x_j^T
    w_row = jnp.exp(cum_row[:, q - 1:] - cum_row) * dt_row      # (1, q)
    s_ref[0, 0, 0] = jnp.dot(bmt * w_row, xh, precision=_HIGHEST,
                             preferred_element_type=jnp.float32
                             ).astype(s_ref.dtype)


def _inter_kernel(cm_ref, cum_col_ref, hprev_ref, y_intra_ref, y_ref):
    """y[i, p] = y_intra[i, p] + exp(cum_i) * (C_i · h_prev[:, p])."""
    cm = cm_ref[0, 0].astype(jnp.float32)             # (q, n)
    cum_col = cum_col_ref[0, 0, 0]                    # (q, 1)
    hprev = hprev_ref[0, 0, 0].astype(jnp.float32)    # (n, p)
    y_inter = jnp.dot(cm, hprev, precision=_HIGHEST,
                      preferred_element_type=jnp.float32) * jnp.exp(cum_col)
    y_ref[0, 0, 0] = (y_intra_ref[0, 0, 0].astype(jnp.float32)
                      + y_inter).astype(y_ref.dtype)


def _per_head(*block):
    """BlockSpec for a head-major (b, c, h, *block) array."""
    return pl.BlockSpec((1, 1, 1) + block,
                        lambda ib, ic, ih: (ib, ic, ih) + (0,) * len(block))


def _per_chunk(*block):
    """BlockSpec for a (b, c, *block) array shared by every head."""
    return pl.BlockSpec((1, 1) + block,
                        lambda ib, ic, ih: (ib, ic) + (0,) * len(block))


_PARALLEL3 = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel"))


def ssd_intra(xh, bmt, cm, cum, dt, *, interpret: bool = False):
    """xh: (b, c, h, q, p); bmt: (b, c, n, q); cm: (b, c, q, n);
    cum/dt: (b, c, h, q). Returns y_intra (b, c, h, q, p) and the
    chunk summaries (b, c, h, n, p), both fp32."""
    b, c, h, q, p = xh.shape
    n = bmt.shape[2]
    return pl.pallas_call(
        _intra_kernel,
        grid=(b, c, h),
        in_specs=[_per_head(q, p), _per_chunk(n, q), _per_chunk(q, n),
                  _per_head(q, 1), _per_head(1, q), _per_head(1, q)],
        out_specs=[_per_head(q, p), _per_head(n, p)],
        out_shape=[
            jax.ShapeDtypeStruct((b, c, h, q, p), jnp.float32),
            jax.ShapeDtypeStruct((b, c, h, n, p), jnp.float32),
        ],
        compiler_params=_PARALLEL3,
        interpret=interpret,
    )(xh, bmt, cm, cum[..., :, None], cum[..., None, :], dt[..., None, :])


def ssd_inter(cm, cum, h_prevs, y_intra, out_dtype, *,
              interpret: bool = False):
    """cm: (b, c, q, n); cum: (b, c, h, q); h_prevs: (b, c, h, n, p);
    y_intra: (b, c, h, q, p). Returns y (b, c, h, q, p)."""
    b, c, q, n = cm.shape
    h = cum.shape[2]
    p = h_prevs.shape[-1]
    return pl.pallas_call(
        _inter_kernel,
        grid=(b, c, h),
        in_specs=[_per_chunk(q, n), _per_head(q, 1), _per_head(n, p),
                  _per_head(q, p)],
        out_specs=_per_head(q, p),
        out_shape=jax.ShapeDtypeStruct((b, c, h, q, p), out_dtype),
        compiler_params=_PARALLEL3,
        interpret=interpret,
    )(cm, cum[..., :, None], h_prevs, y_intra)
