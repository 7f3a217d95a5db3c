"""Fused residual-add + RMSNorm — TPU Pallas kernel.

One HBM round-trip instead of three (add, square-reduce, scale): the
row block is loaded into VMEM once, the fp32 mean-square reduction and
the scale happen in-register, and both the normalized output and the
updated residual stream are written back. Rows are tiled in
(block_rows, d) VMEM windows with d on the 128-lane minor axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, res_ref, w_ref, y_ref, new_res_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    r = res_ref[...].astype(jnp.float32)
    s = x + r
    var = jnp.mean(s * s, axis=-1, keepdims=True)
    y = s * jax.lax.rsqrt(var + eps) * w_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    new_res_ref[...] = s.astype(new_res_ref.dtype)


def fused_rmsnorm_2d(x: jnp.ndarray, residual: jnp.ndarray, w: jnp.ndarray,
                     *, eps: float = 1e-6, block_rows: int = 256,
                     interpret: bool = False):
    """x/residual: (rows, d); w: (d,). Returns (normed, x + residual)."""
    rows, d = x.shape
    block_rows = min(block_rows, rows)
    assert rows % block_rows == 0
    grid = (rows // block_rows,)
    row_spec = pl.BlockSpec((block_rows, d), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[row_spec, row_spec,
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=[row_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((rows, d), x.dtype),
                   jax.ShapeDtypeStruct((rows, d), x.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, residual, w)
