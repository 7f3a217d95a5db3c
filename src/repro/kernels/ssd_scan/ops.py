"""Public SSD scan: pass-1 kernel -> host chunk scan -> pass-2 kernel."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.kernel import ssd_inter, ssd_intra


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(xh: jnp.ndarray, b_mat: jnp.ndarray, c_mat: jnp.ndarray,
             log_a: jnp.ndarray, dt: jnp.ndarray, *, chunk: int = 128,
             interpret: bool = False,
             h0: Optional[jnp.ndarray] = None
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD scan (Mamba2).

    xh: (b, s, h, p); b_mat/c_mat: (b, s, n); log_a/dt: (b, s, h).
    Returns (y (b, s, h, p), final state (b, h, n, p) fp32).
    """
    bsz, s, h, p = xh.shape
    n = b_mat.shape[-1]
    q = min(chunk, s)
    assert s % q == 0, f"seq {s} not divisible by chunk {q}"
    c = s // q

    # head-major layouts for the kernels (see kernel.py)
    xc = xh.reshape(bsz, c, q, h, p).transpose(0, 1, 3, 2, 4)
    bmt = b_mat.reshape(bsz, c, q, n).transpose(0, 1, 3, 2)
    cc = c_mat.reshape(bsz, c, q, n)
    la = log_a.reshape(bsz, c, q, h).astype(jnp.float32)
    dc = dt.reshape(bsz, c, q, h).astype(jnp.float32)
    cum = jnp.cumsum(la, axis=2).transpose(0, 1, 3, 2)          # (b,c,h,q)

    y_intra, s_chunk = ssd_intra(xc, bmt, cc, cum,
                                 dc.transpose(0, 1, 3, 2),
                                 interpret=interpret)
    chunk_decay = jnp.exp(cum[..., -1])                         # (b,c,h)

    if h0 is None:
        h0 = jnp.zeros((bsz, h, n, p), jnp.float32)

    def step(hprev, inp):
        s_c, dec = inp                                          # (b,h,n,p),(b,h)
        return hprev * dec[..., None, None] + s_c, hprev

    h_last, h_prevs = jax.lax.scan(
        step, h0.astype(jnp.float32),
        (s_chunk.transpose(1, 0, 2, 3, 4),
         chunk_decay.transpose(1, 0, 2)))
    h_prevs = h_prevs.transpose(1, 0, 2, 3, 4)                  # (b,c,h,n,p)

    y = ssd_inter(cc, cum, h_prevs, y_intra, xh.dtype, interpret=interpret)
    return y.transpose(0, 1, 3, 2, 4).reshape(bsz, s, h, p), h_last
