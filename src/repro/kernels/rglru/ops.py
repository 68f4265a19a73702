"""Public jit'd wrapper for the RG-LRU scan kernel.

Takes the model-layer quantities (x, r, i, Λ, h0), precomputes the
kernel inputs (gated input, log-a), pads T to the time-chunk and W to
the width-block, and dispatches: compiled on TPU, through the Pallas
interpreter only when the caller passes ``interpret=True``."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import RGLRU_C, rglru_scan_kernel


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


# das: hot-path
@functools.partial(jax.jit, static_argnames=("interpret",))
def rglru_scan(x, r, i, lam, h0, *, interpret: bool = False):
    """x, r, i: (B,T,W) fp32; lam (W,); h0 (B,W). → (h_seq, h_final)."""
    B, T, W = x.shape
    a_base = jnp.log(jax.nn.sigmoid(lam))
    log_a = RGLRU_C * r * a_base[None, None, :]
    gx = i * x
    tc = min(128, _round_up(T, 8))
    wb = min(512, _round_up(W, 128))
    Tp, Wp = _round_up(T, tc), _round_up(W, wb)
    if Tp != T or Wp != W:
        # pad with a=1 (log_a=0), gx=0 → padded steps keep h unchanged
        gx = jnp.pad(gx, ((0, 0), (0, Tp - T), (0, Wp - W)))
        log_a = jnp.pad(log_a, ((0, 0), (0, Tp - T), (0, Wp - W)))
        h0p = jnp.pad(h0, ((0, 0), (0, Wp - W)))
    else:
        h0p = h0
    hs, hfin = rglru_scan_kernel(
        gx, log_a, h0p, t_chunk=tc, w_block=wb, interpret=interpret
    )
    return hs[:, :T, :W], hfin[:, :W]
