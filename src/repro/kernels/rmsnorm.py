"""Fused residual-add + RMSNorm as a Pallas TPU kernel.

y = rmsnorm(x + res) * (1 + scale); also returns the post-residual sum
(needed as the next block's residual stream). Fusing the add avoids one full
HBM round-trip of the hidden states — this layer is pure memory traffic, so
the fusion is worth ~1/3 of its bytes. Rows tile on the sublane axis; the
full feature dim stays resident (d_model <= 5120 fits VMEM comfortably).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, res_ref, scale_ref, y_ref, sum_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    if res_ref is not None:
        x = x + res_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    yn = x * jax.lax.rsqrt(var + eps)
    y_ref[...] = (yn * (1.0 + scale_ref[...].astype(jnp.float32))
                  ).astype(y_ref.dtype)
    sum_ref[...] = x.astype(sum_ref.dtype)


@functools.partial(jax.jit, static_argnames=("blk_rows", "eps", "interpret"))
def fused_rmsnorm(x: jnp.ndarray, res: jnp.ndarray, scale: jnp.ndarray, *,
                  blk_rows: int = 256, eps: float = 1e-6,
                  interpret: bool = False):
    """x, res: (..., d); scale: (d,). Returns (normed, x + res)."""
    orig = x.shape
    d = orig[-1]
    xr = x.reshape(-1, d)
    rr = res.reshape(-1, d)
    rows = xr.shape[0]
    blk = min(blk_rows, rows)
    pad = (-rows) % blk
    if pad:
        xr = jnp.pad(xr, ((0, pad), (0, 0)))
        rr = jnp.pad(rr, ((0, pad), (0, 0)))
    total = xr.shape[0]

    kernel = functools.partial(_rmsnorm_kernel, eps=eps)
    y, s = pl.pallas_call(
        kernel,
        grid=(total // blk,),
        in_specs=[
            pl.BlockSpec((blk, d), lambda i: (i, 0)),
            pl.BlockSpec((blk, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=[pl.BlockSpec((blk, d), lambda i: (i, 0)),
                   pl.BlockSpec((blk, d), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((total, d), x.dtype),
                   jax.ShapeDtypeStruct((total, d), x.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(xr, rr, scale)
    if pad:
        y, s = y[:rows], s[:rows]
    return y.reshape(orig), s.reshape(orig)
