"""Fused sweep tick — lag update + detector observe + RLS — as one Pallas
kernel.

One simulation tick of the fused sweep engine (:mod:`repro.dsp.fused`)
does three elementwise-over-scenarios things in sequence: advance the
consumer-lag queue, observe ``y = log1p(lag)`` with a per-scenario AR(1)
anomaly predictor, and apply the rank-1 RLS update to the predictor

    lag' = down ? lag0 + r·dt : max(lag0 + (r − cap)·dt, 0)
    e    = y − wᵀφ,  φ = (1, y_prev)
    g    = Pφ / (λ + φᵀPφ)
    w'   = w + g·e
    P'   = (P − g·(Pφ)ᵀ) / λ

The RLS recursion is the :mod:`repro.kernels.rls_update` math with the
predictor-weight update riding along; fusing all three keeps the per-tick
state (lag, w, P, y) resident in VMEM for the whole tick instead of
bouncing through HBM between three dispatches. Row blocks batch onto the
sublane axis exactly like ``rls_update``; the grid is fully parallel.

On CPU (this container) the kernel runs in interpret mode, pinned against
:func:`repro.kernels.ref.fused_tick_ref` by ``tests/test_kernels.py``; on
a real TPU it lowers to Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _fused_tick_kernel(lag_ref, add_ref, rate_ref, cap_ref, down_ref,
                       w_ref, p_ref, yprev_ref, lam_ref, thresh_ref,
                       newlag_ref, w2_ref, p2_ref, err_ref, flag_ref,
                       *, dt: float):
    lag = lag_ref[...]                   # (blk, 1)
    rate = rate_ref[...]                 # (blk, 1)
    down = down_ref[...]                 # (blk, 1) — 1.0 when down
    lam = lam_ref[...]                   # (blk, 1)

    # -- consumer-lag update (mirrors step_batch_arrays / fused_tick_ref) --
    lag0 = lag + add_ref[...]
    demand = rate * dt + lag0
    processed = jnp.minimum(cap_ref[...] * dt, demand)
    new_lag = jnp.where(down > 0.0, lag0 + rate * dt, demand - processed)
    newlag_ref[...] = new_lag

    # -- detector observe: AR(1)+bias prediction error on log1p(lag) -------
    y = jnp.log1p(new_lag)               # (blk, 1)
    w = w_ref[...]                       # (blk, k)
    P = p_ref[...]                       # (blk, k, k)
    phi = jnp.concatenate([jnp.ones_like(yprev_ref[...]), yprev_ref[...]],
                          axis=-1)       # (blk, k)
    err = y - jnp.sum(w * phi, axis=-1, keepdims=True)
    err_ref[...] = err
    flag_ref[...] = (jnp.abs(err) > thresh_ref[...]).astype(lag.dtype)

    # -- rank-1 RLS update (the rls_update.py recursion + weight update) ---
    Pphi = jnp.sum(P * phi[:, None, :], axis=-1)
    denom = lam + jnp.sum(phi * Pphi, axis=-1, keepdims=True)
    gain = Pphi / denom
    w2_ref[...] = w + gain * err
    p2_ref[...] = (P - gain[:, :, None] * Pphi[:, None, :]) / lam[:, :, None]


@functools.partial(jax.jit,
                   static_argnames=("dt", "blk_rows", "interpret"))
def fused_tick(lag: jnp.ndarray, lag_add: jnp.ndarray, rates: jnp.ndarray,
               cap: jnp.ndarray, down_pre: jnp.ndarray, w: jnp.ndarray,
               P: jnp.ndarray, y_prev: jnp.ndarray, lam: float,
               thresh: float, dt: float, *, blk_rows: int = 8,
               interpret: bool = False):
    """lag/lag_add/rates/cap/down_pre/y_prev: (B,); w: (B, k); P: (B, k, k).

    Returns ``(new_lag (B,), w' (B, k), P' (B, k, k), err (B,),
    flag (B,) bool)``; ``lam``/``thresh``/``dt`` are scalars.
    """
    B, k = w.shape
    dtype = lag.dtype
    col = lambda a: a.astype(dtype).reshape(B, 1)  # noqa: E731
    lag2, add2, rate2, cap2, yprev2 = map(
        col, (lag, lag_add, rates, cap, y_prev))
    down2 = col(down_pre)
    lam2 = jnp.full((B, 1), lam, dtype)
    thresh2 = jnp.full((B, 1), thresh, dtype)

    blk = min(blk_rows, B)
    pad = (-B) % blk
    if pad:
        pads2 = ((0, pad), (0, 0))
        lag2, add2, rate2, cap2, down2, yprev2, thresh2 = (
            jnp.pad(a, pads2) for a in (lag2, add2, rate2, cap2, down2,
                                        yprev2, thresh2))
        # λ = 1 and cap > 0 keep the padded rows' (discarded) math finite
        lam2 = jnp.pad(lam2, pads2, constant_values=1.0)
        w = jnp.pad(w, pads2)
        P = jnp.pad(P, ((0, pad), (0, 0), (0, 0)))
    total = lag2.shape[0]

    row = pl.BlockSpec((blk, 1), lambda i: (i, 0))
    mat = pl.BlockSpec((blk, k), lambda i: (i, 0))
    cov = pl.BlockSpec((blk, k, k), lambda i: (i, 0, 0))
    new_lag, w2, p2, err, flag = pl.pallas_call(
        functools.partial(_fused_tick_kernel, dt=float(dt)),
        grid=(total // blk,),
        in_specs=[row, row, row, row, row, mat, cov, row, row, row],
        out_specs=[row, mat, cov, row, row],
        out_shape=[jax.ShapeDtypeStruct((total, 1), dtype),
                   jax.ShapeDtypeStruct((total, k), dtype),
                   jax.ShapeDtypeStruct((total, k, k), dtype),
                   jax.ShapeDtypeStruct((total, 1), dtype),
                   jax.ShapeDtypeStruct((total, 1), dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(lag2, add2, rate2, cap2, down2, w, P, yprev2, lam2, thresh2)
    return (new_lag[:B, 0], w2[:B], p2[:B], err[:B, 0],
            flag[:B, 0] > 0.0)


def fused_tick_contract():
    """Compilation contract for the fused-tick lowering (checked through the
    SIM_ENGINES registry alongside the fused engine's interval scan): the
    grid is fully parallel over row blocks, so the dispatch must stay free
    of host callbacks and cross-device collectives."""
    from ..analysis.contracts import COLLECTIVE_HLO_OPS, CompilationContract
    return CompilationContract(
        name="kernel:fused-tick",
        forbidden_hlo=COLLECTIVE_HLO_OPS,
        forbid_callbacks=True,
        note="fused lag-update + detector observe + RLS tick (Pallas)")
