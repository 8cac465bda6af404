"""Batched rank-1 RLS covariance update as a Pallas TPU kernel.

One recursive-least-squares step per stream of the forecast bank
(:mod:`repro.core.forecast_bank`):

    g  = Pφ / (λ + φᵀPφ)
    P' = (P − g·(Pφ)ᵀ) / λ

The covariance order k (AR lags + bias) is tiny, so a single stream is pure
VPU work; batching the whole bank onto the sublane axis is what fills the
lanes. Each grid step owns a (blk, k, k) block of covariances resident in
VMEM — there is no reduction across blocks, so the grid is fully parallel.

On CPU (this container) the kernel runs in interpret mode, where it also
supports the bank's float64 arrays; on a real TPU it lowers to Mosaic for
float32 banks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rls_kernel(p_ref, phi_ref, lam_ref, gain_ref, pnew_ref):
    P = p_ref[...]                       # (blk, k, k)
    phi = phi_ref[...]                   # (blk, k)
    lam = lam_ref[...]                   # (blk, 1)
    Pphi = jnp.sum(P * phi[:, None, :], axis=-1)
    denom = lam + jnp.sum(phi * Pphi, axis=-1, keepdims=True)
    gain = Pphi / denom
    gain_ref[...] = gain
    pnew_ref[...] = (P - gain[:, :, None] * Pphi[:, None, :]) / lam[:, :, None]


@functools.partial(jax.jit, static_argnames=("blk_rows", "interpret"))
def rls_rank1_update(P: jnp.ndarray, phi: jnp.ndarray, lam: jnp.ndarray, *,
                     blk_rows: int = 8, interpret: bool = False):
    """P: (B, k, k), phi: (B, k), lam: (B,). Returns (gain (B, k), P' (B, k, k))."""
    B, k, _ = P.shape
    lam2 = lam.reshape(B, 1)
    blk = min(blk_rows, B)
    pad = (-B) % blk
    if pad:
        P = jnp.pad(P, ((0, pad), (0, 0), (0, 0)))
        phi = jnp.pad(phi, ((0, pad), (0, 0)))
        # λ = 1 on padded rows keeps their (discarded) divisions finite
        lam2 = jnp.pad(lam2, ((0, pad), (0, 0)), constant_values=1.0)
    total = P.shape[0]

    gain, pnew = pl.pallas_call(
        _rls_kernel,
        grid=(total // blk,),
        in_specs=[
            pl.BlockSpec((blk, k, k), lambda i: (i, 0, 0)),
            pl.BlockSpec((blk, k), lambda i: (i, 0)),
            pl.BlockSpec((blk, 1), lambda i: (i, 0)),
        ],
        out_specs=[pl.BlockSpec((blk, k), lambda i: (i, 0)),
                   pl.BlockSpec((blk, k, k), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((total, k), P.dtype),
                   jax.ShapeDtypeStruct((total, k, k), P.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(P, phi, lam2)
    if pad:
        gain, pnew = gain[:B], pnew[:B]
    return gain, pnew


def rls_contract():
    """Compilation contract for the kernel's lowering (checked through the
    FORECAST_BACKENDS registry, see docs/ANALYSIS.md): whether it lowers to
    Mosaic (TPU) or interpret-mode XLA (CPU), the dispatch must stay free of
    host callbacks and cross-device collectives — the grid is fully
    parallel over covariance blocks."""
    from ..analysis.contracts import COLLECTIVE_HLO_OPS, CompilationContract
    return CompilationContract(
        name="kernel:rls-rank1-update",
        forbidden_hlo=COLLECTIVE_HLO_OPS,
        forbid_callbacks=True,
        note="batched rank-1 RLS covariance update (Pallas)")
