"""Expert-grouped matmul (megablox-style) as a Pallas TPU kernel.

For MoE FFNs over tokens sorted by expert: ``out[i] = lhs[i] @ rhs[e_i]``.
The ops wrapper pads each expert's token group to a BLK_M multiple so every
M-tile maps to exactly one expert; the tile -> expert table arrives via
scalar prefetch and the rhs index map streams only that expert's weight
tiles. Compared to a dense dispatch einsum this does N*k*d*f FLOPs instead
of N*E*d*f and keeps rhs HBM reads at one expert per tile.

Grid: (M/BLK_M, N/BLK_N, K/BLK_K), K innermost with an f32 VMEM accumulator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(tile_expert_ref, lhs_ref, rhs_ref, out_ref, acc_scr, *,
                blk_k_steps: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot(lhs_ref[...].astype(jnp.float32),
                                rhs_ref[0].astype(jnp.float32),
                                preferred_element_type=jnp.float32)

    @pl.when(ki == blk_k_steps - 1)
    def _finalize():
        out_ref[...] = acc_scr[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("blk_m", "blk_n", "blk_k",
                                             "interpret"))
def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray,
                   tile_expert: jnp.ndarray, *, blk_m: int = 128,
                   blk_n: int = 128, blk_k: int = 128,
                   interpret: bool = False) -> jnp.ndarray:
    """lhs: (M, K) tokens sorted+padded by expert; rhs: (E, K, N);
    tile_expert: (M/blk_m,) int32 expert id per M-tile. Returns (M, N)."""
    m, k = lhs.shape
    e, k2, n = rhs.shape
    assert k == k2 and m % blk_m == 0
    blk_n = min(blk_n, n)
    blk_k = min(blk_k, k)
    assert n % blk_n == 0 and k % blk_k == 0

    kernel = functools.partial(_gmm_kernel, blk_k_steps=k // blk_k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m // blk_m, n // blk_n, k // blk_k),
        in_specs=[
            pl.BlockSpec((blk_m, blk_k), lambda mi, ni, ki, te: (mi, ki)),
            pl.BlockSpec((1, blk_k, blk_n),
                         lambda mi, ni, ki, te: (te[mi], ki, ni)),
        ],
        out_specs=pl.BlockSpec((blk_m, blk_n),
                               lambda mi, ni, ki, te: (mi, ni)),
        scratch_shapes=[pltpu.VMEM((blk_m, blk_n), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(tile_expert.astype(jnp.int32), lhs, rhs)


def sort_tokens_for_experts(x: np.ndarray, expert_ids: np.ndarray,
                            n_experts: int, blk_m: int = 128):
    """Host-side helper: sort tokens by expert and pad each group to a
    BLK_M multiple. Returns (padded lhs, tile_expert, inverse gather index,
    valid mask). Used by the ops wrapper and tests."""
    order = np.argsort(expert_ids, kind="stable")
    sizes = np.bincount(expert_ids, minlength=n_experts)
    padded_sizes = ((sizes + blk_m - 1) // blk_m) * blk_m
    total = int(padded_sizes.sum()) or blk_m
    lhs = np.zeros((total, x.shape[1]), x.dtype)
    inv = np.full(total, -1, np.int64)
    offs = np.concatenate([[0], np.cumsum(padded_sizes)])
    src = 0
    for e_idx in range(n_experts):
        cnt = sizes[e_idx]
        dst = offs[e_idx]
        sel = order[src:src + cnt]
        lhs[dst:dst + cnt] = x[sel]
        inv[dst:dst + cnt] = sel
        src += cnt
    tile_expert = np.repeat(np.arange(n_experts),
                            padded_sizes // blk_m).astype(np.int32)
    if len(tile_expert) == 0:
        tile_expert = np.zeros(total // blk_m, np.int32)
    return lhs, tile_expert, inv, inv >= 0
