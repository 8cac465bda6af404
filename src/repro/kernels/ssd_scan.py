"""Mamba2 SSD chunked scan as a Pallas TPU kernel.

One grid row per (batch x head); the chunk dimension is innermost and
sequential, carrying the (P, N) state in VMEM scratch — the inter-chunk
recurrence never touches HBM. Per chunk the kernel fuses the three SSD
contractions (intra-chunk dual form, state readout, state update) on MXU
tiles: chunk length Q and state width N are 128-multiples, head dim P=64.
The per-head decay scalar A arrives via scalar prefetch; B/C group
projections are shared across the heads of a group through the index maps
(no host-side head expansion, matching the memory behaviour of the fused
CUDA kernel the paper's authors ship — rethought here as MXU block
contractions instead of warp-level scans).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, state_ref,
                state_scr, *, chunk: int, n_heads: int):
    bh = pl.program_id(0)
    ci = pl.program_id(1)
    nc = pl.num_programs(1)
    a = a_ref[bh % n_heads]                              # per-head -exp(A_log)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    dt = dt_ref[...].astype(jnp.float32).reshape(chunk, 1)   # (Q, 1)
    da = dt * a                                              # (Q, 1) log-decay
    cum = jnp.cumsum(da, axis=0)                             # (Q, 1)

    x = x_ref[0].astype(jnp.float32)                         # (Q, P)
    bmat = b_ref[0].astype(jnp.float32)                      # (Q, N)
    cmat = c_ref[0].astype(jnp.float32)                      # (Q, N)
    xdt = x * dt

    # Intra-chunk dual (attention-like) form.
    cb = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    li = cum - cum.reshape(1, chunk)                         # cum_i - cum_j
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    m = jnp.where(rows >= cols, cb * jnp.exp(li), 0.0)
    y = jax.lax.dot(m, xdt, preferred_element_type=jnp.float32)

    # State readout (contribution of previous chunks).
    prev = state_scr[...]                                    # (P, N)
    y += jax.lax.dot_general(cmat, prev, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) \
        * jnp.exp(cum)

    # State update: decay whole chunk + inject decayed inputs.
    last = cum[chunk - 1:chunk]                              # (1, 1)
    decay_to_end = jnp.exp(last - cum)                       # (Q, 1)
    inject = jax.lax.dot_general(xdt, bmat * decay_to_end,
                                 (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (P, N)
    state_scr[...] = jnp.exp(last) * prev + inject

    y_ref[0] = y.astype(y_ref.dtype)
    state_ref[0] = state_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, a_log: jnp.ndarray,
             b: jnp.ndarray, c: jnp.ndarray, *, chunk: int = 256,
             interpret: bool = False):
    """x: (B, S, H, P); dt: (B, S, H); a_log: (H,); b, c: (B, S, G, N).

    Returns (y: (B, S, H, P), final_state: (B, H, P, N)).
    """
    bsz, seq, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    assert seq % chunk == 0
    nc = seq // chunk
    rep = h // g

    xr = x.transpose(0, 2, 1, 3).reshape(bsz * h, seq, p)
    dtr = dt.transpose(0, 2, 1).reshape(bsz * h, seq)
    br = b.transpose(0, 2, 1, 3).reshape(bsz * g, seq, n)
    cr = c.transpose(0, 2, 1, 3).reshape(bsz * g, seq, n)
    a = -jnp.exp(a_log.astype(jnp.float32))

    def bc_index(bh, ci, a_pref):
        return (bh // h) * g + (bh % h) // rep, ci, 0

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_heads=h)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz * h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda bh, ci, a_pref: (bh, ci, 0)),
            pl.BlockSpec((1, chunk), lambda bh, ci, a_pref: (bh, ci)),
            pl.BlockSpec((1, chunk, n), bc_index),
            pl.BlockSpec((1, chunk, n), bc_index),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda bh, ci, a_pref: (bh, ci, 0)),
            pl.BlockSpec((1, p, n), lambda bh, ci, a_pref: (bh, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
    )
    y, state = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((bsz * h, seq, p), x.dtype),
                   jax.ShapeDtypeStruct((bsz * h, p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(a, xr, dtr, br, cr)
    y = y.reshape(bsz, h, seq, p).transpose(0, 2, 1, 3)
    return y, state.reshape(bsz, h, p, n)
