"""Flash attention (forward) as a Pallas TPU kernel.

Online-softmax tiling: the grid is (batch*q_heads, Sq/BLK_Q, Skv/BLK_KV) with
the KV dimension innermost ("arbitrary" semantics) so the running max /
denominator / accumulator live in VMEM scratch across KV iterations. Blocks
are MXU-aligned (128x128 tiles over the score matrix; head_dim up to 256
stays resident). GQA is handled in the index maps: the KV operand block for
query head ``h`` is KV head ``h // (Hq // Hkv)`` — no host-side KV repeat, so
HBM traffic stays at the GQA-compressed size.

Causal masking skips fully-masked KV blocks via ``pl.when`` (they cost one
predicate evaluation, no MXU work) and applies an iota mask on the diagonal.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.0 ** 30
LANES = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  blk_q: int, blk_kv: int, causal: bool, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * blk_q
    k_start = ki * blk_kv
    run = True
    if causal:
        # Skip blocks strictly above the diagonal.
        run = k_start <= q_start + blk_q - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                 # (blk_q, d)
        k = k_ref[0].astype(jnp.float32)                 # (blk_kv, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (blk_q, blk_kv), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (blk_q, blk_kv), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)

        m_prev = m_scr[...]                              # (blk_q, LANES)
        m_cur = jnp.max(s, axis=1, keepdims=True)        # (blk_q, 1)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])    # (blk_q, 1)
        p = jnp.exp(s - m_new[:, :1])                    # (blk_q, blk_kv)

        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha \
            + jax.lax.dot(p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "blk_q", "blk_kv",
                                             "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, blk_q: int = 128, blk_kv: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D) -> (B, Sq, Hq, D)."""
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    blk_q = min(blk_q, sq)
    blk_kv = min(blk_kv, skv)
    assert sq % blk_q == 0 and skv % blk_kv == 0

    qr = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * hkv, skv, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * hkv, skv, d)

    def kv_index(bh, qi, ki):
        return (bh // hq) * hkv + (bh % hq) // group, ki, 0

    kernel = functools.partial(_flash_kernel, blk_q=blk_q, blk_kv=blk_kv,
                               causal=causal, scale=1.0 / (d ** 0.5))
    out = pl.pallas_call(
        kernel,
        grid=(b * hq, sq // blk_q, skv // blk_kv),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, blk_kv, d), kv_index),
            pl.BlockSpec((1, blk_kv, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, blk_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, LANES), jnp.float32),   # running max
            pltpu.VMEM((blk_q, LANES), jnp.float32),   # denominator
            pltpu.VMEM((blk_q, d), jnp.float32),       # output acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, hq, sq, d).transpose(0, 2, 1, 3)
