"""Single-token (decode) attention as a Pallas TPU kernel.

Flash-decoding layout: queries are one token per sequence, so the score
matrix is tiny and the work is streaming the KV cache. The grid is
(B*Hkv, S_max/BLK_KV) with the KV dimension innermost; all G query heads of
one KV head are processed together (the (G, D) q block rides in VMEM the
whole pass, KV blocks stream through). The per-sequence valid length arrives
via scalar prefetch: blocks beyond it are skipped entirely (``pl.when``), so
HBM traffic is proportional to the *actual* context length, not the cache
allocation — the term that dominates the decode roofline.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -2.0 ** 30
LANES = 128


def _decode_kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, blk_kv: int, scale: float,
                   hkv: int):
    bh = pl.program_id(0)
    ki = pl.program_id(1)
    nk = pl.num_programs(1)
    length = lengths_ref[bh // hkv]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k_start = ki * blk_kv

    @pl.when(k_start < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                  # (G, d)
        k = k_ref[0].astype(jnp.float32)                  # (blk_kv, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < length, s, NEG_INF)          # (G, blk_kv)

        m_prev = m_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])
        p = jnp.exp(s - m_new[:, :1])
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


@functools.partial(jax.jit, static_argnames=("blk_kv", "interpret"))
def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     lengths: jnp.ndarray, *, blk_kv: int = 256,
                     interpret: bool = False) -> jnp.ndarray:
    """q: (B, 1, Hq, D); k, v: (B, S_max, Hkv, D); lengths: (B,) int32.

    Returns (B, 1, Hq, D) attention over the first ``lengths[b]`` cache
    entries of each sequence.
    """
    b, sq, hq, d = q.shape
    assert sq == 1
    smax, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    blk_kv = min(blk_kv, smax)
    assert smax % blk_kv == 0

    qr = q[:, 0].reshape(b, hkv, group, d).reshape(b * hkv, group, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * hkv, smax, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * hkv, smax, d)
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (b,))

    kernel = functools.partial(_decode_kernel, blk_kv=blk_kv,
                               scale=1.0 / (d ** 0.5), hkv=hkv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hkv, smax // blk_kv),
        in_specs=[
            pl.BlockSpec((1, group, d), lambda bh, ki, lens: (bh, 0, 0)),
            pl.BlockSpec((1, blk_kv, d), lambda bh, ki, lens: (bh, ki, 0)),
            pl.BlockSpec((1, blk_kv, d), lambda bh, ki, lens: (bh, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, group, d), lambda bh, ki, lens: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, LANES), jnp.float32),
            pltpu.VMEM((group, LANES), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, qr, kr, vr)
    return out.reshape(b, hq, d)[:, None].transpose(0, 1, 2, 3).reshape(
        b, 1, hq, d)
