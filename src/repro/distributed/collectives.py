"""Explicit collective schedules (shard_map) for the perf path.

XLA's GSPMD inserts collectives automatically; these helpers exist for the
cases where *we* want to own the schedule:

* :func:`ring_allreduce` — bandwidth-optimal ring reduce-scatter +
  all-gather built from ``collective_permute``. Because each chunk is an
  independent permute step, XLA can overlap chunk k's transfer with chunk
  k-1's add — the overlap pattern the cross-pod gradient reduction uses
  (pair with int8 EF compression from :mod:`compression` for the wire term).
* :func:`hierarchical_allreduce` — reduce within pods, exchange across the
  "pod" axis, broadcast within pods: the 2-level schedule for multi-pod
  meshes where DCI bandwidth is the scarce resource.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def ring_allreduce(x: jnp.ndarray, mesh: Mesh, axis: str) -> jnp.ndarray:
    """All-reduce ``x`` (replicated on ``axis``) with an explicit ring.

    x is sharded on its leading dim across ``axis``; returns the fully
    reduced array with the same sharding. Requires leading dim divisible by
    the axis size.
    """
    n = mesh.shape[axis]

    def inner(xs):
        # xs: this device's local buffer (its gradient shard). Flatten, pad
        # to n chunks; ring reduce-scatter then ring all-gather, one
        # collective_permute per chunk step (overlappable by XLA).
        shape = xs.shape
        flat = xs.reshape(-1)
        size = flat.size
        pad = (-size) % n
        if pad:
            flat = jnp.pad(flat, (0, pad))
        acc = flat.reshape(n, -1)
        perm = [(i, (i + 1) % n) for i in range(n)]
        idx = jax.lax.axis_index(axis)

        # reduce-scatter: after n-1 steps, device i owns chunk (i+1) % n.
        for step in range(n - 1):
            send = jnp.take(acc, (idx - step) % n, axis=0)
            got = jax.lax.ppermute(send, axis, perm)
            acc = acc.at[(idx - step - 1) % n].add(got)
        # all-gather the completed chunks around the ring.
        own = (idx + 1) % n
        cur = jnp.take(acc, own, axis=0)
        for step in range(n - 1):
            cur = jax.lax.ppermute(cur, axis, perm)
            acc = acc.at[(own - step - 1) % n].set(cur)
        return acc.reshape(-1)[:size].reshape(shape)

    spec = P(axis)
    return jax.shard_map(inner, mesh=mesh, in_specs=(spec,), out_specs=spec,
                         check_vma=False)(x)


def hierarchical_allreduce(x: jnp.ndarray, mesh: Mesh, *,
                           inner_axis: str = "data",
                           outer_axis: str = "pod") -> jnp.ndarray:
    """psum within pods, then across pods: 2-level schedule for multi-pod."""
    axes = [a for a in (inner_axis, outer_axis) if a in mesh.axis_names]

    def inner(xs):
        y = jax.lax.psum(xs, inner_axis)
        if outer_axis in mesh.axis_names:
            y = jax.lax.psum(y, outer_axis)
        return y

    specs = P(*(None for _ in x.shape))
    return jax.shard_map(inner, mesh=mesh, in_specs=(specs,),
                         out_specs=specs, check_vma=False)(x)
