"""Pallas TPU kernel: full-ruleset traversal reductions.

The paper's headline traversal result (25 min vs >2 h, an ~8× win) is a
visit-every-rule pass.  On the frozen SoA trie that pass is a masked
column reduction over the node arrays — this kernel tiles the columns
through VMEM and accumulates (count, Σ support, max confidence,
Σ confidence) across grid steps in lanes 0-3 of one ``(1, 128)`` output
vector (the TPU stores whole vectors to VMEM, never single scalars).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .metrics_inkernel import dequantize_metrics, metric_pad_dtype
from .tuning import get_kernel_config

BN = 8192   # default nodes per tile (tunable: KernelConfig.reduce_bn)
LANE = 128  # accumulator width: lanes 0-3 hold the four running values


def _make_kernel(n_transactions: int, confidence_scale: float):
    def kernel(sup_ref, conf_ref, depth_ref, out_ref):
        i = pl.program_id(0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANE), 1)

        @pl.when(i == 0)
        def _init():
            out_ref[...] = jnp.where(lane == 2, -jnp.inf, 0.0)

        # Quantized columns (compressed layout) widen per tile; lift is
        # unused by this reduction so confidence stands in for it.
        sup, conf, _ = dequantize_metrics(
            sup_ref[...][0], conf_ref[...][0], conf_ref[...][0],
            n_transactions, confidence_scale, confidence_scale,
        )
        depth = depth_ref[...][0]
        mask = depth > 0
        acc = out_ref[...]
        tile = jnp.where(
            lane == 0, jnp.sum(mask.astype(jnp.float32)),
            jnp.where(
                lane == 1, jnp.sum(jnp.where(mask, sup, 0.0)),
                jnp.where(
                    lane == 2, jnp.max(jnp.where(mask, conf, -jnp.inf)),
                    jnp.sum(jnp.where(mask, conf, 0.0)),
                ),
            ),
        )
        out_ref[...] = jnp.where(
            lane == 2, jnp.maximum(acc, tile),
            jnp.where(lane < 4, acc + tile, acc),
        )

    return kernel


def trie_reduce_pallas(
    support: jax.Array,      # f32|int32 [N]
    confidence: jax.Array,   # f32|bf16|int8 [N]
    depth: jax.Array,        # int32 [N]
    interpret: bool = False,
    block_n: int | None = None,
    n_transactions: int = 0,
    confidence_scale: float = 1.0,
):
    """``block_n`` (nodes per tile) resolves from the active per-backend
    ``KernelConfig`` when None.  Retiling reassociates the fp32 running
    sums (count/max stay bitwise); the jnp oracle agrees to 1e-6.
    Quantized columns (compressed layout) stay narrow through VMEM and
    widen in-kernel via the static dequant params."""
    if block_n is None:
        block_n = get_kernel_config().reduce_bn
    return _trie_reduce_impl(
        support, confidence, depth,
        interpret=interpret, block_n=int(block_n),
        n_transactions=int(n_transactions),
        confidence_scale=float(confidence_scale),
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "interpret", "block_n", "n_transactions", "confidence_scale",
    ),
)
def _trie_reduce_impl(support, confidence, depth, *, interpret, block_n,
                      n_transactions, confidence_scale):
    n = support.shape[0]
    if n == 0:
        # Empty trie: nothing to reduce.  Returning zeros here avoids
        # tracing a zero-grid pallas_call (mirrors the rule-search guards)
        # and keeps the max-confidence slot at 0.0 instead of -inf.
        z = jnp.float32(0.0)
        return z, z, z, z
    npad = -n % block_n
    sup = jnp.pad(
        support.astype(metric_pad_dtype(support)), (0, npad)
    ).reshape(1, -1)
    conf = jnp.pad(
        confidence.astype(metric_pad_dtype(confidence)), (0, npad)
    ).reshape(1, -1)
    dep = jnp.pad(
        depth.astype(jnp.int32), (0, npad), constant_values=-1
    ).reshape(1, -1)
    nn = sup.shape[1]
    grid = (nn // block_n,)
    out = pl.pallas_call(
        _make_kernel(n_transactions, confidence_scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, LANE), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, LANE), jnp.float32),
        interpret=interpret,
    )(sup, conf, dep)
    # All-padding tries (no depth > 0 node) never update the running max,
    # leaving the -inf init value; report 0.0 like the empty-trie guard.
    conf_max = jnp.where(out[0, 0] > 0, out[0, 2], 0.0)
    return out[0, 0], out[0, 1], conf_max, out[0, 3]
