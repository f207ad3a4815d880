"""Bytes the rank kernel (``kernels/rank.py`` ``topk_rank_batch_pallas``)
moves through HBM in one launch, from the launch's shapes.

The grid is ``(Q, ceil(N / block_n))`` with queries outer: every query
streams every tile of the four node columns (support, confidence, lift,
depth, in their stored dtypes), plus a ``[Q, N]`` int32 membership tile
when the launch ranks ``rules_with`` sets, reads its 128-lane parameter
row once and writes its two ``kpad``-wide output rows once.  The kernel
compares and merges; it does no matmul, so the roofline bound is the
bytes over HBM bandwidth.
"""
from __future__ import annotations

import re

import numpy as np

LANE = 128


def itemsize(dtype) -> int:
    return int(np.dtype(dtype).itemsize) if str(dtype) != "bfloat16" else 2


def rank_bytes(q: int, n: int, column_dtypes, block_n: int, k: int,
               masked: bool) -> int:
    """HBM bytes of one launch over ``q`` query rows and ``n`` nodes."""
    n_pad = -(-int(n) // int(block_n)) * int(block_n)
    per_node = sum(itemsize(d) for d in column_dtypes)
    if masked:
        per_node += 4
    kpad = int(k) + (-int(k) % LANE)
    per_query = LANE * 4 + 2 * kpad * 4
    return int(q) * (n_pad * per_node + per_query)


def roofline_share(bytes_moved: float, seconds: float,
                   hbm_bytes_per_s: float) -> float:
    """Least time the bytes need at peak bandwidth over the time taken,
    in %."""
    return 100.0 * (bytes_moved / hbm_bytes_per_s) / seconds


_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|f64)"
                    r"\[([\d,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
          "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "f64": 8}


def _shapes(text: str) -> list:
    return [(t, [int(d) for d in dims.split(",") if d])
            for t, dims in _SHAPE.findall(text)]


def rank_bytes_from_hlo(text: str) -> int:
    """HBM bytes of one rank-kernel launch read off its HLO text as the
    device trace names it::

        %_topk_rank_batch_impl.1 = (f32[Q,1,KPAD], s32[Q,1,KPAD])
            custom-call(s32[Q,1,128] params, f32[1,N] sup, f32[1,N] conf,
                        f32[1,N] lift, s32[1,N] depth[, s32[Q,1,N] member])

    Each query streams every ``[1, N]`` column and its own ``[Q, 1, N]``
    membership row, reads its parameter row and writes its outputs."""
    head, _, rest = text.partition(" custom-call(")
    operands = rest.split("), custom_call_target", 1)[0]
    outs = _shapes(head.split(" = ", 1)[-1])
    ins = _shapes(operands)
    if not outs or not ins:
        raise ValueError(f"not a rank kernel launch: {text[:120]!r}")
    q = outs[0][1][0]
    total = sum(int(np.prod(d)) * _BYTES[t] for t, d in outs)
    for t, dims in ins:
        if len(dims) == 2 and dims[0] == 1:        # a node column
            total += q * dims[1] * _BYTES[t]
        else:                                      # per-query rows
            total += int(np.prod(dims)) * _BYTES[t]
    return total
