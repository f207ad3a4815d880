"""Plain reference for store kind ``baskets``: every frequent itemset of
the baskets, mined again here from the raw baskets by a projected-basket
depth-first search, as a ``RuleTable``.

A set is frequent when its count is at least ``minsup`` times the number
of baskets, and the table holds those of at most ``max_len`` items.
Nothing of the program is imported.
"""
from __future__ import annotations

import numpy as np

from benchlib.ruletable import RuleTable


def _gather(indptr, vals, rows):
    """Concatenated ``vals`` of CSR ``rows`` and the row index of each."""
    lens = indptr[rows + 1] - indptr[rows]
    total = int(lens.sum())
    if total == 0:
        return np.zeros(0, vals.dtype), np.zeros(0, np.int64)
    which = np.repeat(np.arange(rows.size), lens)
    starts = np.repeat(indptr[rows] - np.cumsum(lens) + lens, lens)
    return vals[starts + np.arange(total)], which


def reference(data: dict) -> RuleTable:
    indptr = np.asarray(data["indptr"], np.int64)
    items = np.asarray(data["items"], np.int64)
    n_items = int(data["n_items"])
    n = indptr.size - 1
    max_len = int(data["max_len"])
    thr = float(data["minsup"]) * n
    counts = np.bincount(items, minlength=n_items)
    order = np.lexsort((np.arange(n_items), -counts))
    rank = np.empty(n_items, np.int64)
    rank[order] = np.arange(n_items)
    # baskets as frequent item ranks, ascending within each basket
    r = rank[items]
    keep = counts[items] >= thr
    row = np.repeat(np.arange(n), np.diff(indptr))[keep]
    r = r[keep]
    srt = np.lexsort((r, row))
    r, row = r[srt], row[srt]
    bptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(row, minlength=n), out=bptr[1:])

    parent, item, cnt = [-1], [-1], [float(n)]

    def grow(prow: int, tids: np.ndarray, last: int, depth: int):
        if depth >= max_len:
            return
        ext, which = _gather(bptr, r, tids)
        sel = ext > last
        ext, which = ext[sel], which[sel]
        c = np.bincount(ext, minlength=1)
        freq = np.flatnonzero(c >= thr)
        if freq.size == 0:
            return
        sel = np.isin(ext, freq)
        ext, which = ext[sel], which[sel]
        srt = np.argsort(ext, kind="stable")
        ext, which = ext[srt], which[srt]
        bounds = np.searchsorted(ext, freq, side="left")
        ends = np.searchsorted(ext, freq, side="right")
        for e, lo, hi in zip(freq, bounds, ends):
            me = len(parent)
            parent.append(prow)
            item.append(int(order[e]))
            cnt.append(float(hi - lo))
            grow(me, tids[which[lo:hi]], int(e), depth + 1)

    grow(0, np.arange(n), -1, 0)
    return RuleTable(parent, item, cnt, n, rank, max_len)
