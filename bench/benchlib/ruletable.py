"""Plain reference answers over a table of rules.

A store is a set of itemsets, each held as its path in canonical order
(item rank ascending, ties by id).  The table keeps one row per itemset:
its parent row (the path without its last item), last item, depth and
transaction count.  Every metric follows from the counts in float64:

    support    = count / n
    confidence = count / count(parent)           (the root counts n)
    lift       = confidence / support({item})

and a rule A -> C is the path sorted(A) + sorted(C), found when that row
is a path of the table.  This module imports nothing of the program.
"""
from __future__ import annotations

import numpy as np


class RuleTable:
    def __init__(self, parent, item, count, n_transactions: int,
                 rank, max_len: int):
        """``parent``/``item``/``count`` are per row, row 0 the root
        (parent -1, item -1, count = n); ``rank[i]`` orders items."""
        self.parent = np.asarray(parent, np.int64)
        self.item = np.asarray(item, np.int64)
        self.count = np.asarray(count, np.float64)
        self.n = float(n_transactions)
        self.rank = np.asarray(rank, np.int64)
        self.n_items = int(self.rank.size)
        self.max_len = int(max_len)
        n = self.parent.size
        depth = self._depths()
        # anc[d, r]: the row's ancestor at depth d (itself at its own)
        anc = np.full((self.max_len + 1, n), -1, np.int32)
        cur = np.arange(n)
        live = np.ones(n, bool)
        live[0] = False
        for _ in range(self.max_len):
            d = depth[cur]
            ok = live & (d > 0)
            anc[d[ok], np.flatnonzero(ok)] = cur[ok]
            cur = np.where(ok, self.parent[cur], 0)
            live = ok
        self.depth = depth
        self.anc = anc
        # child lookup: (parent, item) -> row
        key = self.parent[1:] * (self.n_items + 1) + self.item[1:]
        order = np.argsort(key, kind="stable")
        self._keys = key[order]
        self._rows = order + 1
        # the singleton count of every item (0 when not in the table)
        self.c1 = np.zeros(self.n_items, np.float64)
        top = np.flatnonzero(self.parent == 0)
        self.c1[self.item[top]] = self.count[top]

    def _depths(self) -> np.ndarray:
        n = self.parent.size
        depth = np.zeros(n, np.int64)
        todo = np.arange(1, n)
        par = self.parent
        # iterate levels: a row's depth is its parent's plus one
        known = np.zeros(n, bool)
        known[0] = True
        while todo.size:
            ready = known[par[todo]]
            r = todo[ready]
            depth[r] = depth[par[r]] + 1
            known[r] = True
            todo = todo[~ready]
        return depth

    # ------------------------------------------------------------------
    def canonical(self, items) -> list:
        it = [int(x) for x in items]
        return sorted(it, key=lambda x: (
            self.rank[x] if 0 <= x < self.n_items else 1 << 40, x))

    def child(self, parents: np.ndarray, items: np.ndarray) -> np.ndarray:
        parents = np.asarray(parents, np.int64)
        items = np.asarray(items, np.int64)
        ok = (parents >= 0) & (items >= 0) & (items < self.n_items)
        key = np.where(ok, parents * (self.n_items + 1) + items, -1)
        i = np.searchsorted(self._keys, key)
        i = np.minimum(i, self._keys.size - 1)
        hit = ok & (self._keys.size > 0) & (self._keys[i] == key)
        return np.where(hit, self._rows[i], -1)

    def find(self, path) -> int:
        """Row of an item path taken as it is (no reordering), or -1."""
        row = 0
        for it in path:
            row = int(self.child(np.array([row]), np.array([it]))[0])
            if row < 0:
                return -1
        return row

    def path(self, row: int) -> tuple:
        out = []
        while row > 0:
            out.append(int(self.item[row]))
            row = int(self.parent[row])
        return tuple(out[::-1])

    # ------------------------------------------------------------------
    def metric(self, rows, name: str) -> np.ndarray:
        rows = np.asarray(rows, np.int64)
        c = self.count[rows]
        if name == "support":
            return c / self.n
        pc = self.count[self.parent[rows]]
        conf = np.where(pc > 0, c / np.where(pc > 0, pc, 1.0), 0.0)
        if name == "confidence":
            return conf
        if name == "lift":
            c1 = self.c1[self.item[rows]] / self.n
            return np.where(c1 > 0, conf / np.where(c1 > 0, c1, 1.0), 0.0)
        raise ValueError(f"unknown metric {name!r}")

    def rule(self, ant, con) -> dict:
        """The rule A -> C: found, its row, support, confidence, lift."""
        a, c = self.canonical(ant), self.canonical(con)
        row = self.find(a + c)
        if row <= 0 or not c:
            return {"found": False, "row": -1}
        arow = self.find(a)
        conf = self.count[row] / self.count[arow]
        if len(c) == 1:
            lift = float(self.metric([row], "lift")[0])
        else:
            crow = self.find(c)
            lift = conf / (self.count[crow] / self.n) if crow > 0 else 0.0
        return {"found": True, "row": row,
                "support": self.count[row] / self.n,
                "confidence": conf, "lift": lift}

    def subtree(self, prefix) -> np.ndarray:
        """Rows whose path starts with the canonical ``prefix``, the
        prefix's own row included (empty when the prefix is no path)."""
        p = self.canonical(prefix)
        if not p:
            return np.arange(1, self.parent.size)
        row = self.find(p)
        if row <= 0:
            return np.zeros(0, np.int64)
        return np.flatnonzero(self.anc[len(p)] == row)

    def holding(self, item: int, role: str) -> np.ndarray:
        """Rows holding ``item`` as antecedent (a strict ancestor's
        item), as consequent (the row's own item), or either."""
        cons = self.item == item
        cons[0] = False
        if role == "consequent":
            return np.flatnonzero(cons)
        ante = np.zeros(self.parent.size, bool)
        for d in range(1, self.max_len):
            a = self.anc[d]
            ok = (a >= 0) & (self.depth > d)
            ante |= ok & (self.item[np.maximum(a, 0)] == item)
        if role == "antecedent":
            return np.flatnonzero(ante)
        return np.flatnonzero(ante | cons)

    def top(self, rows: np.ndarray, name: str, k: int,
            rounding=None) -> tuple:
        """``(rows, values)`` of the k best rows by metric, best first.
        ``rounding`` (the control) rounds the values before ranking."""
        v = self.metric(rows, name)
        if rounding is not None:
            v = rounding(v)
        order = np.lexsort((rows, -v))[:k]
        return rows[order], v[order]
