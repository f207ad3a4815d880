"""The comparison that decides ``correct``.

Each served answer and the reference's answer to the same query are
put in one form (paths of items, and values), then compared:

* ``mismatch`` counts answers that say a different thing: a found flag
  that differs, a returned rule that is not the one asked for or lies
  outside the query's scope, a rule returned twice, or a count of
  ranked rules that differs.  Its limit is 0.
* ``value_gap`` is the widest relative gap between a value the server
  returned and the reference's value: of the rule it returned, and of
  the reference's rule at the same rank.

Values within a tie of each other may come in either order, so ranks are
compared by value, and rules by membership in the query's scope.
"""
from __future__ import annotations

import numpy as np

RESULT_METRICS = ("support", "confidence", "lift")


def bf16_round(v: np.ndarray) -> np.ndarray:
    """Round float32 values to bfloat16 (nearest even), as float64."""
    f = np.asarray(v, np.float32).copy()
    bits = f.view(np.uint32).astype(np.uint64)
    bias = ((bits >> 16) & 1) + 0x7FFF
    out = ((bits + bias) & 0xFFFF0000).astype(np.uint32)
    return out.view(np.float32).astype(np.float64)


def query_key(op: str, payload, kwargs: dict) -> tuple:
    if op == "rule_search":
        a, c = payload
        return (op, tuple(a), tuple(c))
    if op == "top_k":
        return (op, tuple(payload), kwargs.get("metric"), kwargs.get("k"))
    return (op, int(payload), kwargs.get("role"), kwargs.get("metric"),
            kwargs.get("k"))


def served_answer(op: str, row: dict, paths: np.ndarray,
                  depth: np.ndarray) -> dict:
    """A server's result row in the common form: node ids become item
    paths (read off the served store's own parent/item arrays)."""
    def path(node):
        node = int(node)
        return tuple(int(x) for x in paths[node, : depth[node]])

    if op == "rule_search":
        found = bool(row["found"])
        out = {"found": found}
        if found:
            out["path"] = path(row["node"])
            for m in RESULT_METRICS:
                out[m] = float(row[m])
        return out
    nodes = np.asarray(row["node"])
    vals = np.asarray(row["values"], np.float64)
    live = nodes >= 0
    return {"paths": [path(n) for n in nodes[live]],
            "values": vals[live]}


class Reference:
    """Reference answers over a ``RuleTable``; ``rounding`` makes the
    control (values rounded before they are ranked and returned)."""

    def __init__(self, table, rounding=None):
        self.t = table
        self.rounding = rounding
        self._memo = {}

    def _r(self, v):
        v = np.asarray(v, np.float64)
        return v if self.rounding is None else self.rounding(v)

    def scope(self, op, payload, kwargs):
        if op == "top_k":
            return self.t.subtree(payload)
        return self.t.holding(int(payload), kwargs.get("role", "any"))

    def answer(self, op, payload, kwargs) -> dict:
        key = query_key(op, payload, kwargs)
        got = self._memo.get(key)
        if got is not None:
            return got
        t = self.t
        if op == "rule_search":
            ref = t.rule(*payload)
            out = {"found": ref["found"]}
            if ref["found"]:
                out["path"] = t.path(ref["row"])
                for m in RESULT_METRICS:
                    out[m] = float(self._r([ref[m]])[0])
        else:
            rows = self.scope(op, payload, kwargs)
            top, vals = t.top(rows, kwargs.get("metric", "confidence"),
                              int(kwargs.get("k", 10)),
                              rounding=self.rounding)
            out = {"paths": [t.path(r) for r in top], "values": vals,
                   "scope": rows}
        self._memo[key] = out
        return out


def _gap(got, want) -> float:
    got, want = float(got), float(want)
    if want == 0.0:
        return abs(got)
    return abs(got - want) / abs(want)


def compare_one(op, payload, kwargs, got: dict, truth: "Reference"):
    """``(mismatches, widest value gap)`` of one answer."""
    want = truth.answer(op, payload, kwargs)
    t = truth.t
    if op == "rule_search":
        if got["found"] != want["found"]:
            return 1, 0.0
        if not want["found"]:
            return 0, 0.0
        bad = int(got["path"] != want["path"])
        return bad, max(_gap(got[m], want[m]) for m in RESULT_METRICS)
    metric = kwargs.get("metric", "confidence")
    bad = int(len(got["paths"]) != len(want["paths"]))
    rows = np.array([t.find(p) for p in got["paths"]], np.int64)
    inside = np.isin(rows, want["scope"])
    if np.unique(rows).size != rows.size or not inside.all():
        bad += 1
    gap = 0.0
    if inside.any():
        own = t.metric(rows[inside], metric)
        gap = max(_gap(v, w) for v, w in zip(
            np.asarray(got["values"])[inside], own))
    n = min(len(got["values"]), len(want["values"]))
    if n:
        gap = max(gap, max(_gap(got["values"][j], want["values"][j])
                           for j in range(n)))
    return bad, gap


def compare(items, truth: "Reference") -> dict:
    """``items``: ``(op, payload, kwargs, answer)`` in the common form."""
    mismatch, gap = 0, 0.0
    for op, payload, kwargs, got in items:
        b, g = compare_one(op, payload, kwargs, got, truth)
        mismatch += b
        gap = max(gap, g)
    return {"mismatch": mismatch, "value_gap": gap, "compared": len(items)}


def sample(records, sizes, per_op: int, seed: int) -> list:
    """Indices of answered-ok requests to compare: up to ``per_op`` of
    each op, drawn from the seed, the largest query of each op always
    among them (``sizes``: items per request)."""
    rng = np.random.default_rng(seed ^ 0x5EED)
    by_op = {}
    for i, (s, resp) in enumerate(zip(records.status, records.responses)):
        if s == "ok" and resp is not None:
            by_op.setdefault(resp.op, []).append(i)
    out = set()
    for op, idx in sorted(by_op.items()):
        idx = np.asarray(idx)
        out.add(int(idx[np.argmax(np.asarray(sizes)[idx])]))
        out.update(int(i) for i in rng.permutation(idx)[: per_op - 1])
    return sorted(out)
