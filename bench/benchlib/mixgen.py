"""One general traffic generator: a mix's parameters in, requests out.

A mix file (``bench/traffic/<mix>.json``) names the ops and their
shares, how each op draws its queries from the store, the pool of
distinct queries and its Zipf exponent, the tenants and their deadlines,
and the arrival process.  Every seed gets the same amount of work in a
different order: the count of requests in the window, the gaps between
arrivals (quantiles of the exponential law, in the time of the rate),
the count of each op, the multiset of Zipf ranks and the tenants are
fixed by the mix; the seed permutes them and draws the queries.

A mix may name another as its ``base``: it then holds only what it
changes, and a key it gives replaces the base's (a dict, such as
``arrivals``, is merged one level down), so that two cells over one mix
at two loads or deadlines share every other parameter.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np


def load_mix(traffic_dir: Path, name: str, _seen: tuple = ()) -> dict:
    """The parameters of mix ``name`` (``<traffic_dir>/<name>.json``),
    over those of its ``base`` mix."""
    if name in _seen:
        raise ValueError(f"mix {name!r} is its own base")
    mix = json.loads((Path(traffic_dir) / f"{name}.json").read_text())
    base = mix.pop("base", None)
    if base is None:
        return mix
    out = load_mix(traffic_dir, base, _seen + (name,))
    for k, v in mix.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = {**out[k], **v}
        else:
            out[k] = v
    return out


@dataclasses.dataclass
class Request:
    due_s: float          # send time, seconds from the window's start
    op: str
    payload: object
    kwargs: dict
    tenant: str
    deadline_ms: float


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / max(n, 1)


def arrival_times(arr: dict, seconds: float, rng) -> np.ndarray:
    """Due times of the window's requests, sorted.  ``arr`` holds
    ``rate_qps`` (the mean) and optionally ``burst``: ``{"period_s",
    "length_s", "factor"}``, ``factor`` times the mean for ``length_s``
    at the start of each period, the rate between bursts lowered so that
    the mean stays ``rate_qps``."""
    mean = float(arr["rate_qps"])
    n = int(round(mean * seconds))
    if n <= 0:
        return np.zeros(0)
    gaps = -np.log1p(-_quantiles(n))
    gaps = rng.permutation(gaps / gaps.sum() * n)
    tau = np.cumsum(gaps) - gaps[0] * 0.5   # operational time in (0, n)
    burst = arr.get("burst")
    if not burst:
        return tau / mean
    period, length = float(burst["period_s"]), float(burst["length_s"])
    factor = float(burst["factor"])
    frac = length / period
    base = mean * (1.0 - frac * factor) / (1.0 - frac)
    if base <= 0:
        raise ValueError("burst too strong for its mean rate")
    grid = np.linspace(0.0, seconds, int(seconds * 1000) + 1)
    rate = np.where(np.mod(grid, period) < length, mean * factor, base)
    lam = np.concatenate([[0.0], np.cumsum(
        0.5 * (rate[1:] + rate[:-1]) * np.diff(grid))])
    return np.interp(tau * lam[-1] / n, lam, grid)


def zipf_ranks(n_draws: int, pool: int, s: float, rng) -> np.ndarray:
    """A fixed multiset of Zipf(s) ranks over ``pool``, permuted."""
    p = np.arange(1, pool + 1, dtype=np.float64) ** (-s)
    cdf = np.cumsum(p / p.sum())
    ranks = np.searchsorted(cdf, _quantiles(n_draws), side="right")
    return rng.permutation(np.minimum(ranks, pool - 1))


def split_counts(n: int, shares) -> np.ndarray:
    shares = np.asarray(shares, np.float64)
    raw = shares / shares.sum() * n
    c = np.floor(raw).astype(np.int64)
    c[np.argsort(-(raw - c))[: n - c.sum()]] += 1
    return c


class StoreView:
    """What the generator may read of a store: real paths (item lists in
    canonical order) and the catalogue by popularity."""

    def __init__(self, node_paths: np.ndarray, node_depth: np.ndarray,
                 popular_items: np.ndarray, n_items: int):
        self.paths = node_paths          # int [N, L], -1 padded
        self.depth = node_depth          # int [N]
        self.popular = popular_items
        self.n_items = int(n_items)

    def sample_paths(self, rng, n: int, lo: int, hi: int) -> np.ndarray:
        rows = np.flatnonzero((self.depth >= lo) & (self.depth <= hi))
        if rows.size == 0:
            raise ValueError(f"no path of depth {lo}..{hi} in the store")
        return rows[rng.integers(0, rows.size, size=n)]


def _path(view, row) -> list:
    d = int(view.depth[row])
    return [int(x) for x in view.paths[row, :d]]


def make_pool(spec: dict, view: StoreView, size: int, rng) -> list:
    """``size`` distinct (payload, kwargs) queries of one op spec."""
    op = spec["op"]
    metrics = spec.get("metrics", ["confidence"])
    k = int(spec.get("k", 10))
    if op == "rules_with":
        items = view.popular[:size]
        return [(int(it), {"role": spec.get("role", "any"), "k": k,
                           "metric": metrics[i % len(metrics)]})
                for i, it in enumerate(items)]
    seen, out = set(), []
    tries = 0
    while len(out) < size:
        tries += 1
        if tries > 50:
            raise ValueError(f"cannot draw {size} distinct {op} queries")
        m = 2 * (size - len(out)) + 8
        if op == "rule_search":
            lo, hi = spec["depth"]
            rows = view.sample_paths(rng, m, int(lo), int(hi))
            absent = rng.random(m) < float(spec.get("absent_share", 0.0))
            for row, ab in zip(rows, absent):
                p = _path(view, row)
                cl = int(spec.get("cons_len", 0))
                cut = len(p) - cl if cl else int(rng.integers(1, len(p)))
                ant, con = p[:cut], p[cut:]
                if ab:   # an absent rule: a random catalogue item
                    con = con[:-1] + [int(rng.integers(0, view.n_items))]
                key = (op, tuple(sorted(ant)), tuple(sorted(con)))
                if key in seen or set(ant) & set(con):
                    continue
                seen.add(key)
                # the client sends items in any order
                out.append(((list(rng.permutation(ant).tolist()),
                             list(rng.permutation(con).tolist())), {}))
        elif op == "top_k":
            lo, hi = spec["prefix_len"]
            rows = view.sample_paths(rng, m, int(lo), 12)
            lens = rng.integers(int(lo), int(hi) + 1, size=m)
            for row, ln in zip(rows, lens):
                p = _path(view, row)[: int(ln)]
                kw = {"k": k, "metric": metrics[len(out) % len(metrics)]}
                key = (op, tuple(sorted(p)), kw["metric"])
                if key in seen:
                    continue
                seen.add(key)
                out.append((list(rng.permutation(p).tolist()), kw))
        else:
            raise ValueError(f"unknown op {op!r} in the mix")
        out = out[:size]
    return out


def generate(mix: dict, view: StoreView, seconds: float, seed: int):
    """``(requests sorted by due time, pools)`` for one window."""
    rng = np.random.default_rng(seed)
    due = arrival_times(mix["arrivals"], seconds, rng)
    n = due.size
    specs = mix["ops"]
    counts = split_counts(n, [s["share"] for s in specs])
    op_of = rng.permutation(np.repeat(np.arange(len(specs)), counts))
    pools = []
    for spec in specs:
        size = int(spec.get("pool", mix.get("pool", 1024)))
        if spec["op"] == "rules_with":
            size = min(size, view.n_items)
        pools.append(make_pool(spec, view, size, rng))
    picks = np.zeros(n, np.int64)
    for j, spec in enumerate(specs):
        idx = np.flatnonzero(op_of == j)
        picks[idx] = zipf_ranks(idx.size, len(pools[j]),
                                float(mix["zipf_s"]), rng)
    n_ten = int(mix["tenants"])
    dls = [float(x) for x in mix["deadlines_ms"]]
    tenant = rng.permutation(np.arange(n) % n_ten)
    reqs = []
    for i in range(n):
        spec = specs[op_of[i]]
        payload, kw = pools[op_of[i]][picks[i]]
        t = int(tenant[i])
        reqs.append(Request(float(due[i]), spec["op"], payload, dict(kw),
                            f"tenant-{t}", dls[t % len(dls)]))
    return reqs, pools
