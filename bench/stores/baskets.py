"""Store kind ``baskets``: a rule store mined from seeded baskets.

The baskets copy the form of ``repro.arm.datasets.synthesize`` (Zipf item
popularity plus latent co-purchase profiles), vectorized so that a
basket log takes a fraction of a second.  The store is then built by the
deployment path: ``TransactionDB`` -> ``build_trie_of_rules(miner=
"fpgrowth", engine="arrays", use_kernel=...)`` -> ``FrozenTrie``.
"""
from __future__ import annotations

import time

import numpy as np

from benchlib.store import Store


def zipf_probs(n: int, a: float) -> np.ndarray:
    p = np.arange(1, n + 1, dtype=np.float64) ** (-a)
    return p / p.sum()


def generate_baskets(cfg: dict, seed: int):
    """``(indptr int64[B+1], items int32[nnz])``: one sorted, duplicate-
    free basket per row, drawn from ``seed`` with the configuration's
    ``assumed`` profile parameters."""
    a = cfg["assumed"]
    n_tx, n_items = int(cfg["n_baskets"]), int(cfg["n_items"])
    rng = np.random.default_rng(seed)
    probs = zipf_probs(n_items, float(a["zipf_a"]))
    # a seeded permutation decouples popularity rank from item id, as in
    # real catalogues
    item_of_rank = rng.permutation(n_items).astype(np.int64)
    n_prof = int(a["n_profiles"])
    lo, hi = int(a["profile_len_lo"]), int(a["profile_len_hi"])
    plen = rng.integers(lo, hi + 1, size=n_prof)
    prof = np.full((n_prof, hi), -1, np.int64)
    for p in range(n_prof):
        prof[p, : plen[p]] = rng.choice(
            n_items, size=plen[p], replace=False, p=probs)
    weights = rng.dirichlet(np.full(n_prof, 2.0))
    # one or two active profiles per basket
    two = rng.random(n_tx) < float(a["p_second_profile"])
    act = rng.choice(n_prof, size=(n_tx, 2), p=weights)
    pitems = prof[act].reshape(n_tx, 2 * hi)
    keep = rng.random(pitems.shape) < float(a["p_profile_item"])
    keep[:, hi:] &= two[:, None]
    pitems = np.where(keep & (pitems >= 0), pitems, -1)
    # background items: a Zipf draw of a per-basket count
    blo, bhi = int(a["background_lo"]), int(a["background_hi"])
    nbg = rng.integers(blo, bhi + 1, size=n_tx)
    bg = rng.choice(n_items, size=(n_tx, bhi), p=probs)
    bg = np.where(np.arange(bhi)[None, :] < nbg[:, None], bg, -1)
    rows = np.concatenate([pitems, bg], axis=1)
    rows = np.where(rows >= 0, item_of_rank[np.maximum(rows, 0)], -1)
    # sort each row, drop duplicates and padding
    rows.sort(axis=1)
    dup = np.zeros_like(rows, bool)
    dup[:, 1:] = rows[:, 1:] == rows[:, :-1]
    live = (rows >= 0) & ~dup
    empty = ~live.any(axis=1)
    if empty.any():   # a basket holds at least one item
        rows[empty, -1] = item_of_rank[0]
        live[empty, -1] = True
    counts = live.sum(axis=1)
    indptr = np.zeros(n_tx + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    return indptr, rows[live].astype(np.int32)


def build(cfg: dict, seed: int) -> Store:
    from repro.arm.transactions import TransactionDB
    from repro.core.builder import build_trie_of_rules

    t0 = time.perf_counter()
    indptr, items = generate_baskets(cfg, seed)
    t1 = time.perf_counter()
    db = TransactionDB(np.split(items, indptr[1:-1]),
                       n_items=int(cfg["n_items"]))
    t2 = time.perf_counter()
    b = cfg["build"]
    res = build_trie_of_rules(
        db, float(cfg["minsup"]), miner=b["miner"], max_len=int(
            cfg["max_len"]), engine=b["engine"],
        use_kernel=bool(b["use_kernel"]),
    )
    t3 = time.perf_counter()
    counts = np.bincount(items, minlength=int(cfg["n_items"]))
    popular = np.lexsort((np.arange(counts.size), -counts))
    return Store(
        frozen=res.frozen,
        data={"indptr": indptr, "items": items,
              "n_items": int(cfg["n_items"]),
              "minsup": float(cfg["minsup"]),
              "max_len": int(cfg["max_len"])},
        popular_items=popular.astype(np.int64),
        info={"baskets": int(indptr.size - 1),
              "mean_basket": float(items.size / (indptr.size - 1)),
              "nodes": int(res.frozen.n_nodes),
              "generate_s": t1 - t0, "db_s": t2 - t1,
              "mine_s": res.mine_seconds,
              "build_s": t3 - t2 - res.mine_seconds},
    )
