"""Shared benchmark utilities: timing, paired t-test, CSV rows."""
from __future__ import annotations

import math
import time
from dataclasses import dataclass
from typing import Callable, List, Sequence, Tuple


@dataclass
class Row:
    name: str
    us_per_call: float
    derived: str

    def csv(self) -> str:
        return f"{self.name},{self.us_per_call:.3f},{self.derived}"


def time_per_call(fn: Callable, n: int = 100, warmup: int = 3) -> float:
    """Mean seconds per call over n calls."""
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n


def time_per_call_median(
    fn: Callable, n: int = 100, warmup: int = 3
) -> float:
    """Median seconds per call — robust to GC/dispatch stragglers, which
    matters for sub-millisecond lanes in comparison benchmarks."""
    for _ in range(warmup):
        fn()
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def time_each(fns: Sequence[Callable]) -> List[float]:
    """Individually timed calls (paper Fig 9: per-rule distributions)."""
    out = []
    for fn in fns:
        t0 = time.perf_counter()
        fn()
        out.append(time.perf_counter() - t0)
    return out


def paired_t_test(a: Sequence[float], b: Sequence[float]) -> Tuple[float, float]:
    """Paired t-test on (a_i - b_i); returns (t_stat, two-sided p approx).

    Normal approximation of the t distribution is fine at n ≫ 30 (the
    paper's n is the full ruleset, thousands of pairs).
    """
    n = len(a)
    diffs = [x - y for x, y in zip(a, b)]
    mean = sum(diffs) / n
    var = sum((d - mean) ** 2 for d in diffs) / (n - 1)
    if var == 0:
        return float("inf"), 0.0
    t = mean / math.sqrt(var / n)
    p = math.erfc(abs(t) / math.sqrt(2.0))
    return t, p


def bench_interpret() -> bool:
    """Interpret-vs-compiled mode for every bench lane's kernel calls —
    ONE decision (``repro.kernels.ops.interpret_mode``): compiled on a
    TPU, interpreted elsewhere."""
    from repro.kernels.ops import interpret_mode

    return interpret_mode()


def bench_mode_fields() -> dict:
    """Provenance fields every bench JSON payload carries: execution mode
    (interpret vs compiled), backend, and the active tuning knobs — so a
    committed baseline is attributable to the configuration that made it."""
    import dataclasses

    import jax

    from repro.kernels.tuning import get_kernel_config

    return {
        "mode": "interpret" if bench_interpret() else "compiled",
        "backend": jax.default_backend(),
        "tuning": dataclasses.asdict(get_kernel_config()),
    }


def block_until_ready(x):
    return jax_block(x)


def jax_block(x):
    import jax

    return jax.block_until_ready(x)
