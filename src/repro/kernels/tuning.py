"""Per-backend kernel tuning: the ``KernelConfig`` registry.

The Pallas kernels' static launch knobs used to be hard-coded module
constants picked on one CPU host — ``rank.BN = 8192``,
``trie_reduce.BN = 8192`` and the serve scheduler's implicit pow2
launch-pad floor of 1.  The
data-structure literature is clear that these rankings invert across
execution environments, so the knobs are now *resolved at op-dispatch
time* from a committed per-backend tuning table instead:

1. an explicit override (``tuning_overrides`` context / ``set_kernel_config``),
2. else the committed table ``benchmarks/tuning/<backend>.json``
   (directory overridable via ``REPRO_TUNING_DIR``),
3. else the built-in defaults — exactly the historical constants, so a
   missing table reproduces pre-tuning behavior bit-for-bit.

Every knob is semantics-free by contract: kernels are bit-identical to
their jnp oracles at ANY legal knob value (``benchmarks/autotune.py``
asserts this at every swept point before writing a table; the one
exception is ``reduce_bn``, where retiling reassociates fp32 sums — the
count/max outputs stay bitwise, the sums hold to 1e-6).

Knobs
-----
``rank_bn``
    Nodes per VMEM tile for the segmented rank kernel
    (``rank.topk_rank_batch_pallas``, which also ranks ``rules_with``).
``reduce_bn``
    Nodes per tile for the traversal reduction (``trie_reduce``).
``launch_pad_floor``
    Minimum row count batched launches pad to (after the next-pow2
    round-up).  1 keeps pure pow2 padding; a larger floor trades a few
    padded rows for fewer distinct compiled launch shapes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import threading
from typing import Iterator, Optional

LANE = 128   # TPU lane width: tile knobs must be multiples of this


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    rank_bn: int = 8192
    reduce_bn: int = 8192
    launch_pad_floor: int = 1

    def validate(self) -> "KernelConfig":
        for name in ("rank_bn", "reduce_bn"):
            v = getattr(self, name)
            if not isinstance(v, int) or v <= 0 or v % LANE:
                raise ValueError(
                    f"KernelConfig.{name} must be a positive multiple of "
                    f"{LANE}, got {v!r}"
                )
            if v & (v - 1):
                raise ValueError(
                    f"KernelConfig.{name} must be a power of two "
                    f"(the autotune sweep grid), got {v}"
                )
        f = self.launch_pad_floor
        if not isinstance(f, int) or f < 1 or (f & (f - 1)):
            raise ValueError(
                f"KernelConfig.launch_pad_floor must be a power of two "
                f">= 1, got {f!r}"
            )
        return self


DEFAULTS = KernelConfig()
KNOB_NAMES = tuple(f.name for f in dataclasses.fields(KernelConfig))

_lock = threading.Lock()
_override: Optional[KernelConfig] = None
_table_cache: dict = {}        # backend -> Optional[KernelConfig]


def tuning_dir() -> str:
    """The per-backend table directory: ``REPRO_TUNING_DIR`` if set, else
    the repo-checkout ``benchmarks/tuning/`` next to this package."""
    env = os.environ.get("REPRO_TUNING_DIR")
    if env:
        return env
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.normpath(
        os.path.join(here, "..", "..", "..", "benchmarks", "tuning")
    )


def table_path(backend: str) -> str:
    return os.path.join(tuning_dir(), f"{backend}.json")


def load_table(backend: str) -> Optional[KernelConfig]:
    """The committed table's KernelConfig, or None when no table exists.
    Unknown keys in the table's ``knobs`` dict are ignored (forward
    compatibility with newer autotune drivers); known knobs are
    validated."""
    path = table_path(backend)
    try:
        with open(path) as fh:
            payload = json.load(fh)
    except FileNotFoundError:
        return None
    except (OSError, json.JSONDecodeError) as exc:
        raise ValueError(f"unreadable tuning table {path}: {exc}") from exc
    knobs = payload.get("knobs", {})
    known = {k: int(v) for k, v in knobs.items() if k in KNOB_NAMES}
    return dataclasses.replace(DEFAULTS, **known).validate()


def write_table(backend: str, cfg: KernelConfig, extra: dict = None,
                directory: Optional[str] = None) -> str:
    """Persist a tuned config (the autotune driver's output).  Returns
    the written path and invalidates the in-process cache."""
    cfg.validate()
    directory = directory or tuning_dir()
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, f"{backend}.json")
    payload = {
        "backend": backend,
        "generated_by": "benchmarks/autotune.py",
        "knobs": dataclasses.asdict(cfg),
    }
    if extra:
        payload.update(extra)
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2)
        fh.write("\n")
    reset_tuning_cache()
    return path


def _default_backend() -> str:
    import jax

    return jax.default_backend()


def get_kernel_config(backend: Optional[str] = None) -> KernelConfig:
    """The active KernelConfig: override > committed table > defaults.

    Table loads are cached per backend; call ``reset_tuning_cache`` after
    writing a new table (or changing ``REPRO_TUNING_DIR``) mid-process.
    """
    with _lock:
        if _override is not None:
            return _override
    if backend is None:
        backend = _default_backend()
    with _lock:
        if backend not in _table_cache:
            _table_cache[backend] = load_table(backend)
        cfg = _table_cache[backend]
    return cfg if cfg is not None else DEFAULTS


def set_kernel_config(cfg: Optional[KernelConfig]) -> None:
    """Process-wide override (None clears it back to table resolution)."""
    global _override
    if cfg is not None:
        cfg.validate()
    with _lock:
        _override = cfg


def reset_tuning_cache() -> None:
    with _lock:
        _table_cache.clear()


@contextlib.contextmanager
def tuning_overrides(**knobs) -> Iterator[KernelConfig]:
    """Scoped knob overrides on top of the currently-active config —
    the autotune sweep (and the tests) pin one knob at a time with this."""
    bad = set(knobs) - set(KNOB_NAMES)
    if bad:
        raise ValueError(
            f"unknown tuning knob(s) {sorted(bad)}; known: {KNOB_NAMES}"
        )
    base = get_kernel_config()
    cfg = dataclasses.replace(base, **knobs).validate()
    global _override
    with _lock:
        prev = _override
        _override = cfg
    try:
        yield cfg
    finally:
        with _lock:
            _override = prev


def launch_pad(n: int) -> int:
    """Batched-launch row padding: next power of two, floored at the
    active config's ``launch_pad_floor``.  The floor=1 default reproduces
    the historical pure-pow2 normalization exactly."""
    pow2 = 1 << max(int(n) - 1, 0).bit_length()
    return max(pow2, get_kernel_config().launch_pad_floor)
