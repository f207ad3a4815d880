"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never module-level state) so that
importing this module never touches jax device initialization.  The
single-pod mesh is 16×16 = 256 chips (data, model); the multi-pod mesh is
2×16×16 = 512 chips (pod, data, model) — the ``pod`` axis carries
inter-pod data parallelism (DCN-grade collectives only: gradient
all-reduce), while ``model`` stays intra-pod on ICI.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """Degenerate 1×1 mesh over the real local device (tests, examples)."""
    n = jax.device_count()
    return _make_mesh((1, n), ("data", "model"))


def make_trie_mesh(n_shards: int | None = None) -> Mesh:
    """1-D ``("data",)`` mesh for the sharded Trie-of-Rules engine.

    The frozen trie partitions into contiguous DFS subtree ranges, one per
    device along the single ``data`` axis (``distributed.trie_sharding``);
    there is no model axis — queries replicate, the STRUCTURE shards.
    ``n_shards`` defaults to every visible device; pass less to shard over
    a device prefix (benchmark P-sweeps, CI with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
    """
    n = jax.device_count() if n_shards is None else int(n_shards)
    if not 1 <= n <= jax.device_count():
        raise ValueError(
            f"n_shards={n} outside [1, {jax.device_count()}] "
            "visible devices"
        )
    return Mesh(
        np.array(jax.devices()[:n]), ("data",), axis_types=(AxisType.Auto,)
    )
