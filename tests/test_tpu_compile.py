"""Ahead-of-time compiles of every main-path op for a described TPU v5e.

Interpret mode never meets the TPU compiler, which is where a kernel is
refused: a block that breaks the (8, 128) tiling rule, a gather from a
1-D vector, a scalar store to VMEM, more VMEM than a kernel may use.
These tests lower and compile each op for one chip of a described
``v5e:2x2`` topology — no chip needed — at the widths ``chip_smoke.py``
runs: a 2^20-node trie, Q = 64 queries, both layouts.  Nothing runs, so
they say nothing about results or times; the interpret-mode parity
suites do that.

The topology is described inside a module fixture (never while a module
is imported), so every test worker collects the same tests and only the
worker that runs this file loads the TPU library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.item_index import ROLES, rules_with_pallas
from repro.kernels.rank import topk_rank_batch_pallas
from repro.kernels.rule_search import rule_search_fused, rule_search_span
from repro.kernels.support_count import support_count_pallas
from repro.kernels.trie_reduce import trie_reduce_pallas

N = 1 << 20          # trie nodes (chip_smoke.py's scale phase)
Q = 64               # queries per launch
WIDTH = 16           # query-row width (pow2 of the trie depth)
K = 10
MAX_FANOUT = 256     # synthetic_csr_trie's hub root at this size
MAX_POSTINGS = 1 << 17
N_TX, N_ITEMS, N_CAND = 18_000, 3_600, 45_000   # Online Retail build

# metric column dtypes per layout: plain fp32; compressed as quantized
# by ``FrozenTrie.compress(quantize=True)`` (int32 support counts, bf16
# confidence/lift)
LAYOUT_DTYPES = {
    "plain": (jnp.float32, jnp.float32, jnp.float32),
    "compressed": (jnp.int32, jnp.bfloat16, jnp.bfloat16),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)`` → a ShapeDtypeStruct on one described chip.
    The persistent compile cache is off: entries written for a described
    chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip
    )
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text()  # the chip's compiler produced a program
    return compiled


def _columns(spec, layout):
    sup, conf, lift = LAYOUT_DTYPES[layout]
    return (
        spec((N,), sup), spec((N,), conf), spec((N,), lift),
        spec((N,), jnp.int32),
    )


def _dequant(layout):
    return {"n_transactions": N_TX} if layout == "compressed" else {}


@pytest.mark.parametrize("layout", sorted(LAYOUT_DTYPES))
def test_top_k_rank_compiles(spec, layout):
    fn = functools.partial(
        topk_rank_batch_pallas, k=K, metric="confidence", interpret=False,
        **_dequant(layout),
    )
    compiled = _compile(
        fn, *_columns(spec, layout),
        spec((Q,), jnp.int32), spec((Q,), jnp.int32),
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("role", ROLES)
@pytest.mark.parametrize("layout", sorted(LAYOUT_DTYPES))
def test_rules_with_compiles(spec, layout, role):
    fn = functools.partial(
        rules_with_pallas, k=K, metric="lift", role=role,
        max_postings=MAX_POSTINGS, interpret=False, **_dequant(layout),
    )
    compiled = _compile(
        fn, *_columns(spec, layout), spec((N,), jnp.int32),
        spec((N,), jnp.int32), spec((N,), jnp.int32),
        spec((Q,), jnp.int32), spec((Q,), jnp.int32),
        spec((Q,), jnp.int32),
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_rule_search_plain_compiles(spec):
    fn = functools.partial(rule_search_fused, max_fanout=MAX_FANOUT)
    e = N - 1
    _compile(
        fn, spec((N + 1,), jnp.int32), spec((e,), jnp.int32),
        spec((e,), jnp.int32), spec((e,), jnp.float32),
        spec((e,), jnp.float32), spec((e,), jnp.float32),
        spec((Q, WIDTH), jnp.int32), spec((Q,), jnp.int32),
    )


def test_rule_search_compressed_compiles(spec):
    sup, conf, lift = LAYOUT_DTYPES["compressed"]
    nc, ec = N // 4, N // 4 - 1
    fn = functools.partial(
        rule_search_span, max_fanout=MAX_FANOUT, n_transactions=N_TX,
    )
    _compile(
        fn, spec((nc + 1,), jnp.int32), spec((ec,), jnp.int32),
        spec((ec,), jnp.int32), spec((ec,), jnp.int32),
        spec((ec,), jnp.int32), spec((N,), jnp.int32),
        spec((N,), sup), spec((N,), conf), spec((N,), lift),
        spec((Q, WIDTH), jnp.int32), spec((Q,), jnp.int32),
    )


@pytest.mark.parametrize("layout", sorted(LAYOUT_DTYPES))
def test_trie_reduce_compiles(spec, layout):
    sup, conf, _ = LAYOUT_DTYPES[layout]
    fn = functools.partial(
        trie_reduce_pallas, interpret=False, **_dequant(layout)
    )
    compiled = _compile(
        fn, spec((N,), sup), spec((N,), conf), spec((N,), jnp.int32)
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_support_count_compiles(spec):
    fn = functools.partial(support_count_pallas, interpret=False)
    compiled = _compile(
        fn, spec((N_TX, N_ITEMS), jnp.uint8),
        spec((N_CAND, N_ITEMS), jnp.float32), spec((N_CAND,), jnp.int32),
    )
    assert "tpu_custom_call" in compiled.as_text()
