"""§Perf knob equivalence: optimizations must not change the math
(within bf16 reassociation tolerance)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.models import loss_fn, materialize_params


def _setup(arch="granite-3-2b", s=1024):
    cfg = get_reduced_config(arch)
    params, _ = materialize_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    batch = {
        "tokens": jnp.asarray(
            rng.randint(0, cfg.vocab_size, (1, s)), jnp.int32),
        "labels": jnp.asarray(
            rng.randint(0, cfg.vocab_size, (1, s)), jnp.int32),
    }
    return cfg, params, batch


def test_causal_skip_forward_equivalent():
    cfg, params, batch = _setup()
    l0, _ = loss_fn(cfg, params, batch)
    l1, _ = loss_fn(cfg.scaled(causal_skip=True), params, batch)
    l2, _ = loss_fn(
        cfg.scaled(causal_skip=True, unroll_scans=True), params, batch
    )
    assert abs(float(l0) - float(l1)) / float(l0) < 1e-3
    assert abs(float(l0) - float(l2)) / float(l0) < 1e-3


@pytest.mark.slow
def test_causal_skip_gradients_equivalent():
    cfg, params, batch = _setup(s=512)
    g0 = jax.grad(lambda p: loss_fn(cfg, p, batch)[0])(params)
    g1 = jax.grad(
        lambda p: loss_fn(cfg.scaled(causal_skip=True), p, batch)[0]
    )(params)
    ref = max(float(jnp.max(jnp.abs(a))) for a in jax.tree.leaves(g0))
    d = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1))
    )
    assert d < 1e-2 * max(ref, 1.0)


def test_causal_skip_with_segments():
    cfg, params, batch = _setup(s=512)
    segs = np.ones((1, 512), np.int32)
    segs[:, 300:] = 2   # two packed documents
    batch["segment_ids"] = jnp.asarray(segs)
    l0, _ = loss_fn(cfg, params, batch)
    l1, _ = loss_fn(cfg.scaled(causal_skip=True), params, batch)
    assert abs(float(l0) - float(l1)) / float(l0) < 1e-3


def test_remat_policy_dots_same_loss():
    cfg, params, batch = _setup(s=512)
    cfg_r = cfg.scaled(remat=True)
    cfg_d = cfg.scaled(remat=True, remat_policy="dots")
    l0, _ = loss_fn(cfg_r, params, batch)
    l1, _ = loss_fn(cfg_d, params, batch)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5)


def test_moe_psum_bf16_close():
    """bf16 psum knob changes only low-order bits of the MoE output."""
    from dataclasses import replace

    from repro.launch.mesh import make_host_mesh
    from repro.models.moe import moe_alltoall

    cfg = get_reduced_config("deepseek-v2-lite-16b").scaled(n_units=1)
    cfg = cfg.scaled(
        moe=replace(cfg.moe, impl="alltoall", capacity_factor=8.0)
    )
    params, _ = materialize_params(cfg, jax.random.PRNGKey(0))
    p_moe = jax.tree.map(lambda x: x[0], params["units"]["0"]["ffn"])
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 8, cfg.d_model) * 0.3, jnp.float32)
    mesh = make_host_mesh()
    with jax.set_mesh(mesh):
        y0, _ = jax.jit(lambda p, x: moe_alltoall(cfg, p, x))(p_moe, x)
        cfg_b = cfg.scaled(moe_psum_bf16=True)
        y1, _ = jax.jit(lambda p, x: moe_alltoall(cfg_b, p, x))(p_moe, x)
    np.testing.assert_allclose(
        np.asarray(y0), np.asarray(y1), rtol=2e-2, atol=2e-2
    )
