"""Distribution-layer tests: logical sharding rules, MoE impl parity,
elastic re-meshing, and specs plumbing on the local host mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_reduced_config
from repro.distributed.sharding import logical_to_spec
from repro.launch.mesh import make_host_mesh
from repro.models import materialize_params
from repro.models.moe import moe_alltoall, moe_dense


def _mesh(shape, axes):
    return jax.sharding.AbstractMesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


class TestLogicalRules:
    MESH = _mesh((16, 16), ("data", "model"))
    MP = _mesh((2, 16, 16), ("pod", "data", "model"))

    def test_divisible_shards(self):
        spec = logical_to_spec(
            ("fsdp", "mlp"), self.MESH, (4096, 16384)
        )
        assert spec == P("data", "model")

    def test_indivisible_falls_back_to_replication(self):
        # smollm: 15 heads on a 16-wide model axis → replicate
        spec = logical_to_spec(
            ("batch", "seq", "heads", None), self.MESH, (256, 4096, 15, 64)
        )
        assert spec == P("data", None, None, None)

    def test_granite_vocab_fallback(self):
        spec = logical_to_spec(
            ("vocab", "fsdp"), self.MESH, (49155, 2048)
        )
        assert spec == P(None, "data")

    def test_axis_used_once(self):
        spec = logical_to_spec(
            ("mlp", "heads"), self.MESH, (256, 256)
        )
        # both want "model"; only the first gets it
        assert spec == P("model", None)

    def test_multi_pod_batch(self):
        spec = logical_to_spec(
            ("batch", "seq"), self.MP, (256, 4096)
        )
        assert spec == P(("pod", "data"), None)

    def test_seq_kv_soaks_free_axes(self):
        # decode_32k: batch takes (pod,data); seq_kv picks up model
        spec = logical_to_spec(
            ("batch", "kv_heads", "seq_kv", None), self.MP,
            (128, 8, 32768, 128),
        )
        assert spec == P(("pod", "data"), None, "model", None)
        # long_500k: batch=1 unshardable → seq_kv takes everything
        spec = logical_to_spec(
            ("batch", "kv_heads", "seq_kv", None), self.MP,
            (1, 8, 524288, 128),
        )
        assert spec == P(None, None, ("model", "data", "pod"), None)

    def test_partial_prefix_on_indivisible(self):
        # 524288 % 512 == 0 but if batch were 3 → falls to prefix subsets
        spec = logical_to_spec(("seq_kv",), self.MP, (16 * 3,))
        # (model,data,pod)=512 ✗ → (model,data)=256 ✗ → (model)=16 ✓
        assert spec == P("model")


class TestMoEParity:
    def test_dense_equals_alltoall_on_host_mesh(self):
        """The EP path (sort/capacity/psum) must reproduce the dense
        oracle when capacity is not binding — run on the 1×1 host mesh."""
        cfg = get_reduced_config("deepseek-v2-lite-16b").scaled(n_units=1)
        from dataclasses import replace

        cfg = cfg.scaled(
            moe=replace(cfg.moe, impl="alltoall", capacity_factor=8.0)
        )
        params, _ = materialize_params(cfg, jax.random.PRNGKey(0))
        # grab the moe params of the first (only) unit layer
        p_moe = jax.tree.map(lambda x: x[0], params["units"]["0"]["ffn"])
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(2, 8, cfg.d_model) * 0.3, jnp.float32)
        mesh = make_host_mesh()
        with jax.set_mesh(mesh):
            y_ep, aux_ep = jax.jit(
                lambda p, x: moe_alltoall(cfg, p, x)
            )(p_moe, x)
            y_dense, aux_dense = jax.jit(
                lambda p, x: moe_dense(cfg, p, x)
            )(p_moe, x)
        np.testing.assert_allclose(
            np.asarray(y_ep), np.asarray(y_dense), rtol=2e-2, atol=2e-3
        )
        np.testing.assert_allclose(
            float(aux_ep), float(aux_dense), rtol=1e-4
        )

    def test_capacity_drops_tokens_gracefully(self):
        cfg = get_reduced_config("deepseek-v2-lite-16b").scaled(n_units=1)
        from dataclasses import replace

        cfg = cfg.scaled(
            moe=replace(cfg.moe, impl="alltoall", capacity_factor=0.1)
        )
        params, _ = materialize_params(cfg, jax.random.PRNGKey(0))
        p_moe = jax.tree.map(lambda x: x[0], params["units"]["0"]["ffn"])
        x = jnp.ones((2, 8, cfg.d_model), jnp.float32)
        mesh = make_host_mesh()
        with jax.set_mesh(mesh):
            y, aux = jax.jit(lambda p, x: moe_alltoall(cfg, p, x))(
                p_moe, x
            )
        assert jnp.isfinite(y).all()


class TestElastic:
    def test_remesh_state_roundtrip(self):
        from repro.train.elastic import remesh_state

        mesh = make_host_mesh()
        tree = {"w": jnp.arange(8.0).reshape(2, 4)}
        axes = {"w": ("fsdp", "mlp")}
        out = remesh_state(tree, axes, mesh)
        np.testing.assert_array_equal(
            np.asarray(out["w"]), np.asarray(tree["w"])
        )
        assert out["w"].sharding.mesh.shape == dict(mesh.shape)


class TestHostMeshLowering:
    """specs + jit plumbing compiles on the local 1-device mesh."""

    @pytest.mark.parametrize(
        "arch", ["granite-3-2b", "deepseek-v2-lite-16b", "mamba2-370m"]
    )
    def test_reduced_train_step_compiles_under_mesh(self, arch):
        from repro.train.optimizer import pick_optimizer
        from repro.train.train_step import make_train_step

        cfg = get_reduced_config(arch)
        mesh = make_host_mesh()
        with jax.set_mesh(mesh):
            params, _ = materialize_params(cfg, jax.random.PRNGKey(0))
            opt = pick_optimizer(cfg)
            state = opt.init(params)
            step = jax.jit(make_train_step(cfg, opt))
            rng = np.random.RandomState(0)
            batch = {
                "tokens": jnp.asarray(
                    rng.randint(0, cfg.vocab_size, (2, 16)), jnp.int32),
                "labels": jnp.asarray(
                    rng.randint(0, cfg.vocab_size, (2, 16)), jnp.int32),
            }
            p2, s2, m = step(params, state, batch, jnp.float32(0))
            assert jnp.isfinite(m["loss"])
