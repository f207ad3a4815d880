"""The kernel-tuning registry: KernelConfig validation, table
resolution order (override > committed table > defaults), the scoped
``tuning_overrides`` context, ``launch_pad``'s floor semantics, and the
``REPRO_FORCE_INTERPRET`` execution-mode override (refused on a TPU)."""
import dataclasses
import json

import pytest

from repro.kernels import ops
from repro.kernels.tuning import (
    DEFAULTS,
    KNOB_NAMES,
    KernelConfig,
    get_kernel_config,
    launch_pad,
    load_table,
    reset_tuning_cache,
    set_kernel_config,
    table_path,
    tuning_overrides,
    write_table,
)


@pytest.fixture
def isolated_tables(tmp_path, monkeypatch):
    """Point the registry at an empty table dir so the repo's committed
    ``benchmarks/tuning/cpu.json`` can't leak into resolution tests."""
    monkeypatch.setenv("REPRO_TUNING_DIR", str(tmp_path))
    reset_tuning_cache()
    set_kernel_config(None)
    yield tmp_path
    reset_tuning_cache()
    set_kernel_config(None)


class TestKernelConfig:
    def test_defaults_are_historical_constants(self):
        assert DEFAULTS.rank_bn == 8192
        assert DEFAULTS.reduce_bn == 8192
        assert DEFAULTS.launch_pad_floor == 1
        DEFAULTS.validate()  # defaults must self-validate

    @pytest.mark.parametrize("knob", ["rank_bn", "reduce_bn"])
    @pytest.mark.parametrize("bad", [0, -128, 100, 192, 8192 + 128])
    def test_tile_knobs_must_be_pow2_lane_multiples(self, knob, bad):
        with pytest.raises(ValueError):
            dataclasses.replace(DEFAULTS, **{knob: bad}).validate()

    @pytest.mark.parametrize("bad", [0, -1, 3, 6])
    def test_launch_pad_floor_must_be_pow2(self, bad):
        with pytest.raises(ValueError):
            dataclasses.replace(DEFAULTS, launch_pad_floor=bad).validate()


class TestTableResolution:
    def test_missing_table_falls_back_to_defaults(self, isolated_tables):
        assert get_kernel_config("cpu") == DEFAULTS
        assert load_table("cpu") is None

    def test_write_then_load_round_trip(self, isolated_tables):
        cfg = dataclasses.replace(DEFAULTS, rank_bn=4096,
                                  launch_pad_floor=2)
        path = write_table("cpu", cfg, extra={"smoke": True})
        assert path == table_path("cpu")
        payload = json.loads(open(path).read())
        assert payload["backend"] == "cpu"
        assert payload["smoke"] is True
        assert payload["knobs"]["rank_bn"] == 4096
        # write_table invalidates the cache, so resolution sees it
        assert get_kernel_config("cpu") == cfg

    def test_unknown_table_knobs_ignored(self, isolated_tables):
        with open(table_path("cpu"), "w") as fh:
            json.dump({"knobs": {"rank_bn": 4096,
                                 "knob_from_the_future": 7}}, fh)
        reset_tuning_cache()
        assert load_table("cpu").rank_bn == 4096

    def test_invalid_table_raises(self, isolated_tables):
        with open(table_path("cpu"), "w") as fh:
            json.dump({"knobs": {"rank_bn": 100}}, fh)
        reset_tuning_cache()
        with pytest.raises(ValueError):
            load_table("cpu")

    def test_override_beats_table(self, isolated_tables):
        write_table("cpu", dataclasses.replace(DEFAULTS, rank_bn=4096))
        forced = dataclasses.replace(DEFAULTS, rank_bn=1024)
        set_kernel_config(forced)
        assert get_kernel_config("cpu") == forced
        set_kernel_config(None)
        assert get_kernel_config("cpu").rank_bn == 4096


class TestTuningOverrides:
    def test_scoped_override_and_restore(self, isolated_tables):
        before = get_kernel_config()
        with tuning_overrides(reduce_bn=4096) as cfg:
            assert cfg.reduce_bn == 4096
            assert get_kernel_config().reduce_bn == 4096
        assert get_kernel_config() == before

    def test_unknown_knob_rejected(self, isolated_tables):
        with pytest.raises(ValueError, match="unknown tuning knob"):
            with tuning_overrides(block_size=256):
                pass  # pragma: no cover

    def test_nested_overrides_compose(self, isolated_tables):
        with tuning_overrides(rank_bn=4096):
            with tuning_overrides(reduce_bn=2048) as inner:
                # inner layers on top of the outer override
                assert inner.rank_bn == 4096
                assert inner.reduce_bn == 2048
            assert get_kernel_config().reduce_bn == DEFAULTS.reduce_bn
            assert get_kernel_config().rank_bn == 4096

    def test_knob_names_cover_all_fields(self):
        assert set(KNOB_NAMES) == {
            f.name for f in dataclasses.fields(KernelConfig)
        }


class TestLaunchPad:
    def test_pure_pow2_at_default_floor(self, isolated_tables):
        assert [launch_pad(n) for n in (1, 2, 3, 5, 8, 9)] == \
            [1, 2, 4, 8, 8, 16]

    def test_floor_applies_below_only(self, isolated_tables):
        with tuning_overrides(launch_pad_floor=8):
            assert launch_pad(1) == 8
            assert launch_pad(3) == 8
            assert launch_pad(9) == 16  # above the floor: plain pow2


class TestInterpretMode:
    @pytest.fixture(autouse=True)
    def fresh_cache(self):
        ops._interpret_cache.clear()
        yield
        ops._interpret_cache.clear()

    def test_default_interprets_off_tpu(self, monkeypatch):
        monkeypatch.delenv("REPRO_FORCE_INTERPRET", raising=False)
        import jax

        expected = jax.default_backend() != "tpu"
        assert ops.interpret_mode() is expected

    @pytest.mark.parametrize("val,mode", [
        ("1", True), ("true", True), ("interpret", True), ("ON", True),
        ("0", False), ("false", False), ("compiled", False), ("Off", False),
    ])
    def test_env_override(self, monkeypatch, val, mode):
        monkeypatch.setenv("REPRO_FORCE_INTERPRET", val)
        assert ops.interpret_mode() is mode

    def test_unrecognized_value_raises(self, monkeypatch):
        monkeypatch.setenv("REPRO_FORCE_INTERPRET", "maybe")
        with pytest.raises(ValueError, match="REPRO_FORCE_INTERPRET"):
            ops.interpret_mode()

    def test_flip_mid_process_takes_effect(self, monkeypatch):
        monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
        assert ops.interpret_mode() is True
        monkeypatch.setenv("REPRO_FORCE_INTERPRET", "0")
        # cached per (env value, backend): a new value is a new key
        assert ops.interpret_mode() is False

    def test_back_compat_alias(self):
        assert ops._interpret is ops.interpret_mode

    @pytest.mark.parametrize("val", ["1", "interpret"])
    def test_truthy_refused_on_tpu(self, monkeypatch, val):
        monkeypatch.setenv("REPRO_FORCE_INTERPRET", val)
        monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
        with pytest.raises(ValueError, match="on a TPU"):
            ops.interpret_mode()

    def test_tpu_compiles_by_default(self, monkeypatch):
        monkeypatch.delenv("REPRO_FORCE_INTERPRET", raising=False)
        monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
        assert ops.interpret_mode() is False
