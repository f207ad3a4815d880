"""Roofline analysis: the per-device-kind kernel bandwidth figure
(``kernel_roofline``), the model-side term math on a synthetic dry-run
record, and the perf hillclimb driver's variant table."""
import math

import pytest

from repro.launch import roofline


class TestKernelRoofline:
    def test_achieved_and_util_math(self):
        # 25.6 GB moved in 2 s = 12.8 GB/s achieved = half the cpu peak
        out = roofline.kernel_roofline(25.6e9, 2.0, device_kind="cpu")
        assert out["device_kind"] == "cpu"
        assert out["achieved_gbps"] == pytest.approx(12.8)
        assert out["peak_gbps"] == pytest.approx(25.6)
        assert out["bandwidth_util"] == pytest.approx(0.5)

    def test_zero_seconds_is_zero_not_inf(self):
        out = roofline.kernel_roofline(1e9, 0.0, device_kind="cpu")
        assert out["achieved_gbps"] == 0.0
        assert out["bandwidth_util"] == 0.0

    def test_unknown_device_kind_raises(self):
        with pytest.raises(ValueError, match="quantum"):
            roofline.kernel_roofline(1e9, 1.0, device_kind="quantum")

    def test_default_backend_resolves(self):
        import jax

        out = roofline.kernel_roofline(1e9, 1.0)
        assert out["device_kind"] == jax.devices()[0].device_kind
        assert out["device_kind"] in roofline.KERNEL_PEAKS

    def test_peaks_table_shape(self):
        for name, peaks in roofline.KERNEL_PEAKS.items():
            assert peaks["peak_flops"] > 0, name
            assert peaks["hbm_gbps"] > 0, name
        # the v5e row must stay consistent with the model-side constants
        tpu = roofline.KERNEL_PEAKS["TPU v5 lite"]
        assert tpu["peak_flops"] == roofline.PEAK_FLOPS
        assert tpu["hbm_gbps"] == pytest.approx(roofline.HBM_BW / 1e9)


def _fake_record(arch, shape, flops=1e15, mem=1e12, coll=1e10):
    return {
        "arch": arch,
        "shape": shape,
        "mesh": "pod16x16",
        "n_devices": 256,
        "flops_per_device": flops,
        "bytes_per_device": mem,
        "collective_bytes_per_device": {"total": coll},
    }


class TestAnalyzeRecord:
    @pytest.fixture(scope="class")
    def arch_and_shape(self):
        from repro.configs import ARCH_IDS, SHAPES

        # pick a real (arch, train shape) so model_flops exercises the
        # actual config tables — catches arch-table drift
        shape = next(s.name for s in SHAPES if s.kind == "train")
        return ARCH_IDS[0], shape

    def test_terms_and_dominant(self, arch_and_shape):
        arch, shape = arch_and_shape
        rec = _fake_record(arch, shape)
        out = roofline.analyze_record(rec)
        t = out["terms"]
        assert t["compute_s"] == pytest.approx(1e15 / roofline.PEAK_FLOPS)
        assert t["memory_s"] == pytest.approx(1e12 / roofline.HBM_BW)
        assert t["collective_s"] == pytest.approx(1e10 / roofline.LINK_BW)
        assert out["dominant"] == "compute_s"
        assert out["useful_ratio"] > 0
        assert math.isfinite(out["roofline_fraction"])

    def test_model_flops_positive_for_every_arch(self):
        from repro.configs import ARCH_IDS, SHAPES

        shape = next(s.name for s in SHAPES if s.kind == "train")
        for arch in ARCH_IDS:
            assert roofline.model_flops(arch, shape) > 0, arch

    def test_what_moves_it_covers_each_bottleneck(self, arch_and_shape):
        arch, shape = arch_and_shape
        compute = roofline.analyze_record(_fake_record(arch, shape))
        memory = roofline.analyze_record(
            _fake_record(arch, shape, flops=1e12, mem=1e14)
        )
        coll = roofline.analyze_record(
            _fake_record(arch, shape, flops=1e12, coll=1e14)
        )
        assert memory["dominant"] == "memory_s"
        assert coll["dominant"] == "collective_s"
        msgs = {roofline.what_moves_it(r) for r in (compute, memory, coll)}
        assert len(msgs) == 3  # three distinct diagnoses

    def test_table_renders_markdown(self, arch_and_shape):
        arch, shape = arch_and_shape
        out = roofline.table([_fake_record(arch, shape)], mesh="pod16x16")
        lines = out.splitlines()
        assert lines[0].startswith("| arch |")
        assert arch in lines[2]


class TestPerfDriver:
    def test_import_has_no_env_side_effect(self, monkeypatch):
        # the hillclimb driver must not mutate XLA_FLAGS at import time
        # (importing it from a test or another tool would reconfigure
        # the process's device count)
        monkeypatch.delenv("XLA_FLAGS", raising=False)
        import importlib

        from repro.launch import perf

        importlib.reload(perf)
        import os

        assert "XLA_FLAGS" not in os.environ

    def test_variants_are_pure_config_transforms(self):
        from repro.configs import get_config
        from repro.launch.perf import VARIANTS

        cfg = get_config("smollm-360m")
        assert "baseline" in VARIANTS
        assert VARIANTS["baseline"](cfg) == cfg
        for name, fn in VARIANTS.items():
            out = fn(cfg)
            assert out is not None, name
        # purity: applying a non-trivial variant leaves the input alone
        VARIANTS["causal_skip"](cfg)
        assert cfg == get_config("smollm-360m")
