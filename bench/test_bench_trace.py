"""The trace reduction on small recorded traces, and the rank kernel's
byte count."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchlib import kernelbytes, tracereduce  # noqa: E402
from benchlib.tracereduce import Event  # noqa: E402

DEV, HOST = "/device:TPU:0", "/host:CPU"
MS = 1_000_000


def ev(plane, line, name, start_ms, dur_ms):
    return Event(plane, line, name, int(start_ms * MS), int(dur_ms * MS))


def recorded():
    """A 100 ms window: host step 0-40 (device busy 10-30 and an
    overlapping op 25-35), wait 40-80, step 80-100 (device 85-95); one
    device op outside the window."""
    return [
        ev(HOST, "main", "bench.step", 0, 40),
        ev(HOST, "main", "bench.wait", 40, 40),
        ev(HOST, "main", "bench.step", 80, 20),
        ev(DEV, "XLA Ops", "%rank.1 = f32[8] custom-call()", 10, 20),
        ev(DEV, "XLA Ops", "%gather.2 = s32[8] gather()", 25, 10),
        ev(DEV, "XLA Ops", "%rank.1 = f32[4] custom-call()", 85, 10),
        ev(DEV, "XLA Ops", "%rank.1 = f32[8] custom-call()", 150, 10),
        ev(DEV, "XLA Modules", "jit_rank(17)", 10, 15),
        ev(DEV, "XLA Modules", "jit_descent(4)", 25, 10),
        ev(DEV, "XLA Modules", "jit_rank(18)", 85, 10),
        ev(HOST, "main", "PjitFunction(add)", 2, 5),
        ev(HOST, "main", "PjitFunction(add)", 36, 2),
        ev(HOST, "main", "DevicePut", 81, 2),
        ev(HOST, "other", "elsewhere", 0, 100),
    ]


def test_busy_idle_and_per_op_time():
    s = tracereduce.summarize(recorded())
    assert s.window_s == pytest.approx(0.100)
    # busy = [10, 35) + [85, 95) = 35 ms
    assert s.busy_s == pytest.approx(0.035)
    assert s.idle_share == pytest.approx(0.65)
    # ops are keyed by program and short name; the first rank op ran
    # inside the jit_rank program, whose event starts with it
    assert s.op_seconds["jit_rank:%rank.1"] == pytest.approx(0.030)
    assert s.op_seconds["jit_descent:%gather.2"] == pytest.approx(0.010)
    assert len(s.matching("custom-call", module="jit_rank")) == 2
    assert len(s.matching("gather", module="descent")) == 1
    assert len(s.matching("jit_rank", line="XLA Modules")) == 2


def test_idle_gaps_by_host_annotation():
    s = tracereduce.summarize(recorded())
    # idle: [0,10) + [35,40) in a step, [40,80) waiting, [80,85) +
    # [95,100) in a step; inside steps, by the JAX call under way
    assert s.idle_by_host["bench.wait"] == pytest.approx(0.040)
    assert s.idle_by_host["bench.step/PjitFunction(add)"] == \
        pytest.approx(0.007)
    assert s.idle_by_host["bench.step/DevicePut"] == pytest.approx(0.002)
    assert s.idle_by_host["bench.step/python"] == pytest.approx(0.016)
    assert "bench.step/elsewhere" not in s.idle_by_host
    b = s.breakdown()
    assert b["idle_gaps"][0] == ["bench.wait", pytest.approx(0.040)]
    assert b["device_ops"][0][0] == "jit_rank:%rank.1"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_interval_helpers():
    assert tracereduce.union([(5, 7), (0, 3), (2, 4)]) == [(0, 4), (5, 7)]
    assert tracereduce.gaps([(2, 4), (6, 8)], 0, 10) == [
        (0, 2), (4, 6), (8, 10)]
    assert tracereduce.overlap([(0, 4), (6, 10)], [(2, 7)]) == 3


def test_no_annotation_is_an_error():
    with pytest.raises(ValueError):
        tracereduce.summarize([ev(DEV, "XLA Ops", "%rank.1 = f32[8]", 0, 1)])


def test_load_events_reads_a_recorded_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.step"):
            jax.block_until_ready(jnp.arange(8) * 2)
        with jax.profiler.TraceAnnotation("other"):
            pass
    finally:
        jax.profiler.stop_trace()
    events = tracereduce.load_events(tmp_path)
    names = [e.name for e in events]
    # the loop's thread comes whole: its annotations and JAX's own calls
    assert "bench.step" in names and "other" in names
    assert tracereduce.summarize(events).window_s > 0


@pytest.mark.parametrize("layout,dtypes,per_node", [
    ("plain", ("float32", "float32", "float32", "int32"), 16),
    ("compressed", ("int32", "bfloat16", "bfloat16", "int32"), 12),
])
def test_rank_bytes_per_layout(layout, dtypes, per_node):
    n, bn, q = 10_000, 8192, 64
    got = kernelbytes.rank_bytes(q, n, dtypes, bn, k=10, masked=False)
    per_query = 128 * 4 + 2 * 128 * 4
    assert got == q * (16384 * per_node + per_query)
    masked = kernelbytes.rank_bytes(q, n, dtypes, bn, k=10, masked=True)
    assert masked - got == q * 16384 * 4


RANK_HLO = (
    "%_topk_rank_batch_impl.1 = (f32[8,1,128]{2,1,0:T(1,128)}, "
    "s32[8,1,128]{2,1,0:T(1,128)}) custom-call(s32[8,1,128]{2,1,0} %p, "
    "f32[1,16384]{1,0} %a, f32[1,16384]{1,0} %b, f32[1,16384]{1,0} %c, "
    "s32[1,16384]{1,0} %d{member}), custom_call_target=\"tpu_custom_call\", "
    "operand_layout_constraints={{s32[8,1,128]{{2,1,0}}}}")


@pytest.mark.parametrize("masked", [False, True])
def test_rank_bytes_read_off_the_trace(masked):
    member = ", s32[8,1,16384]{2,1,0} %m" if masked else ""
    text = RANK_HLO.replace("{member}", member).replace("{{", "{").replace(
        "}}", "}")
    want = kernelbytes.rank_bytes(
        8, 10_000, ("float32", "float32", "float32", "int32"), 8192, k=10,
        masked=masked)
    assert kernelbytes.rank_bytes_from_hlo(text) == want


def test_roofline_share():
    # 819 MB at 819 GB/s takes 1 ms: in 2 ms that is half the roofline
    assert kernelbytes.roofline_share(819e6, 2e-3, 819e9) == \
        pytest.approx(50.0)


@pytest.mark.parametrize("name,stem", [
    ("device.idle_share", "device.idle_share"),
    ("device.idle_share.sat", "device.idle_share"),
    ("rank_roofline.sat", "rank_roofline"),
])
def test_a_split_metric_is_read_by_its_base_reader(name, stem):
    from benchlib import harness

    reader = harness.metric_reader(name)
    assert Path(reader.__file__).name == stem + ".py"


def test_a_metric_with_no_reader_is_an_error():
    from benchlib import harness

    with pytest.raises(FileNotFoundError):
        harness.metric_reader("no_such.metric")
