"""The harness end to end on the CPU at a tiny size: it refuses a
machine with no TPU, a sound run is correct, and a run whose timed path
is broken underneath, or the control in the program's place, is not.

The runs go in one child process (they change JAX's compile-cache
settings, which must not leak into other tests of this worker)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from benchlib import harness  # noqa: E402

CHILD_SCRIPT = r'''
import json, sys, time
import numpy as np
sys.path.insert(0, {bench!r})
from benchlib import harness
from repro.kernels import ops
from repro.serve import scheduler as sched_mod

cell = "retail.recs"
_, _, _, mix = harness.load_cell(cell)
cfg_over = {{"n_baskets": 1500, "n_items": 150, "minsup": 0.01,
            "scheduler": {{"max_pending": 256, "max_batch": 4,
                          "cache_size": 64}}}}
mix_over = {{"ops": [dict(o, pool=60) for o in mix["ops"]],
            "arrivals": {{"rate_qps": 200.0}}}}

def run(control=False):
    return harness.run_cell(cell, 20240601, 2.0, False,
                            t_start=time.perf_counter(), require_tpu=False,
                            cfg_over=cfg_over, mix_over=mix_over,
                            log=lambda s: None, control=control)

out = {{}}
r = run(control=True)
out["sound"] = r["checks"]; out["sound_correct"] = r["correct"]
out["control"] = r["control"]

# an answer altered where it is produced: the rank kernel's values
orig_rank = ops.topk_rank_batch_pallas
def nudged(*a, **kw):
    vals, pos = orig_rank(*a, **kw)
    return vals * np.float32(1.001), pos
ops.topk_rank_batch_pallas = nudged
try:
    r = run()
finally:
    ops.topk_rank_batch_pallas = orig_rank
out["altered"] = r["checks"]; out["altered_correct"] = r["correct"]

# half of each batch left out: its rows answered with the first half's
orig_exec = sched_mod.TrieScheduler._execute
def half(self, op, reps):
    h = (len(reps) + 1) // 2
    res, info = orig_exec(self, op, reps[:h])
    fill = lambda v: np.concatenate(
        [np.asarray(v)[:h], np.asarray(v)[:len(reps) - h]])
    return {{k: fill(v) for k, v in res.items()}}, info
sched_mod.TrieScheduler._execute = half
try:
    r = run()
finally:
    sched_mod.TrieScheduler._execute = orig_exec
out["half"] = r["checks"]; out["half_correct"] = r["correct"]
print("RESULT " + json.dumps(out))
'''


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("bench_runs")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp / "jax_cache")
    src = str(BENCH.parent / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", CHILD_SCRIPT.format(bench=str(BENCH))],
        capture_output=True, text=True, timeout=600, env=env, cwd=tmp)
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("RESULT ")]
    assert proc.returncode == 0 and lines, proc.stderr[-4000:]
    return json.loads(lines[-1][len("RESULT "):])


def test_harness_refuses_a_machine_without_a_tpu(capsys):
    rc = harness.main(["--workload", "retail.recs", "--seed", "1",
                       "--seconds", "1", "--trace", "0"], t_start=0.0)
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "no TPU" in out.err


def test_harness_refuses_an_unknown_cell(capsys):
    rc = harness.main(["--workload", "no.such", "--seed", "1",
                       "--seconds", "1", "--trace", "0"], t_start=0.0)
    assert rc != 0 and capsys.readouterr().out == ""


def test_a_sound_run_is_correct(runs):
    assert runs["sound_correct"], runs["sound"]
    assert runs["sound"]["mismatch"]["value"] == 0


@pytest.mark.parametrize("fault", ["altered", "half"])
def test_a_broken_timed_path_is_not_correct(runs, fault):
    assert runs[fault + "_correct"] is False, runs[fault]


def test_the_control_fails_the_limits(runs):
    limit = runs["sound"]["value_gap"]["limit"]
    c = runs["control"]
    assert c["value_gap"] > limit or c["mismatch"] > 0
