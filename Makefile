PY := python
export PYTHONPATH := src

.PHONY: test test-fast test-slow test-multidevice lint bench-smoke \
	bench-gate bench-baseline bench-search bench-topk bench-build \
	bench-batched bench-traversal bench-sharded bench-serve \
	bench-compress bench-streaming bench-obs bench autotune \
	autotune-smoke

# 8 simulated CPU devices for the sharded-trie tier (tests + benches)
MULTIDEV := XLA_FLAGS=--xla_force_host_platform_device_count=8

# tier-1 verification (ROADMAP.md)
test:
	$(PY) -m pytest -x -q

# the CI split: fast excludes @pytest.mark.slow (target < ~2 min with
# HYPOTHESIS_PROFILE=ci), slow runs only the marked cases
test-fast:
	$(PY) -m pytest -x -q -m "not slow"

test-slow:
	$(PY) -m pytest -x -q -m slow

# the multi-device tier: the sharded suite plus the serve loop's
# degraded-mode cases under 8 simulated CPU devices (P in {1, 2, 8} all
# execute; on plain hosts the same tests cover P=1)
test-multidevice:
	$(MULTIDEV) $(PY) -m pytest -x -q tests/test_sharded.py \
		tests/test_serve_loop.py tests/test_streaming.py

# static checks (ruff config lives in pyproject.toml)
lint:
	$(PY) -m ruff check src tests benchmarks examples

# tiny-trie smoke of the search + ranked-extraction + construction
# benchmarks; writes to separate JSONs so it never clobbers the full-run
# perf-trajectory artifacts
bench-smoke:
	$(PY) -m benchmarks.run --only search --smoke \
		--json-out BENCH_rule_search_smoke.json --json-out-topk '' \
		--json-out-build '' --json-out-batched ''
	$(PY) -m benchmarks.run --only topk --smoke \
		--json-out '' --json-out-topk BENCH_topk_smoke.json \
		--json-out-build '' --json-out-batched ''
	$(PY) -m benchmarks.run --only build_engines --smoke \
		--json-out '' --json-out-topk '' \
		--json-out-build BENCH_build_smoke.json --json-out-batched ''
	$(PY) -m benchmarks.run --only batched_query --smoke \
		--json-out '' --json-out-topk '' --json-out-build '' \
		--json-out-batched BENCH_batched_query_smoke.json
	$(PY) -m benchmarks.run --only traversal --smoke \
		--json-out '' --json-out-topk '' --json-out-build '' \
		--json-out-batched '' \
		--json-out-traversal BENCH_traversal_smoke.json
	$(MULTIDEV) $(PY) -m benchmarks.run --only sharded_query --smoke \
		--json-out '' --json-out-topk '' --json-out-build '' \
		--json-out-batched '' \
		--json-out-sharded BENCH_sharded_query_smoke.json
	$(PY) -m benchmarks.run --only serve_loop --smoke \
		--json-out '' --json-out-topk '' --json-out-build '' \
		--json-out-batched '' \
		--json-out-serve BENCH_serve_smoke.json
	$(PY) -m benchmarks.run --only compress_layout --smoke \
		--json-out '' --json-out-topk '' --json-out-build '' \
		--json-out-batched '' \
		--json-out-compress BENCH_compress_smoke.json
	$(PY) -m benchmarks.run --only streaming --smoke \
		--json-out '' --json-out-topk '' --json-out-build '' \
		--json-out-batched '' \
		--json-out-streaming BENCH_streaming_smoke.json
	$(PY) -m benchmarks.run --only obs_overhead --smoke \
		--json-out '' --json-out-topk '' --json-out-build '' \
		--json-out-batched '' \
		--json-out-obs BENCH_obs_smoke.json

# CI bench gate: every lane in benchmarks/gates.json gets a fresh smoke
# run and is gated against its committed baseline (ratio-based; per-lane
# run spec, env, and slack all live in the manifest — including the
# autotune sweep)
bench-gate:
	$(PY) benchmarks/check_regression.py --run-all

# refresh the committed gate baselines (explicit — bench-smoke never
# touches them)
bench-baseline:
	$(PY) -m benchmarks.run --only rule_search_kernels --smoke \
		--json-out benchmarks/baselines/rule_search_smoke.json \
		--json-out-topk '' --json-out-build '' --json-out-batched ''
	$(PY) -m benchmarks.run --only topk --smoke \
		--json-out '' --json-out-topk benchmarks/baselines/topk_smoke.json \
		--json-out-build '' --json-out-batched ''
	$(PY) -m benchmarks.run --only build_engines --smoke \
		--json-out '' --json-out-topk '' \
		--json-out-build benchmarks/baselines/build_smoke.json \
		--json-out-batched ''
	$(PY) -m benchmarks.run --only batched_query --smoke \
		--json-out '' --json-out-topk '' --json-out-build '' \
		--json-out-batched benchmarks/baselines/batched_query_smoke.json
	$(PY) -m benchmarks.run --only traversal --smoke \
		--json-out '' --json-out-topk '' --json-out-build '' \
		--json-out-batched '' \
		--json-out-traversal benchmarks/baselines/traversal_smoke.json
	$(MULTIDEV) $(PY) -m benchmarks.run --only sharded_query --smoke \
		--json-out '' --json-out-topk '' --json-out-build '' \
		--json-out-batched '' \
		--json-out-sharded benchmarks/baselines/sharded_query_smoke.json
	$(PY) -m benchmarks.run --only serve_loop --smoke \
		--json-out '' --json-out-topk '' --json-out-build '' \
		--json-out-batched '' \
		--json-out-serve benchmarks/baselines/serve_smoke.json
	$(PY) -m benchmarks.run --only compress_layout --smoke \
		--json-out '' --json-out-topk '' --json-out-build '' \
		--json-out-batched '' \
		--json-out-compress benchmarks/baselines/compress_smoke.json
	$(PY) -m benchmarks.run --only streaming --smoke \
		--json-out '' --json-out-topk '' --json-out-build '' \
		--json-out-batched '' \
		--json-out-streaming benchmarks/baselines/streaming_smoke.json
	$(PY) -m benchmarks.run --only obs_overhead --smoke \
		--json-out '' --json-out-topk '' --json-out-build '' \
		--json-out-batched '' \
		--json-out-obs benchmarks/baselines/obs_smoke.json
	$(PY) -m benchmarks.autotune --smoke --no-write-table \
		--json-out benchmarks/baselines/autotune_smoke.json

# full per-backend kernel autotune: sweeps every tuning knob over its
# pow2 grid with bit-parity asserted against the jnp oracles at every
# point, then commits the winner table to benchmarks/tuning/<backend>.json
autotune:
	$(PY) -m benchmarks.autotune --json-out BENCH_autotune.json

# CI-sized sweep (tiny trie, reduced grids); never writes the table
autotune-smoke:
	$(PY) -m benchmarks.autotune --smoke --no-write-table \
		--json-out BENCH_autotune_smoke.json

# full rule-search kernel comparison (seed sweep vs CSR fused vs oracles)
bench-search:
	$(PY) -m benchmarks.run --only rule_search_kernels

# segmented top-k rank kernel vs lax.top_k vs full-sort oracles
bench-topk:
	$(PY) -m benchmarks.run --only topk

# pointer vs array-native construction engines (miner → DeviceTrie)
bench-build:
	$(PY) -m benchmarks.run --only build_engines

# one-launch batched query ops vs the Q-launch loop (serving shape)
bench-batched:
	$(PY) -m benchmarks.run --only batched_query

# paper traversal lanes incl. the trie_reduce kernel (BENCH_traversal.json)
bench-traversal:
	$(PY) -m benchmarks.run --only traversal

# sharded multi-device engine vs single device, P in {1, 2, 8}
# (8 simulated CPU devices; real accelerators drop the XLA_FLAGS)
bench-sharded:
	$(MULTIDEV) $(PY) -m benchmarks.run --only sharded_query

# resilient serve loop under zipfian multi-tenant load: measured +
# deterministic-gate lanes, three load levels, shard-kill fault replay
# (BENCH_serve.json)
bench-serve:
	$(PY) -m benchmarks.run --only serve_loop

# path-compressed(+quantized) layout vs plain: operational-residency
# bytes-per-edge + rule_search latency parity (BENCH_compress.json)
bench-compress:
	$(PY) -m benchmarks.run --only compress_layout

# streaming-insert delta overlay: insert throughput, frozen+delta query
# latency vs from-scratch rebuild (bit-parity asserted in-run), and the
# concurrent insert/query scheduler replay (BENCH_streaming.json)
bench-streaming:
	$(PY) -m benchmarks.run --only streaming

# observability overhead lane: the same deterministic serve replay run
# with tracing+metrics fully off vs fully on (overhead ratio + response
# parity gated), plus span-tree/exporter validity checks; --trace-out
# writes the traced replay as Perfetto JSON (open in ui.perfetto.dev)
bench-obs:
	$(PY) -m benchmarks.run --only obs_overhead \
		--trace-out BENCH_obs_trace.json

# every paper figure + kernel benches.  The sharded lane needs the
# 8-device env to produce its full P sweep, so the first pass (plain
# env, honest single-device timings for every other lane) disables its
# JSON and a second MULTIDEV pass rewrites BENCH_sharded_query.json —
# otherwise a plain host would clobber the committed P∈{1,2,8}
# trajectory with a P=1-only file.
bench:
	$(PY) -m benchmarks.run --json-out-sharded ''
	$(MULTIDEV) $(PY) -m benchmarks.run --only sharded_query
