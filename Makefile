PY := PYTHONPATH=src python

.PHONY: test test-fast test-attention test-kernels test-shard test-serve \
	test-faults test-cp test-hybrid dryrun-gate bench bench-json \
	bench-tpu ci-fast autotune autotune-check

# full tier-1 suite (everything, incl. multi-minute subprocess compiles)
test:
	$(PY) -m pytest -x -q

# fast verify loop: excludes everything marked `slow` (the ~8-minute
# sharding/dryrun subprocess compiles, e2e driver runs, per-arch
# integration sweeps). ~2 min on a 1-CPU container, dominated by the f64
# operator-equivalence sweeps; the excluded tests still run under `test`.
# Includes the `kernels` marker subset (see test-kernels for just those).
test-fast:
	$(PY) -m pytest -q -m "tier1 and not slow"

# just the attention-operator API (spec/registry/dispatch/decode protocol)
test-attention:
	$(PY) -m pytest -q tests/test_attention_api.py

# just the Pallas kernel validation (fwd/bwd/decode interpret equivalence)
test-kernels:
	$(PY) -m pytest -q -m "kernels and not slow"

# continuous-batching engine tier: slot pool, scheduler, prefix cache, and
# engine-vs-generate() token parity for every decode-capable backend (the
# slow-marked SSM-arch parity sweeps still run under `test`)
test-serve:
	$(PY) -m pytest -q -m "serve and not slow"

# serving chaos tier: deterministic fault injection (NaN-into-slot,
# raising callbacks, burst overload, deadlines, mid-stream cancel, wedged
# ticks) — the engine must fail only the targeted request with the right
# status while unaffected requests stay byte-identical to an undisturbed
# run, and stalls surface as EngineStalled, never silent spins
test-faults:
	$(PY) -m pytest -q -m "faults and not slow"

# multi-device tier: shard_map kernel parity + feature-TP scan grads on 8
# forced host CPU devices (no TPU required; conftest injects XLA_FLAGS)
test-shard:
	REPRO_TEST_DEVICES=8 $(PY) -m pytest -q -m shard tests/test_shard_map.py

# context-parallel tier: seq-mode shard_map training parity (CP=2/4 grads
# vs the single-device kernel, ring vs allgather carry exchange, plan
# selection) on 8 forced host CPU devices
test-cp:
	REPRO_TEST_DEVICES=8 $(PY) -m pytest -q -m cp \
		tests/test_context_parallel.py

# hybrid near/far-field tier: banded-softmax+moments vs the composed
# dense oracle (fwd + grads), window edge cases (w=0 bitwise fastmax,
# w>=N exact softmax), chunked-prefill/decode lockstep, serve parity
test-hybrid:
	$(PY) -m pytest -q -m "hybrid and not slow"

# sharding-health gate: the cells the shard-native work must keep clean —
# 0 involuntary remats on train_4k (feature-TP scan AND the feature-TP
# kernel training path) and decode_32k, decode routed to the shard_map
# Pallas kernels (no jnp fallback), TP=16 training routed to the
# shard_map[feature] Dv-blocked kernels (no chunked-scan fallback), and
# 1M-token context-parallel training (--cp 16) routed shard_map[seq]
# with 0 remats — its cell JSON records the modeled constant-size
# carry-exchange bytes next to the ring-attention O(N*D) alternative;
# hybrid2-kernel training routed shard_map[feature] with 0 remats; and
# whisper-small (12 heads, indivisible by TP=16) proving noncausal
# encoder attention routes the feature-mode kernel wrap, not the
# chunked-scan fallback
dryrun-gate:
	$(PY) -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k \
		--assert-no-remat --out results/dryrun-gate
	$(PY) -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k \
		--attn fastmax2-kernel --assert-no-remat --assert-kernel-route \
		--out results/dryrun-gate
	$(PY) -m repro.launch.dryrun --arch qwen2.5-32b --shape decode_32k \
		--attn fastmax2-kernel --assert-no-remat --assert-kernel-route \
		--out results/dryrun-gate
	$(PY) -m repro.launch.dryrun --arch llama3-405b --shape decode_32k \
		--attn softmax --assert-no-remat --out results/dryrun-gate
	$(PY) -m repro.launch.dryrun --arch qwen3-1.7b --shape train_1M \
		--cp 16 --attn fastmax2-kernel --assert-no-remat \
		--assert-kernel-route --out results/dryrun-gate
	$(PY) -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k \
		--attn hybrid2-kernel --assert-no-remat --assert-kernel-route \
		--out results/dryrun-gate
	$(PY) -m repro.launch.dryrun --arch whisper-small --shape train_4k \
		--attn fastmax2-kernel --assert-kernel-route \
		--out results/dryrun-gate

# mirror the CI PR job locally (`.github/workflows/ci.yml` fast tier):
# the seven suites a PR must keep green, in the same order
ci-fast: test-fast test-kernels test-shard test-cp test-serve test-faults \
	test-hybrid

bench:
	$(PY) -m benchmarks.run --quick

# per-phase attention timings -> BENCH_attention.json (the committed perf
# baseline); prints a fail-soft warning when >20% slower than the baseline
bench-json:
	$(PY) -m benchmarks.run --only attn_phases --json BENCH_attention.json

# real-hardware bench lane: same suite as bench-json but refuses to run
# off-TPU, tunes on silicon (REPRO_AUTOTUNE=1 measures on cache miss), and
# every kernel cell lands in BENCH_attention.json with hardware="tpu" +
# its measured schedule — never compared against interpret cells
bench-tpu:
	REPRO_AUTOTUNE=1 $(PY) -m benchmarks.run --only attn_phases \
		--json BENCH_attention.json --require-tpu

# regenerate the committed autotune cache (deterministic cost-model
# winners over the dryrun-gate + bench shapes) / check it is not stale —
# the CI autotune job runs the check on every PR
autotune:
	$(PY) -m repro.kernels.autotune --write

autotune-check:
	$(PY) -m repro.kernels.autotune --check
