# Build/CI entry points (SURVEY.md §2 L9: the reference ships CMake +
# Travis; this is the TPU build's single-command analog).
#
#   make test     - full suite on the 8-virtual-CPU-device mesh
#   make dryrun   - multi-chip sharding compile/execute check (8 devices)
#   make chip-smoke - python chip_smoke.py: every main path once on the
#                   TPU (trainers, table kernels, wire server; with >= 4
#                   chips also dp x mp meshes and a 4-member fleet).
#                   Needs a chip — run it through the chip tool; with
#                   no accelerator it exits non-zero and prints no
#                   result. One process per chip: run nothing else that
#                   touches jax on the host meanwhile.
#   make bench    - driver benchmark on the TPU (one metric JSON line
#                   carrying both metrics; no TPU = non-zero exit)
#   make bench-dryrun - INTEGRATED bench pipeline at toy sizes, pinned
#                   to the CPU so it never takes a chip (~16s;
#                   integration seams real, numbers meaningless, no
#                   roofline block)
#   make fuzz     - extended differential fuzz (~10-40 min; not in ci)
#   make lint     - stdlib linter (tools/lint.py: syntax + unused
#                   imports; neither ruff nor pyflakes is vendored in
#                   this image) over the package, tests, and bench
#   make bench-diff - compare two bench artifacts (OLD=... NEW=...);
#                   nonzero exit when a watched metric regresses
#   make client-bench - worker-side client pipeline micro-bench
#                   (coalescing / cache / staging) at tiny sizes on CPU;
#                   drop MVTPU_CLIENT_BENCH_TINY for real sizes
#   make ckpt-bench - run-level checkpoint store/restore micro-bench
#                   (tiny sizes on CPU; drop MVTPU_CKPT_BENCH_TINY for
#                   real sizes; emits checkpoint_bench.json)
#   make kernel-bench - server-side table-kernel micro-bench, XLA vs
#                   Pallas engines with a cross-engine parity guard,
#                   plus the sharded lane (model=2 shard_map engines;
#                   TINY forces 2 virtual CPU devices so it always
#                   runs; drop MVTPU_KERNEL_BENCH_TINY for real sizes
#                   on TPU; emits table_kernels_bench.json)
#   make tier-bench - tiered KV storage micro-bench: trains a
#                   TieredKVTable with the device budget a fraction of
#                   the table, asserts zero overflow raises + non-zero
#                   demotions/disk fills + a bit-identical tiered
#                   checkpoint resume (tiny sizes on CPU; drop
#                   MVTPU_TIER_BENCH_TINY for real sizes; emits
#                   tiered_kv_bench.json)
#   make health-smoke - training-health smoke: tiny sparse-logreg run
#                   with a chaos-injected NaN, asserting the fused
#                   stats audit catches it, /healthz flips 503, and
#                   MVTPU_HEALTH_ACTION=rollback restores the last
#                   pre-violation checkpoint generation
#   make serve-smoke - serving/observability smoke: tiny serving bench
#                   (8 client threads, one dispatcher) in-process with
#                   an ephemeral statusz server + SLO rule armed, then
#                   scrape /metrics /healthz /statusz /trace over HTTP
#                   and assert non-null serving p50/p99/p999
#   make mp-smoke - multi-process wire smoke: TableServer processes +
#                   jax-free worker processes; dense-fp32, 1bit-quant
#                   and shm:// ring train lanes, a fusion-on-vs-off
#                   cross-client ops comparison (fused adds must be
#                   bit-identical to unfused AND faster), and a paired
#                   staleness-read RTT probe (shm ring vs tcp loopback;
#                   asserts the quant lane ships >= 4x fewer bytes at
#                   matched loss; emits serving_mp_bench.json)
#   make flood-smoke - overload/admission smoke: a deliberate flooder
#                   client vs protected workers through one admission-
#                   controlled server (QoS classes + token bucket +
#                   bounded queue); asserts the flooder is shed with
#                   retry-after, the protected p999 holds the armed
#                   MVTPU_SLO rule (slo_violations == 0), and both
#                   final tables stay bit-exact (no shed-resent add
#                   double-applies); emits serving_mp_flood.json —
#                   a partial line on every give-up path
#   make fleet-smoke - sharded-fleet smoke: 2 partitioned server
#                   processes behind the scatter-gather router vs one
#                   server, jax-free workers on the range-read serving
#                   lane; asserts fleet >= 1.5x single aggregate ops/s,
#                   both finals bit-exact, /statusz?fleet=1 aggregates
#                   both partitions, and SIGKILLing one member leaves
#                   the surviving shard serving; emits
#                   serving_mp_fleet.json — a partial line on every
#                   give-up path
#   make replica-smoke - replicated-shard smoke: one rank with a
#                   delta-streamed follower (--replicas 2); asserts
#                   1-bit adds replicate at quantized cost (bytes
#                   ratio >= 2x vs full-precision sync), follower-
#                   routed staleness reads >= 1.5x the primary-pinned
#                   baseline under the same write storm with both
#                   finals bit-exact, and a SIGKILLed primary fails
#                   over (map v2, window replayed exactly once, every
#                   range serving, final bit-exact); emits
#                   serving_mp_replica.json — a partial line on every
#                   give-up path
#   make reshard-smoke - elastic-fleet smoke: a 2-member fleet grows
#                   to 3 under a parent-process write storm (--grow
#                   admin wave: stream, forward, commit donors-first),
#                   then shrinks back quiet; asserts the final tables
#                   are BIT-EXACT against the counted acked adds
#                   (integer-grid deltas — no write lost or doubled
#                   across either flip), moved bytes match the
#                   MapDiff closed form (migration cost ~ moved
#                   ranges, never table size), and post-flip p99
#                   recovers to <= 8x the quiet baseline; emits
#                   serving_mp_reshard.json — a partial line on every
#                   give-up path
#   make trace-smoke - distributed-tracing smoke: a real 2-member
#                   fleet + a traced client fleet get, then a
#                   telemetry.report --fleet scrape-merge; asserts one
#                   request id reconstructs as ONE parent-linked tree
#                   across all 3 processes (client root, rparent-
#                   stitched server spans, chrome flow arrows),
#                   non-null clock offsets against both members, and a
#                   merged mvtpu.metrics.v1 fleet snapshot
#   make autotune-smoke - closed-loop autotuning smoke: a wire server
#                   starts MIStuned (fuse=1, protected QoS class
#                   starved at 2 ops/s) under a bulk flood; the
#                   control.Controller must converge protected
#                   throughput within 10% of a hand-tuned reference,
#                   with every knob move audited in the decision ring;
#                   a second phase re-mistunes with the objective in
#                   the windowed form (p99@1s) and must converge
#                   spending no more latency-clause decisions than a
#                   non-actuating cumulative shadow of the same rule
#                   (emits autotune_bench.json)
#   make chaos    - the chaos lane: fault-injection test subset
#                   (ft subsystem + overwrite crash-window fuzz) plus a
#                   CLI checkpoint/resume smoke under an active
#                   MVTPU_CHAOS spec
#   make native   - C++ data loader + baseline binaries
#   make ci       - everything CI runs, in order

PY ?= python
# bench-diff operands: two bench artifacts, e.g. make bench-diff OLD=a.json NEW=b.json
OLD ?=
NEW ?=

.PHONY: test dryrun bench bench-dryrun chip-smoke bench-diff \
	bench-diff-selftest \
	client-bench ckpt-bench kernel-bench tier-bench serve-smoke \
	mp-smoke flood-smoke fleet-smoke replica-smoke reshard-smoke \
	trace-smoke health-smoke autotune-smoke chaos fuzz lint native ci

fuzz:
	$(PY) tests/deep_fuzz.py

lint:
	$(PY) tools/lint.py multiverso_tpu tests bench.py chip_smoke.py tools

bench-diff:
	$(PY) tools/bench_diff.py $(OLD) $(NEW)

bench-diff-selftest:
	$(PY) tools/bench_diff.py --selftest

test:
	$(PY) -m pytest tests/ -q

bench-dryrun:
	MVTPU_BENCH_TINY=1 $(PY) bench.py

client-bench:
	MVTPU_CLIENT_BENCH_TINY=1 $(PY) benchmarks/client_pipeline.py

ckpt-bench:
	MVTPU_CKPT_BENCH_TINY=1 $(PY) benchmarks/checkpoint_bench.py

kernel-bench:
	MVTPU_KERNEL_BENCH_TINY=1 $(PY) benchmarks/table_kernels.py

tier-bench:
	MVTPU_TIER_BENCH_TINY=1 $(PY) benchmarks/tiered_kv.py

serve-smoke:
	$(PY) tools/serve_smoke.py

mp-smoke:
	MVTPU_SERVING_MP_TINY=1 $(PY) benchmarks/serving_mp.py

flood-smoke:
	MVTPU_SERVING_MP_TINY=1 $(PY) benchmarks/serving_mp.py --flood

fleet-smoke:
	MVTPU_SERVING_MP_TINY=1 $(PY) benchmarks/serving_mp.py --servers 2

replica-smoke:
	MVTPU_SERVING_MP_TINY=1 $(PY) benchmarks/serving_mp.py --replicas

reshard-smoke:
	MVTPU_SERVING_MP_TINY=1 $(PY) benchmarks/serving_mp.py --reshard

trace-smoke:
	$(PY) tools/trace_smoke.py

autotune-smoke:
	MVTPU_SERVING_TINY=1 $(PY) benchmarks/serving.py --autotune

health-smoke:
	$(PY) tools/health_smoke.py

# the chaos lane: recovery paths exercised under injected faults —
# the ft test subset, the overwrite crash-window fuzz, and an app CLI
# checkpoint + resume smoke with chaos-injected IO errors retried live
chaos:
	$(PY) -m pytest tests/test_ft.py \
	  "tests/test_io.py::TestOverwriteCrashWindow" -q \
	  -p no:cacheprovider
	rm -rf /tmp/mvtpu_chaos_smoke
	MVTPU_CHAOS="seed=1;io.write:error:times=2;io.write:latency:ms=1" \
	  $(PY) -c "import jax; jax.config.update('jax_platforms', 'cpu'); \
	  from multiverso_tpu.apps.logreg import main; \
	  main(['-input_dimension=12', '-output_dimension=3', \
	        '-minibatch_size=128', '-train_epoch=2', \
	        '-run_dir=/tmp/mvtpu_chaos_smoke', '-ckpt_every=1'])"
	MVTPU_CHAOS="seed=2;io.read:latency:ms=1" \
	  $(PY) -c "import jax; jax.config.update('jax_platforms', 'cpu'); \
	  from multiverso_tpu.apps.logreg import main; \
	  main(['-input_dimension=12', '-output_dimension=3', \
	        '-minibatch_size=128', '-train_epoch=2', \
	        '-run_dir=/tmp/mvtpu_chaos_smoke', '-ckpt_every=1', \
	        '-resume=true'])"
	rm -rf /tmp/mvtpu_chaos_smoke

dryrun:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	  $(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

bench:
	$(PY) bench.py

chip-smoke:
	$(PY) chip_smoke.py

native:
	$(MAKE) -C native

ci: lint bench-diff-selftest native test dryrun bench-dryrun \
	client-bench ckpt-bench kernel-bench tier-bench serve-smoke \
	mp-smoke flood-smoke fleet-smoke replica-smoke reshard-smoke \
	trace-smoke health-smoke autotune-smoke chaos
