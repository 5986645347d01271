#!/usr/bin/env bash
# CI driver — the reference's ci/build.py + runtime_functions.sh analog
# (SURVEY.md §2.7): every supported build/test variant behind one entry
# point. Usage:
#
#   ci/run.sh native        # build libmxtpu.so + run the C++ test binary
#   ci/run.sh tier1         # docs-freshness gates + serving smoke +
#                           #   chaos smoke + the tier-1 pytest
#                           #   selection (the driver's acceptance run)
#   ci/run.sh mxlint        # the AST concurrency/invariant analyzer
#                           #   (lock discipline, determinism hygiene,
#                           #   donation safety, registration
#                           #   completeness + doc freshness) — fails
#                           #   on any unwaived finding or stale
#                           #   waiver; ci/mxlint_waivers.toml
#   ci/run.sh envdoc        # thin alias: the analyzer's env-surface
#                           #   rules alone (MX-R001 + MX-R004)
#   ci/run.sh faultdoc      # thin alias: the analyzer's fault-site
#                           #   doc rule alone (MX-R003)
#   ci/run.sh serving-smoke # tools/serve_bench.py --smoke alone
#                           #   (batching wins / bounded compiles /
#                           #   shed-not-crash)
#   ci/run.sh generation-smoke # continuous-batching generation gate:
#                           #   mixed prompt-length traffic at 8
#                           #   clients, >=2x tokens/sec vs sequential
#                           #   one-shot-per-token, 0 decode compiles
#                           #   after warmup, clean shed under a
#                           #   2x-slot flood; PLUS the speculative
#                           #   leg: draft/verify >=1.3x tokens/sec
#                           #   over the plain engine in serial order,
#                           #   accepted/step >1.0, byte-identical
#                           #   streams, rollback + worker-kill legs
#   ci/run.sh resilience-smoke # serving resilience gate: seeded
#                           #   worker-kill mid-stream -> every stream
#                           #   completes token-identical to the
#                           #   fault-free run on the raw wire;
#                           #   SIGTERM under 8-client load -> clean
#                           #   drain (429 sheds, readiness 503 /
#                           #   liveness 200, exit 0)
#   ci/run.sh dist-resilience-smoke # elastic distributed training
#                           #   gate: seeded ps.server crash mid-
#                           #   training at 2 workers -> supervised
#                           #   restart + snapshot restore + exactly-
#                           #   once parity; worker kill -> auto-
#                           #   resume completes exactly; restart-
#                           #   budget exhaustion degrades (exit 70)
#   ci/run.sh chaos-smoke   # bounded fault-injection/preemption proof
#                           #   (tests/test_faults.py -k smoke)
#   ci/run.sh health-smoke  # training health guard acceptance: seeded
#                           #   NaN plan -> exactly one skip + loss
#                           #   recovery + budget; watchdog stack dump
#                           #   on an injected stall; replay identical
#   ci/run.sh dist-comm-smoke # overlapped-collectives gate: bucketed
#                           #   priority-scheduled gradient reduction
#                           #   >=1.3x steps/sec vs serialized on a
#                           #   calibrated synthetic-slow wire, loss
#                           #   bit-parity / 2bit replay determinism,
#                           #   0 compiles after warmup
#   ci/run.sh input-pipeline-smoke # async device-prefetch gate:
#                           #   synthetic slow loader + real step ->
#                           #   steps/sec ~ max(loader, step) not the
#                           #   sum, <10% stall with a hidden loader,
#                           #   majority-stall demonstrated unpiped,
#                           #   0 compiles after warmup, loss parity
#   ci/run.sh trace-smoke   # distributed-tracing gate: a traced
#                           #   generation request shows HTTP -> queue
#                           #   -> prefill -> >=1 linked iteration ->
#                           #   first-token spans under ONE trace id on
#                           #   the raw /v1/traces wire; traced train
#                           #   steps show prefetch / backward-segment
#                           #   / bucket / optimizer children and a
#                           #   ps.handle remote child across the PS
#                           #   frame; 1%-sampling steps/sec >=0.97x
#                           #   tracing-off, 0 compiles after warmup
#   ci/run.sh chaos         # full chaos suite incl. SIGKILL/SIGTERM
#                           #   subprocess resume proofs
#   ci/run.sh bulk-off      # core suite with MXNET_BULK_MAX_OPS=1
#                           #   (per-op dispatch sanitizer)
#   ci/run.sh unit          # full Python suite on the 8-dev virtual mesh
#   ci/run.sh dist          # real multi-process launcher tests
#   ci/run.sh exec-cache    # suite subset with the per-op executable
#                           #   cache FORCED on (our sanitizer analog:
#                           #   flushes out cache-vs-eager divergence)
#   ci/run.sh naive-engine  # subset under MXNET_ENGINE_TYPE=NaiveEngine
#                           #   (fully synchronous — the race-debug mode)
#   ci/run.sh dryrun        # multichip sharding dry run + entry compile
#   ci/run.sh tpu-sweep     # op sweep against the real chip
#                           #   (MXNET_TEST_CTX=tpu ctx-flip)
#   ci/run.sh tpu-core      # sweep + core-file sample on the chip
#                           #   (~510 tests, the tractable chip gate)
#   ci/run.sh tpu-unit      # the WHOLE suite with default ctx = tpu
#                           #   (test_operator_gpu.py "rerun everything
#                           #   on the accelerator" analog)
#   ci/run.sh all           # native + unit + dist + exec-cache +
#                           #   naive-engine + dryrun
set -euo pipefail
cd "$(dirname "$0")/.."

variant="${1:-all}"

run_native() {
  echo "== native: build libmxtpu.so + C++ tests"
  make -C src
  make -C src test
}

run_mxlint() {
  echo "== mxlint: AST concurrency & invariant analyzer — lock"
  echo "   discipline (blocking-under-lock, lock-order cycles),"
  echo "   determinism hygiene on seeded fault paths, donation safety,"
  echo "   registration completeness (env vars, metric families, fault"
  echo "   sites) + docs/env_vars.md freshness.  Waivers:"
  echo "   ci/mxlint_waivers.toml (unused waivers are errors)"
  # MXNET_NO_AUTO_DISTRIBUTED: the lint must never join a training
  # job's coordinator just because the env leaked into this shell
  JAX_PLATFORMS=cpu MXNET_NO_AUTO_DISTRIBUTED=1 timeout 120 \
    python -m mxnet_tpu.analysis
}

run_envdoc() {
  # thin alias kept for existing invocations — the analyzer subsumed
  # the old regen+git-diff check (MX-R004 render-compares, so a dirty
  # tree lints the same as a clean one)
  echo "== envdoc: env-var surface rules (mxlint MX-R001 + MX-R004)"
  JAX_PLATFORMS=cpu MXNET_NO_AUTO_DISTRIBUTED=1 \
    python -m mxnet_tpu.analysis --rules MX-R001,MX-R004
}

run_serving_smoke() {
  echo "== serving-smoke: dynamic batching beats batch-1, bucketed"
  echo "   compiles stay bounded, overload sheds without crashing"
  JAX_PLATFORMS=cpu python tools/serve_bench.py --smoke
}

run_generation_smoke() {
  echo "== generation-smoke: continuous batching >=2x sequential"
  echo "   one-shot-per-token, 0 decode recompiles after warmup"
  echo "   (incl. across sampled method/param changes — traced"
  echo "   operands), same-seed sampled streams identical, hot-prefix"
  echo "   TTFT p50 <=0.5x cold prefill with byte-identical streams,"
  echo "   2x-slot flood sheds cleanly (tokens/sec + TTFT reported;"
  echo "   the noisy throughput gate gets one re-measure on a miss)"
  JAX_PLATFORMS=cpu timeout 900 python tools/serve_bench.py \
    --generate --smoke
  echo "== generation-smoke (speculative): draft/verify decoding"
  echo "   >=1.3x tokens/sec over the non-speculative engine run in"
  echo "   serial order (vs. one step in flight: reported),"
  echo "   accepted-tokens/step >1.0, greedy AND sampled streams"
  echo "   byte-identical at the same seeds, truncated-draft leg"
  echo "   rejects+rolls back KV rows without changing a byte, seeded"
  echo "   worker-kill replays speculative streams token-identically,"
  echo "   0 XLA compiles after warmup"
  JAX_PLATFORMS=cpu timeout 900 python tools/serve_bench.py \
    --generate --speculative --smoke
}

run_faultdoc() {
  # thin alias kept for existing invocations — the analyzer's static
  # MX-R003 rule subsumed the old runtime known_sites() grep
  echo "== faultdoc: fault-site doc rule (mxlint MX-R003)"
  JAX_PLATFORMS=cpu MXNET_NO_AUTO_DISTRIBUTED=1 \
    python -m mxnet_tpu.analysis --rules MX-R003
}

run_resilience_smoke() {
  echo "== resilience-smoke: worker-kill mid-stream recovers token-"
  echo "   identical (exactly-once on the chunked wire); SIGTERM under"
  echo "   8-client load drains clean (429 sheds, ready 503/live 200,"
  echo "   exit 0) — lock-order sanitizer armed (MXNET_SANITIZE=locks)"
  JAX_PLATFORMS=cpu MXNET_SANITIZE=locks timeout 600 \
    python tools/resilience_smoke.py
}

run_dist_resilience_smoke() {
  echo "== dist-resilience-smoke: seeded PS crash -> supervised restart"
  echo "   + snapshot restore + exactly-once parity; worker kill ->"
  echo "   auto-resume exact; budget exhaustion degrades explicitly"
  JAX_PLATFORMS=cpu timeout 600 python tools/dist_resilience_smoke.py
}

run_chaos_smoke() {
  echo "== chaos-smoke: bounded (~60s) fault-injection / preemption /"
  echo "   checkpoint-fallback / kvstore-timeout proof — lock-order"
  echo "   sanitizer armed (MXNET_SANITIZE=locks)"
  JAX_PLATFORMS=cpu MXNET_SANITIZE=locks timeout 300 \
    python -m pytest tests/test_faults.py \
    -k smoke -q -p no:cacheprovider
}

run_bulk_off() {
  echo "== bulk-off: core suite with bulking DISABLED (per-op dispatch)"
  echo "   — flushes out bulked-vs-eager divergence, the bulking analog"
  echo "   of the exec-cache sanitizer"
  MXNET_BULK_MAX_OPS=1 python -m pytest -q \
    tests/test_bulk.py tests/test_autograd.py tests/test_ndarray.py \
    tests/test_gluon.py tests/test_numpy.py tests/test_rnn.py
}

run_health_smoke() {
  echo "== health-smoke: NaN sentry skip + loss recovery + budget,"
  echo "   hang-watchdog stack dump, deterministic replay"
  JAX_PLATFORMS=cpu timeout 300 python tools/health_smoke.py
}

run_input_pipeline_smoke() {
  echo "== input-pipeline-smoke: prefetched steps/sec ~ max(loader,"
  echo "   step) not their sum, stall <10% with a hidden loader vs"
  echo "   majority-stall unpiped, 0 compiles after warmup, loss parity"
  JAX_PLATFORMS=cpu timeout 300 python tools/input_smoke.py
}

run_dist_comm_smoke() {
  echo "== dist-comm-smoke: bucketed+overlapped gradient reduction"
  echo "   >=1.3x steps/sec vs the serialized push-all/pull-all path"
  echo "   on a calibrated synthetic-slow wire, losses bit-identical"
  echo "   (lossless ctypes) / replay-identical (2bit), 0 compiles"
  echo "   after warmup; PLUS the backward-overlap leg: per-layer"
  echo "   segmentation + grad-ready streaming >=1.5x serialized AND"
  echo "   strictly faster than optimizer-only overlap, bit-identical"
  echo "   losses, 0 steady-state compiles incl. a warm restart via"
  echo "   jax's persistent compilation cache"
  # 900s: the backward-overlap + warm-restart legs roughly tripled
  # the smoke's work (~4min on the reference rig; 2x slow-host margin)
  JAX_PLATFORMS=cpu timeout 900 python tools/dist_comm_smoke.py
}

run_trace_smoke() {
  echo "== trace-smoke: end-to-end distributed tracing — one trace id"
  echo "   spans HTTP front end -> batcher queue -> engine prefill ->"
  echo "   linked iterations -> token stream on the raw /v1/traces"
  echo "   wire; train steps carry prefetch/backward-segment/bucket/"
  echo "   optimizer children + a ps.handle remote child via the PS"
  echo "   frame traceparent; 1%-sampled steps/sec >=0.97x tracing-off"
  echo "   with 0 compiles after warmup"
  JAX_PLATFORMS=cpu timeout 600 python tools/trace_smoke.py
}

run_chaos() {
  echo "== chaos: the full fault-tolerance suite, including the"
  echo "   SIGKILL/SIGTERM subprocess resume proofs"
  JAX_PLATFORMS=cpu python -m pytest tests/test_faults.py -q \
    -p no:cacheprovider
}

run_tier1() {
  echo "== tier1: mxlint (concurrency/invariant analyzer, subsumes the"
  echo "   old envdoc+faultdoc gates) + serving smoke + generation"
  echo "   smoke + resilience smoke + dist-resilience smoke + chaos"
  echo "   smoke + health smoke + input-pipeline smoke + dist-comm"
  echo "   smoke + trace smoke + the tier-1 pytest selection"
  run_mxlint
  run_serving_smoke
  run_generation_smoke
  run_resilience_smoke
  run_dist_resilience_smoke
  run_chaos_smoke
  run_health_smoke
  run_input_pipeline_smoke
  run_dist_comm_smoke
  run_trace_smoke
  JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider
}

run_unit() {
  echo "== unit: full Python suite (virtual CPU mesh)"
  python -m pytest tests/ -q --ignore=tests/test_distributed.py
}

run_dist() {
  echo "== dist: real multi-process launcher tests"
  python -m pytest tests/test_distributed.py -q
}

run_exec_cache() {
  echo "== exec-cache: core suite with the executable cache forced on"
  MXNET_IMPERATIVE_EXEC_CACHE=1 python -m pytest -q \
    tests/test_imperative_cache.py tests/test_autograd.py \
    tests/test_ndarray.py tests/test_gluon.py tests/test_numpy.py \
    tests/test_rnn.py tests/test_sparse.py
}

run_naive_engine() {
  echo "== naive-engine: synchronous dispatch mode"
  MXNET_ENGINE_TYPE=NaiveEngine python -m pytest -q \
    tests/test_autograd.py tests/test_ndarray.py tests/test_gluon.py
}

run_dryrun() {
  echo "== dryrun: multichip sharding + entry compile check"
  python __graft_entry__.py
}

run_tpu_sweep() {
  echo "== tpu-sweep: op sweep with default ctx = tpu"
  MXNET_TEST_CTX=tpu python -m pytest tests/test_op_sweep.py -q
}

run_tpu_core() {
  echo "== tpu-core: op sweep + core file sample with default ctx = tpu"
  echo "   (the tractable on-chip gate; tpu-unit is the exhaustive one)"
  MXNET_TEST_CTX=tpu python -m pytest -q tests/test_op_sweep.py \
    tests/test_autograd.py tests/test_gluon.py tests/test_optimizer.py \
    tests/test_ndarray.py tests/test_numpy.py tests/test_rnn.py \
    tests/test_misc.py tests/test_sparse.py tests/test_image.py \
    tests/test_amp.py
}

run_tpu_unit() {
  echo "== tpu-unit: the WHOLE suite with default ctx = tpu (the"
  echo "   reference's test_operator_gpu.py ctx-flip; host-only"
  echo "   multi-device tests auto-skip via tests/conftest.py)"
  MXNET_TEST_CTX=tpu python -m pytest tests/ -q
}

case "$variant" in
  native)       run_native ;;
  tier1)        run_tier1 ;;
  mxlint)       run_mxlint ;;
  envdoc)       run_envdoc ;;
  faultdoc)     run_faultdoc ;;
  serving-smoke) run_serving_smoke ;;
  generation-smoke) run_generation_smoke ;;
  resilience-smoke) run_resilience_smoke ;;
  dist-resilience-smoke) run_dist_resilience_smoke ;;
  chaos-smoke)  run_chaos_smoke ;;
  health-smoke) run_health_smoke ;;
  input-pipeline-smoke) run_input_pipeline_smoke ;;
  dist-comm-smoke) run_dist_comm_smoke ;;
  trace-smoke)  run_trace_smoke ;;
  chaos)        run_chaos ;;
  bulk-off)     run_bulk_off ;;
  unit)         run_unit ;;
  dist)         run_dist ;;
  exec-cache)   run_exec_cache ;;
  naive-engine) run_naive_engine ;;
  dryrun)       run_dryrun ;;
  tpu-sweep)    run_tpu_sweep ;;
  tpu-core)     run_tpu_core ;;
  tpu-unit)     run_tpu_unit ;;
  all)
    run_native
    run_envdoc
    run_unit
    run_dist
    run_exec_cache
    run_naive_engine
    run_dryrun
    ;;
  *)
    echo "unknown variant: $variant" >&2
    exit 2
    ;;
esac
echo "CI variant '$variant' PASSED"
