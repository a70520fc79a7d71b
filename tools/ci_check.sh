#!/usr/bin/env bash
# One-invocation CI entrypoint: tier-1 core lane + the perf-regression
# guards (compile-count bound for the continuous-batching scheduler).
#
#   tools/ci_check.sh            # tier-1 + guards + offload lane + gateway smoke + observability lane + rlhf lane + sharded lane + hierkv lane + multilora lane + disagg lane + moe lane + capacity lane + fusedblock lane + longctx lane + autoscale lane + multihost lane
#   tools/ci_check.sh --guards   # guards only (fast pre-push check)
#   tools/ci_check.sh --gateway  # gateway smoke only
#   tools/ci_check.sh --offload  # offload-streaming lane only
#   tools/ci_check.sh --observability  # tracing/SLO/flight-recorder lane only
#   tools/ci_check.sh --rlhf     # RLHF hybrid-engine lane only
#   tools/ci_check.sh --sharded  # tensor-sharded decode + replica-set lane only
#   tools/ci_check.sh --hierkv   # hierarchical-KV tier lane only
#   tools/ci_check.sh --multilora # multi-LoRA adapter-serving lane only
#   tools/ci_check.sh --disagg   # disaggregated prefill/decode lane only
#   tools/ci_check.sh --moe      # MoE serving (expert-parallel decode) lane only
#   tools/ci_check.sh --capacity # serving capacity/roofline + profiling lane only
#   tools/ci_check.sh --fusedblock # fused llama-family decode-block lane only
#   tools/ci_check.sh --longctx  # long-context serving (multi-extent KV + seq-parallel prefill) lane only
#   tools/ci_check.sh --autoscale # elastic fleet control plane (autoscaler/brownout/elastic resize) lane only
#   tools/ci_check.sh --multihost # multi-host router/worker-fleet + networked store lane only
#   tools/ci_check.sh --bench-diff [NEW.json]  # advisory bench-round diff only
#
# Exit code is nonzero if any lane fails. DOTS_PASSED echoes the tier-1
# pass count the growth driver tracks (ROADMAP.md "Tier-1 verify").
set -u -o pipefail
cd "$(dirname "$0")/.."

guards() {
  echo "== perf-regression guards =="
  # test_scheduler.py carries BOTH compile-count guards: the legacy bucketed
  # bound (test_compile_count_bounded_on_mixed_stream) and the fused
  # chunked-prefill O(1)-in-length-mix bound
  # (test_fused_compile_count_o1_in_length_mix), plus the prefix-cache
  # hit-vs-cold bit-identity check; test_kv_cache.py guards the slot/radix
  # accounting invariants under eviction storms; test_gateway.py guards the
  # serving gateway's admission/fairness/lifecycle contracts
  timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
    tests/unit/inference/test_scheduler.py \
    tests/unit/inference/test_kv_cache.py \
    tests/unit/inference/test_speculative.py \
    tests/unit/serving/test_gateway.py \
    "tests/unit/inference/test_inference.py::test_paged_decode_kernel_vs_reference" \
    "tests/unit/inference/test_inference.py::test_decode_kernel_vs_reference" \
    "tests/unit/inference/test_inference.py::test_fused_decode_block_matches_unfused" \
    -q -p no:cacheprovider
}

offload_lane() {
  echo "== offload streaming lane =="
  # ZeRO-Infinity streaming-pipeline guards: depth/window parity must stay
  # BIT-identical (host + NVMe tiers, gas>1 buffered path) and the
  # LayerStreamExecutor must add zero new XLA programs (jax.monitoring
  # compile-count). The matching perf leg is `python bench.py offload_stream`
  # (BENCH_OFFLOAD_STREAM JSON: depth 0 vs 2 step time + overlap_efficiency).
  timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
    tests/unit/test_offload_stream.py -q -p no:cacheprovider
}

rlhf_lane() {
  echo "== rlhf hybrid-engine lane =="
  # weight-publication guards: generate-after-publish bit-identical to a
  # fresh engine on the same params (greedy + sampled, radix/spec on/off),
  # no KV/prefix reuse across a weights version (structural version tags),
  # in-memory publish writes no checkpoint files, and the publish cycle
  # adds ZERO new XLA programs after warmup
  # (test_publish_cycle_compile_count_zero_after_warmup). The matching
  # perf leg is `python bench.py rlhf` (BENCH_RLHF JSON: publish vs
  # checkpoint round-trip + scheduler rollout tok/s).
  timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
    tests/unit/rlhf tests/unit/test_hybrid_engine.py -q -p no:cacheprovider
}

sharded_lane() {
  echo "== sharded serving lane =="
  # pod-scale serving guards under the forced multi-CPU-device backend:
  # tp=2 scheduler decode (greedy/sampled/radix/spec/int8-KV, XLA + Pallas
  # paths) must match tp=1 BIT-FOR-BIT (the bitwise all-gather layout), the
  # int8 fused-qkv tp gating must fall back loudly, and the replica set
  # must dispatch (least-loaded + prefix-sticky + drain/health) while
  # adding ZERO XLA programs per replica (jax.monitoring guard). The
  # matching perf leg is `python bench.py serving` ("replicas" entry).
  timeout -k 10 600 env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" python -m pytest \
    tests/unit/inference/test_sharded_decode.py \
    tests/unit/serving/test_replica.py -q -p no:cacheprovider
}

observability_lane() {
  echo "== observability lane =="
  # request tracing / SLO burn-rate / flight recorder / Prometheus
  # exposition guards, plus the telemetry-overhead contract: the
  # default-off sink stays zero-allocation on the hot path and enabled
  # per-token tracing overhead stays bounded on the CPU decode smoke
  # (test_tracing_overhead_bounded in test_observability.py)
  timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
    tests/unit/test_telemetry.py \
    tests/unit/test_observability.py -q -p no:cacheprovider
}

hierkv_lane() {
  echo "== hierarchical-KV tier lane =="
  # hierarchical KV guards: restored-prefix decode BIT-identical to a
  # device-resident hit and to cold prefill (greedy+sampled x bf16/int8 KV
  # x 1/2 replicas, cross-replica restore asserted), demote->restore->decode
  # adds ZERO XLA programs after warmup (jax.monitoring), swap_weights drops
  # the host tier (stale host KV is a structural error), NVMe spill
  # round-trips bytes exactly, and the tiered eviction storm holds the
  # one-tier-per-key invariant after every operation. The matching perf leg
  # is `python bench.py serving` ("hier_kv" entry: LRU-thrashing revisit
  # stream, device-only vs host tier).
  timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
    tests/unit/memory \
    tests/unit/inference/test_kv_cache.py -q -p no:cacheprovider
}

multilora_lane() {
  echo "== multi-LoRA adapter-serving lane =="
  # paged-adapter serving guards: every row of a heterogeneous-adapter batch
  # BIT-identical to that adapter's solo run (greedy+sampled x bf16/int8 KV
  # x tp1/tp2 x 1/2 replicas), base rows bit-identical to the pre-adapter
  # programs, cross-adapter KV/prefix reuse structurally impossible (per-
  # adapter trie roots + namespaced host-store keys, adapter-axis eviction
  # storm in test_kv_cache.py), hot load/evict churn exact, and the
  # jax.monitoring compile guard: a fresh adapter-count/mix/eviction stream
  # adds ZERO XLA programs after the rank bucket warms. Runs UNFILTERED (the
  # bit-identity matrix nodeids are in slow_tests.txt to keep tier-1 in
  # budget). The matching perf leg is `python bench.py serving`
  # ("multi_lora" entry: paged vs merged-weight swap rotation).
  timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
    tests/unit/adapters \
    tests/unit/inference/test_kv_cache.py -q -p no:cacheprovider
}

disagg_lane() {
  echo "== disaggregated prefill/decode lane =="
  # phase-role migration guards, run UNFILTERED (the bit-identity matrix
  # nodeids live in slow_tests.txt to keep tier-1 in budget): migrated
  # decode BIT-identical to single-replica (tokens AND logits, greedy +
  # sampled x bf16/int8 KV x radix hit/cold x with/without adapter),
  # mid-migration cancel frees both ends' slots + the parked store entry,
  # sick-decode failover re-places the handoff, zero-role fleet identical
  # to the plain replica path, and the jax.monitoring compile guard: a
  # warm role/length/sampling/migration mix adds ZERO XLA programs. The
  # matching perf leg is `python bench.py serving` ("disagg" entry: ITL
  # p95 flat while offered prefill load doubles vs the mixed fleet).
  timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
    tests/unit/serving/test_disagg.py -q -p no:cacheprovider
}

moe_lane() {
  echo "== MoE serving lane =="
  # expert-parallel decode guards, run UNFILTERED under the forced
  # multi-CPU-device backend (the bit-identity matrix nodeids live in
  # slow_tests.txt to keep tier-1 in budget): ep=2/ep=4/ep2xtp2 scheduler
  # decode BIT-identical to the ep=1 replicated program (greedy + sampled
  # x radix hit/cold x spec on/off x bf16/int8 KV), non-dividing expert
  # counts fall back replicated LOUDLY, cold-expert offload (all-hot AND
  # half-resident churn) bit-identical to the in-tree path with ZERO new
  # XLA programs over a fresh routing/residency mix (jax.monitoring), and
  # apply_with_cache never collects training-only intermediates. The
  # matching perf leg is `python bench.py serving` ("moe" entry: top-k
  # stream vs dense-equivalent-FLOPs + the residency sweep).
  timeout -k 10 900 env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" python -m pytest \
    tests/unit/inference/test_moe_decode.py -q -p no:cacheprovider
}

fusedblock_lane() {
  echo "== fused decode-block lane =="
  # fused llama-family decode-block guards, run UNFILTERED under the forced
  # multi-CPU-device backend (the parity-matrix and scheduler-stream nodeids
  # live in slow_tests.txt to keep tier-1 in budget): fused_paged_step ==
  # per-projection apply_with_cache across RoPE x RMSNorm x SwiGLU x GQA x
  # int8-KV x column width, greedy AND sampled scheduler streams identical
  # through the fused_block/spec_block retagged programs (radix hit/cold,
  # spec on/off), ZERO new XLA programs on a fresh request mix after warmup
  # (jax.monitoring), one concrete gate reason per excluded model condition,
  # and the capacity-meter registration of the new program kinds. The
  # matching perf leg is `python bench.py serving` ("fused_block" entry:
  # fused vs per-projection step_ms + tok/s, BENCH_SERVING_FUSED knob).
  timeout -k 10 600 env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" python -m pytest \
    tests/unit/inference/test_fused_block.py \
    "tests/unit/inference/test_inference.py::test_fused_decode_block_matches_unfused" \
    -q -p no:cacheprovider
}

longctx_lane() {
  echo "== long-context serving lane =="
  # multi-extent paged KV + seq-parallel prefill guards, run UNFILTERED
  # under the forced multi-CPU-device backend (every nodeid lives in
  # slow_tests.txt to keep tier-1 in budget): a chained request BIT-
  # identical (tokens AND logits, greedy + sampled) to the single-slot
  # path, seq-parallel chunked prefill identical to single-shard, mid-
  # decode extent demote -> detect-miss-and-restore bit-identity, the
  # lossy sliding-window mode gated off by default and asserted NON-
  # identical when on, a fresh chained/unchained length mix compiling
  # ZERO new XLA programs (jax.monitoring), spannable-capacity 400s at
  # submit AND at the gateway, and the paging/extent telemetry. The
  # matching perf leg is `python bench.py serving` ("long_context" entry:
  # TTFT/ITL vs context over tiny extents, BENCH_SERVING_LONGCTX knob).
  timeout -k 10 900 env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" python -m pytest \
    tests/unit/inference/test_long_context.py -q -p no:cacheprovider
}

capacity_lane() {
  echo "== serving capacity/roofline lane =="
  # serving goodput & capacity observability guards (telemetry/capacity.py
  # + telemetry/profiler.py): sampled fenced roofline timing adds ZERO XLA
  # programs over a fresh length/spec/adapter mix (jax.monitoring) and
  # bounded decode overhead, host-gap buckets sum exactly to the measured
  # gap, analytic FLOPs cross-check against jit(...).lower().cost_analysis(),
  # the on-demand profile endpoint writes a loadable trace and 409s on
  # overlap. test_profiling.py rides along: the training-side flops
  # profiler + report-boundary capture share this surface (its slow nodeid
  # lives in slow_tests.txt to keep tier-1 in budget). The matching perf
  # leg is `python bench.py serving` ("capacity" entry: instrumented-vs-off
  # tok/s ratio + live MFU/goodput, BENCH_SERVING_CAPACITY sample knob).
  timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
    tests/unit/serving/test_capacity.py \
    tests/unit/test_profiling.py -q -p no:cacheprovider
}

autoscale_lane() {
  echo "== elastic fleet (autoscale) lane =="
  # elastic fleet control-plane guards, run UNFILTERED (the lifecycle
  # bit-identity nodeids live in slow_tests.txt to keep tier-1 in budget):
  # the FleetController decision ladder against scripted signal traces
  # (multi-window burn, host-gap veto, cooldowns, goodput-priced brownout
  # escalation/de-escalation, rebalance skew), mid-stream add_replica
  # BIT-identical with ZERO new XLA programs (jax.monitoring), the full
  # grow -> park -> two-phase shrink -> role-flip cycle bit-identical to a
  # never-resized run, fair-queue tier eviction, the gateway brownout
  # door (503 + Retry-After below the bar) and /v1/autoscaler admin
  # surface, plus the training-side ElasticityManager resize-plan/restore
  # validation. The matching perf leg is `python bench.py serving`
  # ("autoscale" entry: ramp/spike/decay controller on-vs-off,
  # BENCH_SERVING_AUTOSCALE knob).
  timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest \
    tests/unit/serving/test_controller.py \
    "tests/unit/test_sidecars.py::test_elastic_manager_plan_tiling" \
    "tests/unit/test_sidecars.py::test_elastic_manager_restore_noop_and_resize" \
    "tests/unit/test_sidecars.py::test_elastic_manager_restore_rejects_drifted_config" \
    -q -p no:cacheprovider
}

multihost_lane() {
  echo "== multi-host serving lane =="
  # router tier + cross-process worker fleet + networked prefix/handoff
  # store guards, run UNFILTERED (the spawned-subprocess nodeids live in
  # slow_tests.txt to keep tier-1 in budget): a 2-process fleet behind the
  # router BIT-identical (tokens AND logits, greedy + sampled x radix
  # hit/cold, unary + SSE) to the 1-process gateway, zero XLA programs per
  # worker beyond the solo set, cross-host prefix restore bitwise equal to
  # local with net_store counters moving, prefill->decode handoff across
  # PROCESSES stitched into one client stream, SIGKILL mid-decode shedding
  # (honest truncation + survivor keeps serving + sick marking), handoff
  # lease expiry reclaiming orphaned entries, directory version/coverage
  # semantics, capacity_math fleet merging (no draining double-count), and
  # the per-worker labeled Prometheus families under the 256-label cap.
  timeout -k 10 900 env JAX_PLATFORMS=cpu python -m pytest \
    tests/unit/serving/test_multihost.py -q -p no:cacheprovider
}

bench_diff() {
  echo "== bench diff (advisory) =="
  # diff the given fresh bench JSON against the highest-numbered
  # BENCH_r*.json lying in the repo root (none is committed: save the ones
  # you want compared) and print per-metric deltas with regression flags. ADVISORY: regressions print loudly but never fail CI — a slow
  # bench leg should be seen, not block unrelated work (pass --strict to
  # tools/bench_diff.py directly to gate on it).
  local new="${1:-}"
  if [ -z "$new" ]; then
    new=$(ls BENCH_r*.json 2>/dev/null | sort | tail -1)
  fi
  if [ -z "$new" ]; then
    echo "no BENCH_r*.json to diff; skipping"
    return 0
  fi
  python tools/bench_diff.py "$new" || true
  return 0
}

gateway_smoke() {
  echo "== gateway smoke =="
  # black-box lifecycle of `python -m deepspeed_tpu.serving`: ephemeral
  # port, one streamed completion, one shed (429 + Retry-After), the
  # compiled-program bound via /v1/metrics, SIGTERM drain exits 0
  timeout -k 10 420 env JAX_PLATFORMS=cpu python tools/gateway_smoke.py
}

if [ "${1:-}" = "--guards" ]; then
  guards
  exit $?
fi
if [ "${1:-}" = "--gateway" ]; then
  gateway_smoke
  exit $?
fi
if [ "${1:-}" = "--offload" ]; then
  offload_lane
  exit $?
fi
if [ "${1:-}" = "--observability" ]; then
  observability_lane
  exit $?
fi
if [ "${1:-}" = "--rlhf" ]; then
  rlhf_lane
  exit $?
fi
if [ "${1:-}" = "--sharded" ]; then
  sharded_lane
  exit $?
fi
if [ "${1:-}" = "--hierkv" ]; then
  hierkv_lane
  exit $?
fi
if [ "${1:-}" = "--multilora" ]; then
  multilora_lane
  exit $?
fi
if [ "${1:-}" = "--disagg" ]; then
  disagg_lane
  exit $?
fi
if [ "${1:-}" = "--moe" ]; then
  moe_lane
  exit $?
fi
if [ "${1:-}" = "--capacity" ]; then
  capacity_lane
  exit $?
fi
if [ "${1:-}" = "--longctx" ]; then
  longctx_lane
  exit $?
fi
if [ "${1:-}" = "--fusedblock" ]; then
  fusedblock_lane
  exit $?
fi
if [ "${1:-}" = "--autoscale" ]; then
  autoscale_lane
  exit $?
fi
if [ "${1:-}" = "--multihost" ]; then
  multihost_lane
  exit $?
fi
if [ "${1:-}" = "--bench-diff" ]; then
  bench_diff "${2:-}"
  exit $?
fi

echo "== tier-1 core lane =="
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
  --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly \
  2>&1 | tee /tmp/_t1.log
t1_rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)"

# the compile-count guard runs inside tier-1 too; re-running the guard lane
# standalone keeps its failure visible even when unrelated tier-1 lanes are red
guards
g_rc=$?

offload_lane
o_rc=$?

gateway_smoke
gw_rc=$?

observability_lane
ob_rc=$?

rlhf_lane
rl_rc=$?

sharded_lane
sh_rc=$?

hierkv_lane
hk_rc=$?

multilora_lane
ml_rc=$?

disagg_lane
dg_rc=$?

moe_lane
me_rc=$?

capacity_lane
cp_rc=$?

fusedblock_lane
fb_rc=$?

longctx_lane
lc_rc=$?

autoscale_lane
as_rc=$?

multihost_lane
mh_rc=$?

# advisory: surfaces last round's bench regressions, never fails the build
bench_diff

[ "$t1_rc" -eq 0 ] && [ "$g_rc" -eq 0 ] && [ "$o_rc" -eq 0 ] && [ "$gw_rc" -eq 0 ] && [ "$ob_rc" -eq 0 ] && [ "$rl_rc" -eq 0 ] && [ "$sh_rc" -eq 0 ] && [ "$hk_rc" -eq 0 ] && [ "$ml_rc" -eq 0 ] && [ "$dg_rc" -eq 0 ] && [ "$me_rc" -eq 0 ] && [ "$cp_rc" -eq 0 ] && [ "$fb_rc" -eq 0 ] && [ "$lc_rc" -eq 0 ] && [ "$as_rc" -eq 0 ] && [ "$mh_rc" -eq 0 ]
