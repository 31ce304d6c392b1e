#!/bin/bash
# CI entry — the reference's paddle/scripts/paddle_build.sh role, sized
# for this repo: native build, API freeze gate, tiered tests, wheel.
#
#   tools/ci.sh smoke    # native build + API gate + smoke tier (~2 min)
#   tools/ci.sh mid      # + one deep test per subsystem (~5-6 min;
#                        #   pallas, partitioning, hybrid 3D, CP, quant,
#                        #   native, serving — certify without the full bill)
#   tools/ci.sh full     # everything incl. the slow tier (~15-25 min)
#   tools/ci.sh wheel    # build a wheel into dist/
#
# Exit code is the first failing stage's.

set -u
REPO="$(cd "$(dirname "$0")/.." && pwd)"
cd "$REPO"
MODE="${1:-smoke}"

stage() { echo; echo "=== [$1] ==="; }

stage "native build"
make -C paddle_tpu/native -s || exit $?

stage "native unit tests"
make -C paddle_tpu/native -s test || exit $?

stage "API freeze gate"
JAX_PLATFORMS=cpu python -c "
import jax; jax.config.update('jax_platforms','cpu')
import sys; sys.path.insert(0, 'tools')
import diff_api
sys.exit(diff_api.main())
" || exit $?

case "$MODE" in
  smoke|mid|full)
    # repo lint (analysis/lint.py): the framework's own invariants —
    # atomic state writes, span clocks, thread names, donation hygiene,
    # debug leftovers. Pure AST, budget well under 20 s. Family-scoped
    # so the race-smoke stage below isn't a duplicate repo walk.
    stage "repo lint (tools/lint.py)"
    JAX_PLATFORMS=cpu python tools/lint.py --select PT-LINT || exit $?
    # race smoke: the concurrency verification plane — the PT-RACE
    # static pass repo-wide (lock-order inversions, unsynced shared
    # writes, blocking-under-lock) plus the runtime lock-order
    # watchdog's unit tests incl. the seeded injected inversion.
    # Pure AST + thread-only tests; stays inside the ~20 s lint budget.
    stage "race smoke (PT-RACE lint + lock-order watchdog units)"
    JAX_PLATFORMS=cpu python tools/lint.py --select PT-RACE || exit $?
    JAX_PLATFORMS=cpu python -m pytest tests/test_lockwatch.py -q \
      || exit $?
    # kernel smoke: the int8-native decode plane — interpret-mode
    # parity of the Pallas paged kernel's int8 dequant-epilogue path
    # vs the gather+dequant reference (GQA/MQA, windows, ragged
    # cursors) plus the tuning-table dtype-key roundtrip + stale-table
    # diagnostic. Tiny shapes; runs on CPU without a chip.
    stage "kernel smoke (int8/float paged-decode parity + tuning \
dtype keys)"
    JAX_PLATFORMS=cpu python -m pytest tests/test_paged_kv.py \
      -q -k "quantized_kernel or gather_upto" || exit $?
    JAX_PLATFORMS=cpu python -m pytest tests/test_pallas_decode.py \
      -q -k "dtype_key" || exit $?
    ;;
esac

case "$MODE" in
  smoke)
    stage "smoke tier (pytest -m smoke)"
    python -m pytest tests/ -m smoke -q || exit $?
    ;;
  mid)
    stage "mid tier (pytest -m mid)"
    python -m pytest tests/ -m mid -q || exit $?
    stage "embedding smoke (SIGKILL mid-ep-table-save -> newest \
committed step restores, then re-places onto a smaller ep mesh; the \
fast ep-plan/exchange/host-cache tests ride -m mid above)"
    JAX_PLATFORMS=cpu python -m pytest tests/test_embedding_ckpt.py \
      -q -m chaos || exit $?
    stage "fleet smoke (2-rank launch -> train -> coordinated SIGTERM \
-> resume; chaos tier, FaultInjector seeds pinned)"
    JAX_PLATFORMS=cpu python -m pytest tests/test_fleet_controller.py \
      -q -m chaos || exit $?
    stage "router smoke (2-replica HTTP router e2e on the CPU backend \
+ dispatch-fault failover; deterministic seeds)"
    JAX_PLATFORMS=cpu python -m pytest tests/test_serving_router.py \
      -q -k "http_router_smoke or dispatch_fault or all_replicas_down" \
      || exit $?
    stage "stream smoke (2-worker routed STREAMING request: tokens \
arrive incrementally across processes over per-token-flushed SSE)"
    JAX_PLATFORMS=cpu python -m pytest tests/test_serving_stream.py \
      -q -k "stream_smoke" || exit $?
    stage "aot smoke (export compiled programs -> drop the model -> \
trace-free restore_and_run boot serves bit-identical tokens on CPU; \
fingerprint-mismatch fallback + GC staleness ride -m mid above)"
    JAX_PLATFORMS=cpu python -m pytest tests/test_aot.py \
      -q -k "round_trip or trace_free" || exit $?
    stage "trace smoke (routed request through 2 worker processes -> \
ONE merged cross-process chrome-trace with a shared trace id)"
    JAX_PLATFORMS=cpu python -m pytest tests/test_tracing.py \
      -q -m chaos || exit $?
    stage "scaler smoke (recorded-trace policy replay bit-identity + \
one spawn/retire e2e on real in-process replicas; the SIGKILL chaos \
pair rides the full suite only)"
    JAX_PLATFORMS=cpu python -m pytest tests/test_autoscale.py \
      -q -k "replay or spawn_retire_e2e" || exit $?
    stage "reliability smoke (SIGSTOP a worker mid-stream -> gray \
quarantine + hedge completes within deadline -> SIGCONT half-open \
probe restores; plus seeded retry-budget-exhaustion determinism; \
the fast deadline/budget/breaker units ride -m mid above)"
    JAX_PLATFORMS=cpu python -m pytest tests/test_reliability.py \
      -q -m chaos || exit $?
    stage "dist smoke (REAL 2-process jax.distributed job: preempt \
agreement + a step-agreed periodic save, both over the LIVE \
ClientTransport KV — not the file fallback)"
    JAX_PLATFORMS=cpu python -m pytest \
      "tests/test_dist_fleet_transport.py::\
test_dist_smoke_agreement_and_step_agreed_save" -q || exit $?
    stage "multichip dryrun (8-device CPU sim)"
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -c "import __graft_entry__ as g; g.dryrun_multichip(8)" \
      || exit $?
    ;;
  full)
    stage "full suite"
    python -m pytest tests/ -q || exit $?
    stage "multichip dryrun (8-device CPU sim)"
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -c "import __graft_entry__ as g; g.dryrun_multichip(8)" \
      || exit $?
    ;;
  wheel)
    stage "wheel"
    python setup.py -q bdist_wheel 2>/dev/null || python -m pip wheel \
      --no-deps -w dist . || exit $?
    ls -la dist/
    ;;
  *)
    echo "unknown mode: $MODE (smoke|mid|full|wheel)" >&2
    exit 2
    ;;
esac

echo; echo "CI ($MODE) green"
