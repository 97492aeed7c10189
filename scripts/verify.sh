#!/usr/bin/env bash
# Tier-1 verification with a fast import-health gate.
#
# Stage 1 runs `pytest --collect-only`: any module that fails to import
# (a moved JAX API, a broken compat shim, a missing dependency) fails here in
# seconds, instead of surfacing as a wall of per-module collection ERRORs
# buried in a multi-minute test run — exactly how the seed's 14 import
# breakages went unnoticed.
#
# Stage 2 is the static audit (docs/static_analysis.md): generic lint (ruff
# or the stdlib fallback), jaxlint's seven project rules (host syncs in
# compiled regions, un-rank-gated writes, unlocked cross-thread mutation,
# wall-clock in jitted code, bare excepts, undonated state jits, unstrict
# pytree-leaf zips — every waiver printed with its reason), the
# compiled-program HLO audit (100% param/opt-state donation on the real
# single-step AND chained programs, no fp32 dot/conv under bf16, no host
# callbacks in the chained window), and the SPMD communication audit
# (ISSUE 11): a collective inventory of the partitioned dp8/fsdp8/tp2x4/
# dp2fsdp2tp2 single-step and chained programs checked against the analytic
# expected-comm model (no accidental full-param gathers on the tensor axis,
# totals within the model's bound) and gated against COMM_BASELINE.json
# exactly like the perf gate. The audits run on 8 forced-host devices so
# donation + precision + collectives are all verified on genuinely sharded
# SPMD programs (ISSUE 10/11). The gate's teeth are tested on every run:
# an injected lint violation, an injected undonated lowering (sharded
# programs included), and an injected mis-ruled TP spec (whose optimizer
# update must all-gather the full parameter) must each make it FAIL.
#
# Stage 3 is a ~8s CPU run through the real chained Trainer hot path
# asserting (via the engine's compilation counters) that the chained
# executable compiles exactly once per shape — a dispatch-path regression
# that silently retraces every window fails here in seconds instead of as a
# mysterious multi-minute-per-window slowdown on real hardware.
#
# Stage 4 is a ~10s CPU digits run in precision="bf16" asserting the loss
# decreases, no steps are skipped, compute runs in bf16, and master weights
# stay fp32 — precision regressions fail fast like retrace regressions.
#
# Stage 5 is a short CPU digits run with telemetry="on" asserting the event
# log is well-formed JSONL, goodput bucket fractions sum to 1 +- eps, and the
# on-device health stats rode the chained windows without a retrace. The run
# is also traced with profile=ProfileConfig (ISSUE 6): the capture must
# complete, its StepProfile category fractions must sum to 1 +- eps, and the
# profile_capture event must land in the log.
#
# Stage 6 is the memory-accounting gate (docs/memory.md): the preflight's
# predicted peak must equal the number re-derived from
# compiled.memory_analysis() by independent stdlib arithmetic on the real
# digits single-step AND chained programs, with buffer-class fractions
# summing to 1 — and its --inject-oversize self-test: a deliberately
# unfittable capacity MUST fail preflight with a finite, actually-fitting
# batch recommendation (the perf-gate "gate has teeth" pattern).
#
# Stage 7 is the sharded-training smoke (docs/parallelism.md): on 8
# forced-host CPU devices, an fsdp=8 run must be BIT-EXACT with pure DP
# (losses + params), a data=2/fsdp=2/tensor=2 run must match DP to
# float32-ULP with bit-exact sharded init, the sharded chained trainer must
# compile once per shape, and a SIGTERM-killed fsdp=8 run must resume under
# a pure-DP mesh (the resharding restore path) and finish bit-exact with an
# uninterrupted run.
#
# Stage 8 is the chaos soak in --quick mode: a real digits training job killed
# 3 times (graceful SIGTERM, SIGKILL mid-background-commit, SIGKILL mid-
# chained-window) at seeded offsets, resumed after each kill, asserting every
# kill leaves >= 1 valid checkpoint, the final params are bit-exact with an
# uninterrupted run, and the async save's hot-loop stall is < 25% of the sync
# save wall time. CHAOS_SEED reproduces a failing schedule deterministically.
#
# Stage 9 is the elastic chaos soak (ISSUE 12): the same digits job run on
# 8 forced-host devices under an fsdp=8 mesh, killed (SIGTERM / SIGKILL) and
# resumed on 4 devices with mesh=None — the Trainer must re-plan the mesh +
# grad-accum factor from the checkpoint's sharding record — plus the mirror
# 4->8 grow leg. Asserts every kill leaves a valid sharded checkpoint, every
# elastic resume completes and logs an elastic_restore event with the
# expected axes/accum, the elastic resume is BIT-EXACT with an explicitly
# hand-configured twin resume (the 4->8 leg with no accum change), and final
# params match an uninterrupted same-global-batch run within the documented
# tolerance (docs/fault_tolerance.md).
#
# Stage 10 is the perf-regression gate (docs/profiling.md): a ~10s CPU
# measurement of the real chained-engine path, gated as a machine-portable
# calibrated ratio against the committed PERF_BASELINE.json — a step-time
# regression past tolerance (an accidental retrace, a lost chained dispatch
# path) fails here. The gate's own teeth are tested on every run: a
# deliberate 3x injected slowdown must make it FAIL.
#
# Stage 11 is the data-wait gate (ISSUE 13 / ROADMAP item 5): a short real
# digits Trainer run with telemetry on, gating the steady-state data_wait
# goodput fraction against the committed PERF_BASELINE.json ceiling — the
# input pipeline cannot quietly become the bottleneck. Teeth: an injected
# per-batch loader sleep (the ShardedLoader.load_delay_s seam) must FAIL.
#
# Stage 12 is the run-doctor self-test (ISSUE 13; docs/observability.md):
# four short digits runs — a clean twin plus three with a known bottleneck
# injected through existing seams (loader sleep, async commit_delay_s,
# FaultPlan hang) — and the doctor must name each culprit (data_bound /
# checkpoint_stall / straggler) and say healthy on the clean twin. The
# clean twin's exported timeline must be valid trace-event JSON whose
# goodput spans re-derive the meter's fractions within epsilon.
#
# Stage 13 is the live-monitor self-test (ISSUE 15; docs/observability.md
# "Live monitoring"): run_monitor.py --self-test drives the live
# monitor against real background digits runs through the existing fault
# seams — a clean run must read training/healthy live and match
# run_doctor.py's post-hoc steady fractions to 1e-6 (byte-identical
# diagnoses), an injected FaultPlan hang must flip the verdict to
# stale_heartbeat while the watchdog's patrol heartbeats keep the log
# breathing, SIGKILL mid-hang must flip it to dead, a loader-sleep run
# followed live must raise exactly ONE debounced data_bound alert, and
# the --once exit codes (0 clean / 1 degraded / 2 dead) are asserted.
#
# Stage 14 is the run-comparison gate (ISSUE 14; docs/profiling.md
# "before/after ritual"): run_compare.py --self-test — identical twin runs
# must diff clean (no goodput bucket over the noise floor), and three
# injected known-cause slowdowns (a synthetic 3x convolution, the loader
# load_delay_s seam, the async committer commit_delay_s seam) must each be
# attributed to the correct category/bucket with evidence refs.
#
# Stage 15 is the autotuner gate (ISSUE 17; docs/performance.md
# "Autotuning"): autotune.py --self-test measures a deliberately 3x de-tuned
# baseline on a tiny CPU workload (the perf-gate inject-slowdown pattern —
# applied AFTER measurement so the seam cannot leak into candidates), sweeps
# >= 3 declared chain_steps candidates, and must rank the known-win seam
# first with per-category attribution through profiling.diff — while a
# candidate whose provenance drifted on an UNdeclared key (dtype) must be
# REFUSED, never ranked (the run_compare rule from ISSUE 14, applied
# per-candidate). The TUNED.json emit/load round-trip and the XLA-flag ->
# per-compile compiler_options bridge are asserted in the same run. A
# Pallas-parity smoke leg then re-checks kernel<->plain forward AND backward
# parity in interpret mode plus the one-time kernel_dispatch telemetry and
# the shared scan-chain timing core.
#
# Stage 16 is the fleet-controller soak (ISSUE 16; docs/fault_tolerance.md
# "Closed-loop recovery"): fleet_controller.py --soak --quick spawns a 3-run
# digits fleet and injects one disease per run (SIGKILL mid-run, a FaultPlan
# hang tripping the step watchdog, the slow_chip seam degrading one named
# chip under fsdp=2); the controller must restore ALL THREE to healthy
# autonomously — restart from latest_valid, restart excluding exactly the
# slow chip via the elastic re-plan, and an A/B-judged prefetch tune on the
# starved run — with every decision audited as a controller_action record
# carrying evidence, and final params within the elastic tolerance of
# uninterrupted twins. The --max-restarts 0 leg must REFUSE (record the
# decision, touch nothing) and exit non-zero: the controller never acts
# without budget.
#
# Stage 18 is the actuated-offer soak (ISSUE 20; docs/serving.md "Drain,
# re-plan, and degraded mode"): serving_soak.py --actuate drives the full
# self-healing handshake against real subprocess replicas — a chip freed by
# a trainer's restart_excluding is offered over /admin/offer, the accepting
# dp1 replica drains (bounded deadline, typed 503 + Retry-After) and
# re-plans live onto dp2, and the absorb is A/B-judged on QPS-per-chip with
# the chip-scaled expected floor and KEPT; RetryClient traffic rides the
# drain with ZERO failed requests and bit-identical response bytes across
# the re-plan; the offer_chip -> offer_accept -> drain_start -> replan_done
# audit chain is asserted in wall-clock order across both flight recorders;
# a monitor polling throughout must never read the draining replica as
# dead. A replica under SLO pressure must DECLINE (nothing drained), and a
# handshake against an unreachable replica must revert cleanly and re-arm.
#
# Stage 19 is the ROADMAP.md tier-1 command verbatim.
set -o pipefail

cd "$(dirname "$0")/.."

echo "== stage 1/19: import health (pytest --collect-only) =="
if ! JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --collect-only \
    -p no:cacheprovider > /tmp/_collect.log 2>&1; then
  echo "COLLECTION FAILED — import breakage (full log: /tmp/_collect.log):"
  grep -aE "ERROR|ImportError|ModuleNotFoundError" /tmp/_collect.log | head -40
  exit 2
fi
tail -1 /tmp/_collect.log

echo "== stage 2/19: static audit (generic + jaxlint + HLO + comm) =="
if ! JAX_PLATFORMS=cpu python scripts/static_audit.py; then
  echo "STATIC AUDIT FAILED — fix the finding or waive it inline with a reason"
  echo "(# jaxlint: disable=<rule> -- <why>; catalog: docs/static_analysis.md;"
  echo " comm-baseline drift? re-record: scripts/static_audit.py --update-comm-baseline)"
  exit 3
fi
# Each injection run skips the passes it does not target (they already ran
# clean above) — the self-tests pay only for the pass under test.
if JAX_PLATFORMS=cpu python scripts/static_audit.py --inject-violation lint --skip-hlo --skip-comm \
    > /tmp/_audit_selftest.log 2>&1; then
  echo "STATIC AUDIT SELF-TEST FAILED — injected lint violations PASSED the gate"
  exit 3
fi
if JAX_PLATFORMS=cpu python scripts/static_audit.py --inject-violation hlo --skip-comm \
    > /tmp/_audit_selftest.log 2>&1; then
  echo "STATIC AUDIT SELF-TEST FAILED — an undonated program PASSED the HLO audit"
  exit 3
fi
if JAX_PLATFORMS=cpu python scripts/static_audit.py --inject-violation comm --skip-hlo \
    > /tmp/_audit_selftest.log 2>&1; then
  echo "STATIC AUDIT SELF-TEST FAILED — a mis-ruled TP spec (full-param all-gather) PASSED the comm audit"
  exit 3
fi
echo "static_audit self-tests OK: injected lint + donation + comm violations correctly failed"

echo "== stage 3/19: chained-dispatch retrace guard =="
if ! JAX_PLATFORMS=cpu python scripts/retrace_guard.py; then
  echo "RETRACE GUARD FAILED — the chained executable recompiles per window"
  exit 4
fi

echo "== stage 4/19: mixed-precision smoke (bf16 digits) =="
if ! JAX_PLATFORMS=cpu python scripts/precision_smoke.py; then
  echo "PRECISION SMOKE FAILED — bf16 training path regressed"
  exit 5
fi

echo "== stage 5/19: telemetry smoke (event log + goodput + stats) =="
if ! JAX_PLATFORMS=cpu python scripts/telemetry_smoke.py; then
  echo "TELEMETRY SMOKE FAILED — observability subsystem regressed"
  exit 6
fi

echo "== stage 6/19: memory-accounting gate (preflight parity + oversize self-test) =="
if ! JAX_PLATFORMS=cpu python scripts/memory_probe.py; then
  echo "MEMORY PROBE FAILED — preflight prediction drifted from compiled.memory_analysis()"
  exit 7
fi
if ! JAX_PLATFORMS=cpu python scripts/memory_probe.py --inject-oversize; then
  echo "MEMORY PROBE SELF-TEST FAILED — an unfittable config must fail preflight with a batch recommendation"
  exit 7
fi

echo "== stage 7/19: sharded-training smoke (FSDP/TP parity + resharding resume) =="
if ! JAX_PLATFORMS=cpu python scripts/sharding_smoke.py; then
  echo "SHARDING SMOKE FAILED — FSDP/TP parity, sharded retrace guard, or the resharding restore path regressed"
  exit 8
fi

echo "== stage 8/19: chaos soak (kill/resume, async checkpointing) =="
if ! JAX_PLATFORMS=cpu python scripts/chaos_soak.py --quick; then
  echo "CHAOS SOAK FAILED — recovery machinery regressed (reproduce: CHAOS_SEED)"
  exit 9
fi

echo "== stage 9/19: elastic chaos soak (kill on N devices, resume on M) =="
if ! JAX_PLATFORMS=cpu python scripts/chaos_soak.py --elastic --quick; then
  echo "ELASTIC CHAOS SOAK FAILED — the N->M mesh re-plan / batch-equivalent"
  echo "restore regressed (reproduce: CHAOS_SEED; docs/fault_tolerance.md)"
  exit 11
fi

echo "== stage 10/19: perf-regression gate (clean + injected-slowdown self-test) =="
if ! JAX_PLATFORMS=cpu python scripts/perf_gate.py --quick; then
  echo "PERF GATE FAILED — step time regressed past tolerance vs PERF_BASELINE.json"
  echo "(legitimate perf change? re-record: scripts/perf_gate.py --quick --update)"
  exit 10
fi
if JAX_PLATFORMS=cpu python scripts/perf_gate.py --quick --inject-slowdown 3; then
  echo "PERF GATE SELF-TEST FAILED — a 3x injected regression PASSED the gate"
  exit 10
fi
echo "perf_gate self-test OK: injected 3x regression correctly failed"

echo "== stage 11/19: data-wait gate (clean + injected-starvation self-test) =="
if ! JAX_PLATFORMS=cpu python scripts/perf_gate.py --data-wait; then
  echo "DATA-WAIT GATE FAILED — the input pipeline's steady-state data_wait"
  echo "fraction exceeds the PERF_BASELINE.json ceiling (ROADMAP item 5)"
  echo "(legitimate pipeline change? re-record: scripts/perf_gate.py --data-wait --update)"
  exit 12
fi
if JAX_PLATFORMS=cpu python scripts/perf_gate.py --data-wait --inject-data-wait 0.05 \
    > /tmp/_data_wait_selftest.log 2>&1; then
  echo "DATA-WAIT GATE SELF-TEST FAILED — an injected starved pipeline PASSED the gate"
  exit 12
fi
echo "data-wait gate self-test OK: injected loader sleep correctly failed"

echo "== stage 12/19: run-doctor self-test (injected-bottleneck diagnosis + timeline) =="
if ! JAX_PLATFORMS=cpu python scripts/run_doctor.py --self-test; then
  echo "RUN DOCTOR SELF-TEST FAILED — an injected bottleneck was misdiagnosed,"
  echo "the clean twin was not healthy, or the exported timeline broke the"
  echo "goodput span re-derivation (docs/observability.md)"
  exit 13
fi

echo "== stage 13/19: live-monitor self-test (heartbeat liveness + streaming doctor + alerts) =="
if ! JAX_PLATFORMS=cpu python scripts/run_monitor.py --self-test; then
  echo "RUN MONITOR SELF-TEST FAILED — the liveness contract broke: a hang did"
  echo "not read stale_heartbeat, a SIGKILL did not read dead, the healthy twin"
  echo "diverged from run_doctor's fractions, or the data_bound alert was not"
  echo "debounced to exactly one firing (docs/observability.md 'Live monitoring')"
  exit 15
fi

echo "== stage 14/19: run-comparison gate (twin-diff + injected attribution) =="
if ! JAX_PLATFORMS=cpu python scripts/run_compare.py --self-test; then
  echo "RUN COMPARE SELF-TEST FAILED — identical twins did not diff clean, or"
  echo "an injected known-cause slowdown (3x conv / loader sleep / commit"
  echo "delay) was attributed to the wrong category/bucket (docs/profiling.md)"
  exit 14
fi
echo "== stage 15/19: autotune gate (injected-win ranking + provenance refusal) + pallas parity =="
if ! JAX_PLATFORMS=cpu python scripts/autotune.py --self-test; then
  echo "AUTOTUNE SELF-TEST FAILED — the injected known-win (3x de-tuned"
  echo "baseline) was not ranked first with per-category attribution, a"
  echo "provenance-drifted candidate was not refused, or the TUNED.json"
  echo "round-trip broke (docs/performance.md 'Autotuning')"
  exit 17
fi
if ! JAX_PLATFORMS=cpu python -m pytest tests/test_pallas.py tests/test_dispatch.py tests/test_autotune.py \
    -q -m 'not slow' -p no:cacheprovider > /tmp/_pallas_parity.log 2>&1; then
  echo "PALLAS PARITY SMOKE FAILED — kernel<->plain parity, dispatch telemetry,"
  echo "or the shared timing core regressed (log: /tmp/_pallas_parity.log)"
  tail -20 /tmp/_pallas_parity.log
  exit 17
fi
tail -1 /tmp/_pallas_parity.log

echo "== stage 16/19: fleet-controller soak (closed-loop recovery + zero-budget refusal) =="
if ! JAX_PLATFORMS=cpu python scripts/fleet_controller.py --soak --quick; then
  echo "FLEET SOAK FAILED — the closed-loop controller did not restore the"
  echo "diseased fleet to healthy (restart / restart_excluding / A/B tune),"
  echo "an action went unaudited, or final params diverged from the"
  echo "uninterrupted twins (docs/fault_tolerance.md 'Closed-loop recovery')"
  exit 16
fi
if JAX_PLATFORMS=cpu python scripts/fleet_controller.py --soak --quick --max-restarts 0 \
    > /tmp/_fleet_zero_budget.log 2>&1; then
  echo "FLEET SOAK SELF-TEST FAILED — with --max-restarts 0 the controller"
  echo "must REFUSE (record the decision, touch nothing) and exit non-zero"
  exit 16
fi
echo "fleet soak self-test OK: zero-budget controller refused without acting"

echo "== stage 17/19: serving soak (continuous-batching SLO + hot-swap + failover) =="
if ! JAX_PLATFORMS=cpu python scripts/serving_soak.py --quick; then
  echo "SERVING SOAK FAILED — the p99 SLO was breached, responses were not"
  echo "bit-identical across a checkpoint hot-swap, a SIGKILL'd replica was"
  echo "not failed over by the fleet controller, or a zero-capacity server"
  echo "hung instead of refusing (docs/serving.md)"
  exit 18
fi

echo "== stage 18/19: actuated-offer soak (drain + live re-plan + A/B keep) =="
if ! JAX_PLATFORMS=cpu python scripts/serving_soak.py --actuate --quick; then
  echo "ACTUATE SOAK FAILED — the actuated chip offer regressed: a request"
  echo "failed or hung across the drain window, response bytes changed across"
  echo "the live re-plan, the offer/accept/drain/replan audit chain broke,"
  echo "the A/B judge mis-called the absorb, an SLO-pressured replica did not"
  echo "decline, a dead-replica handshake did not revert-and-re-arm, or the"
  echo "monitor read a draining replica as dead (docs/serving.md)"
  exit 20
fi

echo "== stage 19/19: tier-1 test suite =="
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
  --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly \
  2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
exit $rc
