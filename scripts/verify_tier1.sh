#!/usr/bin/env bash
# Tier-1 verification — the exact command ROADMAP.md pins (kept verbatim so
# CI, the driver, and humans all run the same gate). Exits non-zero on any
# test failure; prints DOTS_PASSED=<n> for the no-worse-than-seed check.
#
# Pre-gate 1: the MoE-dispatch/HLO-collective suites (ISSUE 3), the decode
# fast-path surfaces (ISSUE 4), the graph-auditor suite (ISSUE 5), and the
# serving runtime (ISSUE 6) must COLLECT. The main run passes
# `--continue-on-collection-errors`, under which an import error in one
# file still fails the run but buries the cause at the bottom of a long
# log; failing fast here names the broken file first. Collection is cheap
# (no tests execute).
timeout -k 10 120 env JAX_PLATFORMS=cpu python -m pytest --collect-only -q -p no:cacheprovider \
  tests/test_moe.py tests/test_collectives_hlo.py \
  tests/test_generate.py tests/test_decode_fused.py tests/test_metrics.py \
  tests/test_analysis.py tests/test_numerics.py tests/test_bf16.py \
  tests/test_serve.py tests/test_trace.py tests/test_devprof.py \
  tests/test_adapters.py tests/test_overlap_collectives.py \
  tests/test_router.py tests/test_elastic.py tests/test_goodput.py \
  tests/test_pool.py tests/test_spec.py tests/test_kernel_audit.py > /dev/null || {
    echo "tier-1 pre-gate: MoE/HLO/decode/analysis/serve/trace/devprof/adapters/overlap/router/elastic/goodput/pool/spec/kernel-audit test collection failed" >&2; exit 1; }
# Pre-gate 2 (ISSUE 5 + 6): the graph audit — lower/compile the
# dp/tp/fsdp/ep train steps (8-virtual-device CPU mesh), the greedy decode
# scan, AND the serving (continuous-batching) decode step; run the rule
# engine (collective census, donation, dtype, host-sync lint, recompile)
# and gate on ALL committed baselines under dtc_tpu/analysis/baselines/.
# BOTH serve entries (multi-tenant lora + adapter-free) carry recompile
# fingerprints that ADMIT a request — and, for the lora flavor, LOAD an
# adapter — between the two measured executions, so their
# cold==1/steady==0 baselines prove admission and tenant churn at fixed
# slots never recompile the decode step. ~2-3 min on this
# 1-core host; runs anywhere (JAX_PLATFORMS=cpu, no accelerator). On an
# INTENDED graph change: re-bless with
#   python scripts/audit_graph.py --modes dp,tp,fsdp,ep,fsdp_overlapped,3d,bf16 --decode --serve --write-baseline
# and commit the baseline diff.
# (ISSUE 11 grew the entry set to 9: --decode now also audits the
# layer-fused megakernel flavor `decode_fused_layers`, and --serve the
# int8-cache `serve_decode_int8` flavor — timeout raised 480 -> 660 for
# the two extra lower+compile+execute passes on this 1-core host.
# ISSUE 12 grows it to 11: `fsdp_overlapped` and `3d` (DP×FSDP×TP) audit
# the overlapped-collectives ring programs — their census requires the
# ring transport (collective-permute / Pallas custom-calls) and forbids
# the serialized per-layer kernel all-gathers; timeout 660 -> 960 for
# the two extra unrolled-ring compiles. ISSUE 14 grows it to 12: the
# `bf16` entry audits the bf16_mixed training mode, and the numerics
# (dtype-flow + dtype-literal lint) and memory (static HBM plan) passes
# run ON BY DEFAULT, gating the <entry>.numerics.json / <entry>.memory.json
# baselines alongside the graph fingerprints; timeout 960 -> 1080 for
# the extra lower+compile+execute pass. ISSUE 19 grows it to 13: the
# `serve_spec` entry audits one full speculative round — draft propose +
# one-launch k-verify under admission churn (cold==1/steady==0), with the
# zero-copy draft rung's weights reconciled as entry parameters in the
# memory decomposition; timeout 1080 -> 1200 for the extra
# lower+compile+execute pass.)
timeout -k 10 1200 env JAX_PLATFORMS=cpu python scripts/audit_graph.py \
  --modes dp,tp,fsdp,ep,fsdp_overlapped,3d,bf16 --decode --serve --check-baselines || {
    echo "tier-1 pre-gate: graph audit failed (see findings above)" >&2; exit 1; }
# Pre-gate 3 (ISSUE 6): fast scheduler smoke — four requests (two sharing
# a system-prompt prefix) through the real continuous-batching engine on
# the tiny audit model, every output asserted token-for-token identical
# to generate(). ~30-60 s; catches a broken scheduler before the long
# main run buries it.
timeout -k 10 240 env JAX_PLATFORMS=cpu python scripts/serve_smoke.py || {
    echo "tier-1 pre-gate: serving scheduler smoke failed" >&2; exit 1; }
# Pre-gate 4 (ISSUE 7): tracing smoke — 3 training steps + 2 serve
# requests with tracing on, then the offline leg: trace_report's loaders
# must produce a span attribution table, per-request waterfalls
# (queued->prefill->decode->done for every request), and a Perfetto
# export with the required ph/ts/dur/pid/tid/name keys and monotonic
# timestamps. ~1-2 min; catches a broken span/export pipeline early.
timeout -k 10 300 env JAX_PLATFORMS=cpu python scripts/trace_smoke.py || {
    echo "tier-1 pre-gate: tracing smoke failed" >&2; exit 1; }
# Pre-gate 5 (ISSUE 8): device-time observatory smoke — capture a 2-step
# devprof window around the b8 audit train step, then the offline leg: the
# shared parser + attribution must cover >= 90% of measured device time
# with every dot-class op attributed, and the merged host+device
# Perfetto export must hold both timelines on aligned wall clocks.
# Skips (exit 0) with a warning in environments whose profiler emits no
# op events at all. ~1-2 min. ISSUE 11 adds the decode launch-count
# cross-check (per-layer vs fused_layers: while-census hard assert +
# scan/data_movement share A/B) — timeout raised 300 -> 480 for the two
# extra decode compiles.
timeout -k 10 480 env JAX_PLATFORMS=cpu python scripts/devprof_smoke.py || {
    echo "tier-1 pre-gate: devprof smoke failed" >&2; exit 1; }
# Pre-gate 6 (ISSUE 10): adapter-loop smoke — two LoRA adapters finetuned
# 3 steps each through the real trainer (adapter-only TrainState, shared
# frozen base), exported + reloaded via the adapter-artifact round-trip,
# then two tenants + one base request co-scheduled in ONE in-flight batch
# on the serving engine, every output asserted token-for-token identical
# to solo generate() with the matching adapter, with zero steady-state
# recompiles across the mixed-tenant admissions. ~1-2 min.
timeout -k 10 300 env JAX_PLATFORMS=cpu python scripts/adapter_smoke.py || {
    echo "tier-1 pre-gate: adapter-loop smoke failed" >&2; exit 1; }
# Pre-gate 7 (ISSUE 13): serving-fleet smoke — 3 in-process replicas of
# the tiny audit model with two LoRA tenants + base traffic and a shared
# system prompt, one chaos replica-kill mid-traffic targeting a tenant's
# affinity home. Asserts zero silent drops (submits reconciled against
# terminal results), survivor re-prefill token-identity for EVERY
# completed request (failover hops included — proves the adapter-reload-
# on-survivor path, since base-weight decode would fork the tokens), and
# tenant/prefix affinity actually routing. ~1-2 min.
timeout -k 10 300 env JAX_PLATFORMS=cpu python scripts/fleet_smoke.py || {
    echo "tier-1 pre-gate: serving-fleet smoke failed" >&2; exit 1; }
# Pre-gate 8 (ISSUE 15): elastic-training smoke — kill a virtual host at
# step 6 of an 8-device DP x FSDP run; heartbeat detection + in-memory
# snapshot restore (<= 1 step lost, ring-mirror sourced) + 8 -> 4 shrink
# must finish the token budget. Asserts the bit-exact snapshot-replay
# gate (a shrunk restart from the resize's cold spill replays the
# post-resize losses identically), the loss-parity gate vs an
# uninterrupted run, typed host_lost/elastic_resize events, and exactly
# ONE recompile at the first replayed step. ~1-2 min.
timeout -k 10 300 env JAX_PLATFORMS=cpu python scripts/elastic_smoke.py || {
    echo "tier-1 pre-gate: elastic-training smoke failed" >&2; exit 1; }
# Pre-gate 9 (ISSUE 16): goodput-ledger smoke — a 6-step train run with a
# chaos NaN at step 3 (rollback + replay through the real guard) and a
# 2-request serve run, then the ledger leg: the goodput report must
# render from the shards alone, per-host interval sums must reconcile
# with wall-clock within 1% (unattributed <= 5%), the rollback incident
# bill must carry t_detect/t_restored + the discarded step's tokens,
# the reducer must attach a `goodput` section, and the Perfetto export
# must carry the goodput_pct counter track (ph "C"). ~1-2 min.
timeout -k 10 300 env JAX_PLATFORMS=cpu python scripts/goodput_smoke.py || {
    echo "tier-1 pre-gate: goodput-ledger smoke failed" >&2; exit 1; }
# Pre-gate 10 (ISSUE 17): resource-pool smoke — both legs of
# scripts/pool_smoke.py. Diurnal: GROW absorbs every idle serve host
# (zero-replica phase parks requests as typed backpressure), a spike
# burst shrinks back; asserts the typed transition walk, zero silent
# drops, loss parity vs an uninterrupted reference (prefix bit-exact,
# suffix rtol<=1e-3), exactly ONE recompile per mesh change, and the
# goodput gate (every resize billed as an elastic_resize incident,
# train-shard unattributed <= 5%). Chaos leg: pool_spike_mid_grow
# aborts the pre-resize grow cleanly and pool_kill_mid_shrink's victim
# is never leased back, on the same assertions. ~2-3 min.
timeout -k 10 480 env JAX_PLATFORMS=cpu python scripts/pool_smoke.py || {
    echo "tier-1 pre-gate: pool smoke (diurnal) failed" >&2; exit 1; }
timeout -k 10 480 env JAX_PLATFORMS=cpu python scripts/pool_smoke.py --chaos || {
    echo "tier-1 pre-gate: pool smoke (chaos) failed" >&2; exit 1; }
# Pre-gate 11 (ISSUE 19): speculative-decoding smoke — draft extraction
# (3-of-4 layer rung, shared embed/head), spec_generate + serve-engine
# greedy token-identity vs plain generate() with accept_rate > 0, the
# structural one-launch-per-verify while-census (the jitted spec round
# under fused_layers must lower with strictly fewer HLO while loops
# than the per-layer fused baseline — same baseline as devprof's decode
# cross-check), and the goodput-honesty leg (ledger reconciles >= 99%
# of wall-clock, rejected-proposal seconds billed to the TYPED
# spec_rejected_draft class). ~1-2 min.
timeout -k 10 300 env JAX_PLATFORMS=cpu python scripts/spec_smoke.py || {
    echo "tier-1 pre-gate: speculative-decoding smoke failed" >&2; exit 1; }
# Pre-gate 12 (ISSUE 20): the kernel audit — DMA happens-before race
# detection over the recorded ring-kernel schedules (the concurrency
# discipline interpret mode's serialized execution cannot test), the
# static VMEM/SMEM plans for every Pallas kernel across the model
# ladder gated on the committed kernels_<rung>.json baselines
# (flagship / ladder_350m / ladder_1b — including the static megakernel
# double-buffer verdict), and the index-map/SMEM/gate-coverage lint
# family. Kernel-only invocation (--modes '' + section opt-outs): the
# train/decode/serve graph entries are pre-gate 2's job. ~1 min.
timeout -k 10 600 env JAX_PLATFORMS=cpu python scripts/audit_graph.py \
  --kernels --modes '' --no-numerics --no-memory --check-baselines || {
    echo "tier-1 pre-gate: kernel audit failed (see findings above)" >&2; exit 1; }
set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c); exit $rc
