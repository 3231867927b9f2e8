#!/usr/bin/env bash
# Pre-merge gate: static analysis first (cheap, seconds), then the test
# suite. Mirrors what tier-1 enforces — tests/test_graftlint.py re-runs the
# graftlint baseline check inside pytest — but fails faster when the lint
# gate is the problem.
#
# Usage:
#   helpers/check.sh            # graftlint + ruff/mypy (if installed) + tier-1
#   helpers/check.sh --quick    # same lint gate, then the quick pytest tier
#   helpers/check.sh --lint     # lint gate only, no pytest
#   helpers/check.sh --serve    # lint gate, then the serving smoke: boot
#                               # `python -m lightgbm_tpu.serve`, hit
#                               # /healthz + one /predict, shut down
#   helpers/check.sh --obs      # lint gate, then the observability smoke:
#                               # traced mini-train + serve, validate the
#                               # Chrome-trace JSON + Prometheus /metrics
#   helpers/check.sh --resil    # lint gate, then the resilience smoke:
#                               # SIGKILL a checkpointing training run at an
#                               # injected fault site, resume bit-identically;
#                               # SIGTERM-drain the real server mid-flight
#   helpers/check.sh --drift    # lint gate, then the model/data-observability
#                               # smoke: flight-recorded train (JSONL schema),
#                               # drift-monitored serve (shifted traffic must
#                               # alert, in-dist must not), HTML run report
#   helpers/check.sh --multichip
#                               # lint gate, then the multichip smoke: the
#                               # composed data-parallel sharded-chunk path
#                               # on 8 forced CPU devices — serial-loop vs
#                               # sharded-chunk model strings must match
#                               # bit for bit, one train_chunk compile,
#                               # serial-learner structural cross-check
#   helpers/check.sh --san      # lint gate (JX011-JX013 engaged), then the
#                               # runtime sanitizer: unit tests (seeded
#                               # transfer/NaN/lock-inversion violations all
#                               # caught; off-path provably free) + the
#                               # concurrency stress smoke (concurrent
#                               # predict + hot-swap + drain + drift +
#                               # /metrics scrape under
#                               # LIGHTGBM_TPU_SAN=transfer,nan,locks)
#   helpers/check.sh --loop     # lint gate, then the continuous-training
#                               # smoke: real serve stack — drift-shifted
#                               # traffic raises a PSI alert, the loop
#                               # controller observes it over HTTP,
#                               # retrains warm-started from the live
#                               # model, gates on AUC, publishes through
#                               # resil/atomic and hot-swaps the replica
#                               # (new version answers /predict with
#                               # lineage, drift sidecar refreshed), plus
#                               # one seeded mid-publish SIGKILL recovered
#                               # from the journal — under the full
#                               # runtime sanitizer
#   helpers/check.sh --tune     # lint gate, then the histogram-autotuner
#                               # smoke: sweep a tiny bucket-shape set on
#                               # CPU, persist + reload the tune cache,
#                               # gate the measured win (tuned route no
#                               # slower than the static default at every
#                               # swept shape, strictly faster at >= 1),
#                               # and prove the routing machinery is
#                               # bit-transparent (default-pinned table ==
#                               # untuned bytes; same-table reruns and
#                               # chunk=1-vs-4 byte-identical)
#   helpers/check.sh --elastic  # lint gate, then the elastic preemption-
#                               # tolerance smoke: ONE invocation at forced-
#                               # 8-CPU-device shapes — SIGKILL mid-run ->
#                               # same-mesh resume, SIGTERM -> emergency
#                               # checkpoint + exit 75 -> auto-resume
#                               # byte-equal to the uninterrupted run,
#                               # 8->2 resharded resume (loud warning +
#                               # structural identity), serial<->data@1
#                               # byte-identity (docs/FaultTolerance.md
#                               # §Elastic training)
#   helpers/check.sh --podwatch # lint gate, then the fleet-telemetry
#                               # smoke: ONE invocation — a real 2-process
#                               # CPU training run with the telemetry ring
#                               # + scrape endpoint armed and rank 1 seeded
#                               # slow, scraped live mid-run (/metrics +
#                               # /health + /timeline), then aggregated
#                               # (python -m lightgbm_tpu.obs.podwatch)
#                               # with the seeded straggler named in the
#                               # verdict + telemetry-off byte-identity
#                               # (docs/Observability.md §Fleet telemetry)
#   helpers/check.sh --flex     # lint gate, then the flexctl chaos
#                               # smoke: ONE invocation — a scripted
#                               # capacity storm on forced-multi-CPU
#                               # children (shrink 8->2 at a boundary,
#                               # grow back, SIGKILL one launch mid-
#                               # chunk) supervised end-to-end, gated
#                               # on flex_reshards labels matching the
#                               # script and the exactness taxonomy
#                               # (docs/FaultTolerance.md §Fleet
#                               # orchestrator)
#   helpers/check.sh --ir       # lint gate, then the graftir program
#                               # audit smoke: ONE invocation — seeded
#                               # violations per IR rule all caught, then
#                               # the real tree's registered jit entry
#                               # points traced abstractly over the quick
#                               # shape lattice and checked against the
#                               # IR001-IR006 baseline + the checked-in
#                               # program-fingerprint contract
#                               # (docs/StaticAnalysis.md §Program-level
#                               # audit)
#
# ruff/mypy are optional: the container may not ship them (no network
# installs); when absent they are skipped with a notice — graftlint and
# pytest are the hard gate either way.
set -u -o pipefail
cd "$(dirname "$0")/.."

MODE="${1:-full}"
case "$MODE" in
    full|--quick|--lint|--serve|--obs|--resil|--drift|--multichip|--san|--loop|--tune|--elastic|--podwatch|--flex|--ir) ;;
    *)
        echo "check.sh: unknown mode '$MODE' (expected --quick, --lint, --serve, --obs, --resil, --drift, --multichip, --san, --loop, --tune, --elastic, --podwatch, --flex or --ir)" >&2
        exit 2
        ;;
esac
fail=0

echo "== graftlint (lightgbm_tpu/ + helpers/ against baseline) =="
python -m tools.graftlint lightgbm_tpu/ helpers/ || fail=1

echo "== graftlint (tools/, no baseline) =="
python -m tools.graftlint --no-baseline tools/ || fail=1

if python -m ruff --version >/dev/null 2>&1; then
    echo "== ruff =="
    python -m ruff check lightgbm_tpu/ tools/ helpers/ tests/ || fail=1
else
    echo "== ruff not installed; skipping (config in pyproject.toml) =="
fi

if python -m mypy --version >/dev/null 2>&1; then
    echo "== mypy (strict zone: lightgbm_tpu/utils, tools) =="
    python -m mypy || fail=1
else
    echo "== mypy not installed; skipping (config in pyproject.toml) =="
fi

if [ "$fail" -ne 0 ]; then
    echo "check.sh: lint gate FAILED (fix or baseline with justification)"
    exit 1
fi

if [ "$MODE" = "--lint" ]; then
    echo "check.sh: lint gate clean"
    exit 0
fi

if [ "$MODE" = "--serve" ]; then
    echo "== serve smoke (boot server, /healthz + /predict, shut down) =="
    exec env JAX_PLATFORMS=cpu python helpers/serve_smoke.py
fi

if [ "$MODE" = "--obs" ]; then
    echo "== obs smoke (traced mini-train + serve, validate trace + /metrics) =="
    exec env JAX_PLATFORMS=cpu python helpers/obs_smoke.py
fi

if [ "$MODE" = "--resil" ]; then
    echo "== resil smoke (SIGKILL/resume bit-identity + SIGTERM serve drain) =="
    exec env JAX_PLATFORMS=cpu python helpers/resil_smoke.py
fi

if [ "$MODE" = "--drift" ]; then
    echo "== drift smoke (flight JSONL + PSI separation + HTML report) =="
    exec env JAX_PLATFORMS=cpu python helpers/obs_smoke.py --drift
fi

if [ "$MODE" = "--multichip" ]; then
    echo "== multichip smoke (8 forced CPU devices, sharded-chunk bit-identity) =="
    exec python helpers/multichip_smoke.py
fi

if [ "$MODE" = "--san" ]; then
    echo "== sanitizer unit tests (seeded violations caught, off-path free) =="
    env JAX_PLATFORMS=cpu python -m pytest tests/test_sanitize.py -q \
        -p no:cacheprovider || exit 1
    echo "== graftsan concurrency stress smoke (predict+swap+drain+drift+scrape) =="
    exec env JAX_PLATFORMS=cpu python helpers/san_smoke.py
fi

if [ "$MODE" = "--loop" ]; then
    echo "== loop smoke (drift -> retrain -> validate -> publish -> swap + SIGKILL recovery) =="
    exec env JAX_PLATFORMS=cpu python helpers/loop_smoke.py
fi

if [ "$MODE" = "--tune" ]; then
    echo "== tune smoke (sweep + cache round-trip + perf gate + bit-transparency) =="
    exec env JAX_PLATFORMS=cpu python helpers/tune_smoke.py
fi

if [ "$MODE" = "--elastic" ]; then
    echo "== elastic smoke (SIGKILL/SIGTERM -> resume byte-identity + 8->2 reshard) =="
    exec python helpers/elastic_smoke.py
fi

if [ "$MODE" = "--podwatch" ]; then
    echo "== podwatch smoke (2-proc train + live scrape + straggler verdict) =="
    exec python helpers/podwatch_smoke.py
fi

if [ "$MODE" = "--flex" ]; then
    echo "== flex smoke (capacity storm: shrink/grow drains + mid-chunk SIGKILL under flexctl) =="
    exec python helpers/flex_smoke.py
fi

if [ "$MODE" = "--ir" ]; then
    echo "== irscan smoke (seeded IR violations caught + real-tree scan vs baseline/contract) =="
    exec python helpers/irscan_smoke.py
fi

if [ "$MODE" = "--quick" ]; then
    MARK='quick and not slow'
else
    MARK='not slow'
fi

echo "== pytest (-m \"$MARK\") =="
exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m "$MARK" \
    --continue-on-collection-errors -p no:cacheprovider
