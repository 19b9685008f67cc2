# Dev targets (reference: Makefile style/quality; upgraded to ruff).
.PHONY: test test-fast test-shard1 test-shard2 test-shard3 test-multihost fleet-drill lint typecheck quality style chip-smoke acceptance-network sanitize-drill

TEST_ENV = JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8

# Full suite (learning gates, multihost, kernels): nightly / pre-release.
# Exceeds a 10-min single-command budget — use the three shards below for
# full-suite green within per-command limits (timings: README "Testing").
test:
	$(TEST_ENV) python -m pytest tests/ -q

# Fast tier: per-commit CI signal, < ~4 min on CPU. Includes the resilience
# suite (tests/test_resilience.py — fault drills, guard/watchdog/checkpoint
# hardening): single-process CPU drills, so nothing there needs a slow mark.
test-fast:
	$(TEST_ENV) python -m pytest tests/ -q -m "not slow"

# Full-suite green in three bounded commands: shard1 = fast tier + kernel/
# generate slow tests; shard2 = e2e learning gates; shard3 = mesh/multihost/
# scale. Every test runs in exactly one shard.
test-shard1:
	$(TEST_ENV) python -m pytest tests/ -q -m "not slow" \
	    && $(TEST_ENV) python -m pytest -q -m slow \
	        tests/test_flash.py tests/test_ring_attention.py tests/test_generate.py \
	        tests/test_weight_quant.py tests/test_hf_stream.py

test-shard2:
	$(TEST_ENV) python -m pytest -q -m slow \
	    tests/test_e2e.py tests/test_text_mode.py tests/test_softprompt.py \
	    tests/test_fused_rollout.py

test-shard3:
	$(TEST_ENV) python -m pytest -q -m slow \
	    tests/test_mesh.py tests/test_multihost.py tests/test_scale_compile.py

# 2-process distributed drills: boundary-helper/train-resume semantics plus
# the fault drills (host_hang → CollectiveTimeout, coordinated preemption
# save/resume, host_desync → fingerprint guard), and the disaggregated
# rollout/learner fleet drills (rollout_host_kill → degraded drain,
# broadcast_timeout → starved-worker abort, episode_stream_stall → STALLED
# triage, 2-process staleness-0 parity; RUNBOOK §16). Non-blocking CI job —
# jax.distributed on shared runners can be flaky; see RUNBOOK §3b for the
# local drill command and the triage table.
test-multihost:
	$(TEST_ENV) python -m pytest -q -m slow \
	    tests/test_multihost.py tests/test_distributed_resilience.py \
	    tests/test_fleet_drill.py tests/test_fleet_disagg.py \
	    tests/test_fleet_elastic.py

# 2-process fleet drills under the full runtime sanitizer set: graftfleet's
# slow_host drill (merged clock-aligned trace, skew table naming the
# laggard, live fleet gauges) and hang drill (cross-host incident bundle),
# plus the disaggregated rollout/learner drills (host kill + preemption +
# resume, broadcast timeout, stream stall, 2-process parity; RUNBOOK §16),
# plus the in-flight weight-update drills (torn push rejection, switch-storm
# coalescing, 2-process engine schedule verify + skew, mid-decode host kill
# with slot-state forensics, staleness-0 bitwise parity; RUNBOOK §17).
# Set TRLX_TPU_DRILL_ARTIFACTS=<dir> to keep the merged trace, report
# section, episode-stream index, broadcast log and fleet event log (the CI
# job uploads them). Non-blocking CI job — jax.distributed caveats apply to
# test_fleet_drill.py only (the disagg drills spawn independent
# single-controller worlds); RUNBOOK §14/§16 have the triage.
fleet-drill:
	$(TEST_ENV) TRLX_TPU_SANITIZE=dispatch,donation,race python -m pytest -q \
	    -m slow tests/test_fleet_drill.py tests/test_fleet_disagg.py \
	    tests/test_fleet_elastic.py

# graftlint + graftrace: AST invariant (GL001-GL007, RUNBOOK §11) and
# concurrency (GL008-GL011, RUNBOOK §13) checks in one pass. Blocking,
# < 30 s, stdlib only — the analysis package must never import jax (pinned
# by tests/test_analysis.py), so this runs on CPU-only CI images as-is.
# Second pass: the top-level scripts, under the rule families that apply
# outside the package (no dispatch-lock/trace-purity surface there).
SCRIPT_LINT_RULES = GL003,GL004,GL007,GL008,GL009,GL010,GL011
lint:
	python -m trlx_tpu.analysis trlx_tpu/
	python -m trlx_tpu.analysis --select $(SCRIPT_LINT_RULES) \
	    chip_smoke.py acceptance_network.py bench_flash.py bench_kda.py bench_moe.py

# graftrace runtime half, fully armed: the thread-heavy suites (resilience
# fault drills, overlap pipeline, rollout engine) under
# TRLX_TPU_SANITIZE=dispatch,donation,race so lock-discipline, donation, and
# lockset (Eraser) violations raise instead of deadlocking. Non-blocking CI
# job; RUNBOOK §13 has the triage table for RaceViolation reports.
sanitize-drill:
	$(TEST_ENV) TRLX_TPU_SANITIZE=dispatch,donation,race python -m pytest -q \
	    -m "not slow" tests/test_resilience.py tests/test_overlap.py \
	    tests/test_engine.py tests/test_sanitize.py

# Non-blocking type pass over the typed subset (analysis + engine). Degrades
# to a notice when mypy isn't installed — nothing at runtime needs it, and
# the container must not pip install.
typecheck:
	@if python -c "import mypy" 2>/dev/null; then \
	    python -m mypy --ignore-missing-imports --follow-imports=silent \
	        trlx_tpu/analysis/ trlx_tpu/engine/; \
	else \
	    echo "mypy not installed; skipping typecheck (advisory only)"; \
	fi

quality:
	ruff check trlx_tpu/ tests/ examples/

style:
	ruff format trlx_tpu/ tests/ examples/

# The quickest proof that the PPO main path still starts on the chip: every
# Pallas kernel compiled/run/compared at the GPT-J-6B shapes, then two PPO
# iterations through trlx_tpu.train at the flagship widths. One process per
# chip; exits non-zero without a TPU (`--rehearsal` is the tiny CPU run,
# `--devices 4` drives a four-chip host). ~5 min cold on a v5e.
chip-smoke:
	python chip_smoke.py

# Network-day acceptance: the four reference acceptance examples + gates in
# one command, distilled to ACCEPTANCE.json (RUNBOOK.md). Offline it still
# runs end-to-end with every test skipped — that's the smoke path CI covers.
acceptance-network:
	TRLX_TPU_NETWORK=1 python acceptance_network.py
