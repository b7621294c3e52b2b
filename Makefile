# CI entry points. `make verify` is the tier-1 gate (ROADMAP.md).
PY := PYTHONPATH=src python

# Perf gate files: OLD/SERVE_OLD are the committed baselines; NEW/SERVE_NEW
# are what `bench-scan` / `bench-serve` write (env overrides in
# benchmarks/run.py keep the baselines untouched). To refresh a committed
# baseline instead: `make bench-scan NEW=BENCH_scan.json` /
# `make bench-serve SERVE_NEW=BENCH_serve.json`.
OLD ?= BENCH_scan.json
NEW ?= BENCH_scan.new.json
SERVE_OLD ?= BENCH_serve.json
SERVE_NEW ?= BENCH_serve.new.json
TRAIN_OLD ?= BENCH_train.json
TRAIN_NEW ?= BENCH_train.new.json
# bench-smoke scratch outputs (gitignored experiments/): structure-checked,
# never compared against the committed baselines
SMOKE_SCAN ?= experiments/smoke_scan.json
SMOKE_SERVE ?= experiments/smoke_serve.json
SMOKE_TRAIN ?= experiments/smoke_train.json

# obs-smoke scratch traces (gitignored experiments/): Chrome trace-event
# JSON from tiny traced serve + train launcher runs
OBS_SERVE_TRACE ?= experiments/obs_serve_trace.json
OBS_TRAIN_TRACE ?= experiments/obs_train_trace.json

# seed for the chaos lane's randomized-but-seeded FaultPlan (verify-faults);
# bump it (or set it per-run) to explore a different fault schedule — the
# same value always replays the same faults
FAULT_CHAOS_SEED ?= 0

.PHONY: verify verify-fast verify-faults ci bench-scan bench-serve \
	bench-serve-open bench-train bench-compare \
	bench-smoke bench-accept obs-smoke docs-check quickstart

verify:
	$(PY) -m pytest -x -q

# the CI lane: skip tests marked `slow` (fig2-grid sweeps, serve-engine
# round-trips — see pytest.ini); `make verify` stays the full local default
verify-fast:
	$(PY) -m pytest -q -m "not slow"

# chaos lane: the fault-injection suite (deterministic plans + the seeded
# random plan in test_chaos_seeded_no_hangs_no_garbage). Fast by design —
# the slow kill/restore round-trips stay in `make verify`.
verify-faults:
	FAULT_CHAOS_SEED=$(FAULT_CHAOS_SEED) \
		$(PY) -m pytest -q -m "not slow" tests/test_faults.py

# one-shot CI bundle (what .github/workflows/ci.yml runs): fast tier-1 lane,
# chaos lane, a bounded bench smoke whose JSON structure
# — never its timings — is checked, and the observability smoke (traced
# tiny serve+train runs, trace structure validated)
ci: verify-fast verify-faults bench-smoke obs-smoke docs-check

# regenerate the scan-schedule matrix into $(NEW)
bench-scan:
	BENCH_SCAN_JSON=$(NEW) $(PY) -m benchmarks.run fig2

# regenerate every serving row — closed-loop padded-vs-packed, the
# open-loop v1-vs-v2 scheduler rows, AND the prefix-cache / speculative
# rows — into one $(SERVE_NEW)
bench-serve:
	BENCH_SERVE_JSON=$(SERVE_NEW) \
		$(PY) -m benchmarks.run serve serve_open serve_cached

# open-loop (Poisson-arrival) rows only: v1 vs v2 scheduler at matched
# offered load -> $(SERVE_NEW). Faster iteration on scheduler policy; use
# `make bench-serve` before accepting a new committed baseline.
bench-serve-open:
	BENCH_SERVE_JSON=$(SERVE_NEW) $(PY) -m benchmarks.run serve_open

# regenerate the gated training rows (single vs pad vs pack x f32/bf16
# full train steps) -> $(TRAIN_NEW)
bench-train:
	BENCH_TRAIN_JSON=$(TRAIN_NEW) $(PY) -m benchmarks.run train

# gate on the perf trajectories: one invocation, every offender across both
# files in one report; exits nonzero on >10% regressions. The scan pair is
# REQUIRED (a missing regeneration fails the gate); the serve pair is
# skipped if a side wasn't regenerated.
bench-compare:
	$(PY) benchmarks/compare.py --pair $(OLD) $(NEW) \
		--optional-pair $(SERVE_OLD) $(SERVE_NEW) \
		--optional-pair $(TRAIN_OLD) $(TRAIN_NEW)

# promote freshly-written staging files ($(NEW)/$(SERVE_NEW)) over the
# committed baselines and delete them — prints the delta table first, but
# accepting is the operator's call so regressions never fail this target
bench-accept:
	$(PY) benchmarks/compare.py --pair $(OLD) $(NEW) \
		--optional-pair $(SERVE_OLD) $(SERVE_NEW) \
		--optional-pair $(TRAIN_OLD) $(TRAIN_NEW) --accept

# tiny-shape benchmark pass for CI: exercises fig2 + serve end to end and
# validates the emitted JSON structure; timings are NOT gated (CI machines
# are noisy)
bench-smoke:
	mkdir -p experiments
	BENCH_SMOKE=1 BENCH_SCAN_JSON=$(SMOKE_SCAN) \
		BENCH_SERVE_JSON=$(SMOKE_SERVE) BENCH_TRAIN_JSON=$(SMOKE_TRAIN) \
		$(PY) -m benchmarks.run fig2 serve serve_open serve_cached train
	$(PY) benchmarks/compare.py --schema $(SMOKE_SCAN) $(SMOKE_SERVE) \
		$(SMOKE_TRAIN)

# observability smoke: tiny traced serve + train runs through the REAL
# launchers (--obs-trace), then structural validation of the emitted Chrome
# trace-event JSON — parseable, B/E span nesting balanced per track,
# required metrics present — via the repro.obs.check CLI. The train run
# needs --seq-len 2048: the synthetic corpus draws sequences up to ~2k and
# the packing loader rejects capacities below the longest draw.
obs-smoke:
	mkdir -p experiments
	$(PY) -m repro.launch.serve --tiny --slots 4 --requests 8 \
		--new-tokens 6 --max-len 64 --obs-trace $(OBS_SERVE_TRACE)
	$(PY) -m repro.launch.train --tiny --rows 2 --seq-len 2048 --steps 4 \
		--obs-trace $(OBS_TRAIN_TRACE)
	$(PY) -m repro.obs.check $(OBS_SERVE_TRACE) \
		--require serve.prefills --require serve.generated \
		--require serve.decode_steps
	$(PY) -m repro.obs.check $(OBS_TRAIN_TRACE) --allow-zero \
		--require train.steps --require train.real_tokens \
		--require data.prefetch_hits

# docs stay honest: the README bench table must match the committed
# BENCH_*.json exactly (regenerate with `make docs-check WRITE=--write`),
# and every repo path referenced from README.md / docs/*.md must exist
WRITE ?=
docs-check:
	$(PY) benchmarks/docs_check.py $(WRITE)

quickstart:
	$(PY) examples/quickstart.py
