# Developer entry points (the reference ships the same targets).

PYTHON ?= python

.PHONY: test test-fast bench smoke multichip lint lintcheck dev clean faultcheck chaoscheck nosleep perfcheck nofoldin obscheck noperf nostager ledgercheck noartifacts watchcheck costcheck nocost plancheck noknobs kernelcheck nopallas servecheck noserve fusecheck fusionmask sketchcheck nosketchhash veccheck sweepcheck metricscheck topocheck

test:
	$(PYTHON) -m pytest tests/ -q

test-fast:
	$(PYTHON) -m pytest tests/ -q -x

bench:
	$(PYTHON) bench.py

# Both need a TPU: without one they exit nonzero (no CPU fallback).
smoke:
	$(PYTHON) chip_smoke.py

multichip:
	# dryrun_multichip self-bootstraps a virtual 8-device CPU mesh when
	# fewer real devices exist; it owns the platform selection (the env
	# var alone loses to auto-registered TPU plugins).
	$(PYTHON) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

# Fault-injection suite (includes the end-to-end degraded-bench run)
# + the no-direct-sleep invariant + the seeded chaos campaign.
faultcheck: nosleep chaoscheck
	$(PYTHON) -m pytest tests/test_resilience.py tests/test_faults.py \
	  tests/test_chaos.py -q

# Seeded chaos campaign: 20 deterministic episodes across EVERY
# FaultPlan seam (stream/pass-B/sketch kills, device loss with elastic
# mesh re-form, wedged probe on a FakeClock, serve kill with
# exactly-once lease replay, torn ledger + fsck), with per-episode
# recovery invariants. CPU mesh, zero real sleeps — tier-1-safe. Set
# PIPELINEDP_TPU_CHAOS_SEED to replay a specific campaign; a failing
# episode prints its exact reproduction command.
chaoscheck:
	$(PYTHON) -m pipelinedp_tpu.resilience.chaos --schedules 20

# Performance-path acceptance suite: overlapped-ingest bit-parity,
# fault-kill drain (no orphan threads), O(n) assignment, id-narrowing
# tiers, sweep checkpoint/resume, the kill/resume fault tests — plus
# the quantile-walk suite (counter-noise generator, three-way walk
# bit-parity, partition-block chunking, guard-cliff boundaries), the
# pass-B sweep suite (planner invariants, multi-tile-vs-per-tile
# bit-parity, hybrid prefix cache, pass-B fault drain) and the
# sketch-first suite (sketchcheck: the ingest ring's third consumer,
# with its own kill-mid-stream drain proof) and the topology suite
# (topocheck: hier-vs-flat bit-parity + the collective-confinement
# lint).
perfcheck: sketchcheck veccheck sweepcheck topocheck
	$(PYTHON) -m pipelinedp_tpu.lint --rule nosleep --rule nofoldin \
	  --rule nostager --rule nopallas
	$(PYTHON) -m pytest tests/test_ingest.py tests/test_faults.py \
	  tests/test_walk.py tests/test_pass_b.py -q

# Utility-analysis megasweep acceptance suite (ISSUE 18): configs as a
# device axis — walked-vs-batched bit parity at every config-batch
# width incl. non-dividing widths and the 8-device mesh (PARITY row
# 41), kill-mid-megasweep resume from the .sweep sibling checkpoint,
# serve `tune` requests (admitted, quota'd, books-stamped, zero (eps,
# delta) debited, warm second tune compiles nothing), the configs/s
# compare-gate refusal across batch widths — plus the jit-staticness
# lint over the batched kernels: config values (bounds, eps-splits,
# noise tables, knob reads) must arrive as RUNTIME inputs, never
# freeze into the traced program.
sweepcheck:
	$(PYTHON) -m pipelinedp_tpu.lint --rule jit-staticness
	$(PYTHON) -m pytest tests/test_analysis.py tests/test_serve.py \
	  tests/test_ledger.py tests/test_lint.py -q

# Wide-D vector aggregation acceptance suite: the Pallas wide-D
# segment-sum parity matrix (random shapes, max-lane values past f32
# exactness, every d_block bit-identical, envelope geometry + visible
# fallbacks), the fx fixed-point accumulator's bit-identity across
# backends / 8-device mesh / streamed pass-A (PARITY row 39), knob
# precedence (vector_accumulator dp-UNSAFE, segsum_wide_d_block
# dp-safe), device vector noise keyed by (partition, coordinate) with
# distribution + key-determinism checks (PARITY row 40), fusion bucket
# compatibility + vector padding invariance, the VECTOR_SUM elastic
# 8->4 reshard (fx bit-identical where f32 cannot be), and the
# pallas-confinement + rng-purity lints over the new surfaces.
veccheck: nopallas
	$(PYTHON) -m pipelinedp_tpu.lint --rule rng-purity
	$(PYTHON) -m pytest tests/test_vector_fx.py tests/test_kernels.py \
	  tests/test_fusion.py -q
	$(PYTHON) -m pytest tests/test_faults.py -q \
	  -k "vector_sum_survives_mid_stream_shrink"

# Pallas-kernel acceptance suite: kernel-level bit-parity vs the XLA
# scatter paths (including the lane-plan boundary widths in interpret
# mode), the four-way pass-B parity (multi-tile XLA = per-tile =
# unchunked = Pallas, single device + 8-device mesh — in
# tests/test_pass_b.py), out-of-envelope + pallas-unavailable
# fallbacks with their kernel.fallback events, kernel_backend knob
# precedence (env > seam > default), the interpret-mode CPU
# roofline peak row, and the in-tree nopallas AST twin.
kernelcheck: nopallas
	$(PYTHON) -m pytest tests/test_kernels.py tests/test_pass_b.py -q

# Resident-service acceptance suite: durable per-tenant budget
# ledgers (exactly-once debits, overdraw refused before compute,
# kill-and-restart replay), admission control (malformed / queue-full
# / per-tenant in-flight / quota refusals as structured responses,
# graceful drain with zero orphan pdp-serve threads), warm
# engine/program reuse (second same-signature request captures no new
# compile.program span), serve-vs-direct bit-parity (PARITY row 34),
# per-tenant books, the run-namespaced multi-request heartbeat, and
# the per-directory report-cursor regression — plus the request-fusion
# suite (fusecheck).
servecheck: noserve fusecheck
	$(PYTHON) -m pytest tests/test_serve.py tests/test_ledger.py -q

# Request-fusion acceptance suite: fused-vs-solo bit-parity across a
# pow2 bucket boundary (PARITY row 35 — released values AND kept
# sets, budget debits/audit records unchanged), padding invariance of
# the solo kernel (the pad-mask contract), kill-mid-batch lease
# resolution (every fused request resolves exactly once), zero new
# compile.program captures on the second same-bucket batch, quota
# refusals, and heartbeat bucket occupancy — plus the fusion-masking
# confinement lint.
fusecheck: fusionmask
	$(PYTHON) -m pytest tests/test_fusion.py -q

fusionmask:
	$(PYTHON) -m pipelinedp_tpu.lint --rule fusion-masking

# Sketch-first / DP heavy-hitters acceptance suite: seeded stable-hash
# round-trips at collision-prone widths, matmul-vs-scatter sketch
# bit-parity (PARITY row 36), per-user pre-sketch bounding invariance,
# sketch-vs-exact candidate recall on a power-law key space, the
# cap>=universe bit-parity with the dense path (PARITY row 37, single
# device + 8-device mesh), the phase-1 budget audit record, the
# schema-v5 report sketch section, kill-mid-sketch drain (zero orphan
# pdp-* threads) — plus the sketch-confinement lint (hashing +
# candidate tables confined to sketch/, raw hash() banned on keys).
sketchcheck: nosketchhash
	$(PYTHON) -m pytest tests/test_sketch.py -q

nosketchhash:
	$(PYTHON) -m pipelinedp_tpu.lint --rule sketch-confinement

# Topology-aware collectives suite (ISSUE 20): hier-vs-flat release
# bit-parity (single device, 8-device mesh, simulated hosts), the
# sharded-vs-single-device sketch parity, elastic shrink under hier,
# the comms byte counters — plus the collective-confinement lint
# (raw psum/psum_scatter/all_gather confined to parallel/sharded.py,
# the one seam carrying the parity contract and the byte meter).
topocheck:
	$(PYTHON) -m pipelinedp_tpu.lint --rule collective-confinement
	$(PYTHON) -m pytest tests/test_topology.py -q

# Observability acceptance suite: tracer thread-safety under a live
# overlapped-ingest run, no-op-mode zero emission, bench-field parity
# (names/semantics unchanged, DP outputs bit-identical trace on/off),
# Chrome-trace round-trip, run-report schema, resilience/fault event
# coverage — plus the no-raw-perf-counter and no-ad-hoc-artifact lints
# and the metrics-plane suite (metricscheck).
obscheck: metricscheck
	$(PYTHON) -m pipelinedp_tpu.lint --rule noperf --rule noartifacts
	$(PYTHON) -m pytest tests/test_obs.py -q

# Metrics-plane + wire-surface acceptance suite: request-scoped trace
# propagation across the serve thread handoffs (fused batches included,
# concurrent tenants isolated), histogram bucket-boundary exactness,
# the Prometheus exposition round-trip through a LIVE /metrics scrape,
# endpoint lifecycle (off-by-default zero threads, clean drain under
# ServeKill), and the trace-context on/off DP bit-parity — plus the
# socket-confinement lint (wire machinery confined to obs/http.py).
metricscheck:
	$(PYTHON) -m pipelinedp_tpu.lint --rule socket-confinement
	$(PYTHON) -m pytest tests/test_metrics.py -q

# Audit-record + run-ledger acceptance suite: schema-v2 privacy section
# (per-mechanism eps/delta + noise stddevs, selection pre/post counts,
# expected errors), audit on/off DP bit-parity, durable store semantics
# (fsync'd appends, v1->v2 reader tolerance, truncated-trailing-line
# recovery, concurrent appends, degraded-baseline exclusion) and the
# bench --compare regression gate (two in-process runs).
ledgercheck: noartifacts
	$(PYTHON) -m pytest tests/test_ledger.py tests/test_obs.py -q

# Live-telemetry acceptance suite: heartbeat atomic-replace under a
# concurrent reader, progress/pace fields, stall watchdog firing at
# the exact FakeClock deadline on a wedged staged fetch (zero orphan
# threads on drain), flight-record ring + thread-stack round-trip,
# heartbeat on/off DP bit-parity, the --summarize ledger analytics
# CLI, and the wedged-probe watchdog-cancel path.
watchcheck:
	$(PYTHON) -m pipelinedp_tpu.lint --rule noperf --rule nosleep
	$(PYTHON) -m pytest tests/test_monitor.py tests/test_obs.py -q

# Device-cost observatory acceptance suite: roofline verdict math,
# instrumented_jit capture-once semantics (the compile-count
# assertion), analysis tolerance across jax versions, HBM watermark
# sampling, store schema tolerance v1->v2->v3 (last_known_good /
# --summarize / bench --compare on a mixed-schema ledger), --csv
# output, Chrome-trace counter tracks, the e2e device_costs report
# shape, and the costs on/off DP bit-parity (PARITY row 31, in
# tests/test_obs.py) — plus the no-direct-analysis-call lint.
costcheck: nocost
	$(PYTHON) -m pytest tests/test_costs.py tests/test_obs.py -q

# Knob-registry suite: cold-start byte-identity to the hardcoded
# defaults, env > seam > default precedence for every registered knob,
# unknown enumerated values falling back to the default, a stale plan
# file on disk steering nothing, the pass-B q_chunk pin, the store's
# --since-run-id window — plus the no-direct-knob-read lint.
plancheck: noknobs
	$(PYTHON) -m pytest tests/test_plan.py -q

# ---------------------------------------------------------------------
# Static analysis: ONE AST rule engine (pipelinedp_tpu/lint/) replaced
# the former grep forest. Every legacy target below is now a thin
# alias over `python -m pipelinedp_tpu.lint --rule <id>`; `lintcheck`
# runs the full registry (9 ported rules + rng-purity,
# blocking-under-lock, jit-staticness). Findings are `file:line
# rule-id message`; deliberate exceptions are inline
# `# lint: disable=<rule>(reason)` suppressions, counted and reported.
# See README "Static analysis" for the rule table.
# ---------------------------------------------------------------------

lintcheck:
	$(PYTHON) -m pipelinedp_tpu.lint

noserve:
	$(PYTHON) -m pipelinedp_tpu.lint --rule noserve

nopallas:
	$(PYTHON) -m pipelinedp_tpu.lint --rule nopallas

noknobs:
	$(PYTHON) -m pipelinedp_tpu.lint --rule noknobs

nocost:
	$(PYTHON) -m pipelinedp_tpu.lint --rule nocost

noartifacts:
	$(PYTHON) -m pipelinedp_tpu.lint --rule noartifacts

noperf:
	$(PYTHON) -m pipelinedp_tpu.lint --rule noperf

nofoldin:
	$(PYTHON) -m pipelinedp_tpu.lint --rule nofoldin

nostager:
	$(PYTHON) -m pipelinedp_tpu.lint --rule nostager

nosleep:
	$(PYTHON) -m pipelinedp_tpu.lint --rule nosleep

lint: lintcheck
	@if $(PYTHON) -c "import pyflakes" 2>/dev/null; then \
	  $(PYTHON) -m pyflakes pipelinedp_tpu tests; \
	else \
	  $(PYTHON) -m py_compile $$(git ls-files '*.py'); \
	fi

dev:
	$(PYTHON) -m pip install -e . --no-deps --no-build-isolation

clean:
	rm -rf build *.egg-info .pytest_cache
	find . -name __pycache__ -type d -exec rm -rf {} +
	rm -f pipelinedp_tpu/native/_secure_noise.so pipelinedp_tpu/native/_encode.so
