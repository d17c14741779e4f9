# Build/push targets for the four deploy images (reference shape: Makefile).
# Image names match what the Argo workflow template pulls
# (argo-workflow.yml.template: gordo-tpu-{builder,server,client,deploy}).
REGISTRY ?= localhost:5000
TAG ?= $(shell git rev-parse --short HEAD)

IMAGES = builder server client deploy

DOCKERFILE_builder = Dockerfile-ModelBuilder
DOCKERFILE_server  = Dockerfile-ModelServer
DOCKERFILE_client  = Dockerfile-Client
DOCKERFILE_deploy  = Dockerfile-Deploy

# NB: image-%/push-% pattern targets must NOT be .PHONY — GNU make skips
# implicit-rule search for .PHONY targets
.PHONY: all test test-sanitize lint bench bench-summary bench-cold-start bench-hetero bench-sharded bench-streaming bench-precision bench-slo bench-gameday bench-attribution build-multiworker images push

all: lint test

test:
	python -m pytest tests/ -q

# the gordo_tpu.analysis static/JAX-discipline checker; exit code is the
# finding count, so a dirty tree fails the target (docs/static_analysis.md)
lint:
	python -m gordo_tpu.cli lint gordo_tpu tests benchmarks

# tier-1 under the runtime lock-order sanitizer: the threading
# constructors are instrumented for the whole run, the observed lock
# graph dumps to lock_graph_report.json, and `gordo-tpu lockgraph`
# renders it — exit code == ordering inversions, so a new inversion
# anywhere in the suite fails the target (docs/static_analysis.md)
test-sanitize:
	GORDO_LOCK_SANITIZE=1 GORDO_LOCK_SANITIZE_REPORT=lock_graph_report.json \
		python -m pytest tests/ -q -m 'not slow'
	python -m gordo_tpu.cli lockgraph lock_graph_report.json

bench:
	python bench.py

# fold every ad-hoc results_*.json into one benchmarks/trajectory.json
# (bench name, revision, headline metric, knob settings) — the autotuner
# corpus reader ingests it (docs/tuning.md)
bench-summary:
	python benchmarks/consolidate.py

# time-to-first-prediction for a freshly exec'd server, cold trace vs
# the build-time AOT executable cache (docs/performance.md)
bench-cold-start:
	python benchmarks/cold_start.py --machines 6 --model lstm --repeats 2

bench-hetero:
	python benchmarks/hetero_fleet.py --output benchmarks/results_hetero_cpu_r10.json

# sharded serving plane (docs/serving.md): open-loop goodput + p99 at
# 1/2/4 replicas behind the router, plus goodput retained across a
# mid-run replica kill
# NB: the whole plane shares one Python process (and one CPU) here, so
# offered load must sit below single-process capacity — past it the
# arms melt into queueing collapse, which measures the box, not the
# router. On real hardware each replica is its own process/host.
bench-sharded:
	python benchmarks/load_test.py --self-serve --open-loop --fleet 6 \
		--replicas 1,2,4 --rps 4 --duration 15 --kill-replica-at 5 \
		--output benchmarks/results_sharded_cpu_r11.json

# streaming scoring plane (docs/serving.md "Streaming scoring"):
# per-update p50/p99 and sustained updates/s at N concurrent streams,
# mixed with the existing open-loop one-shot POST load — the one-shot
# arm's p99 is what device-resident windows beat
bench-streaming:
	python benchmarks/stream_load.py --streams 1,4,16 --duration 10 \
		--update-rows 5 --window-rows 256 --mixed-rps 2 \
		--output benchmarks/results_stream_cpu_r12.json

# per-machine mixed precision + transfer pipelining + donation arms
# (docs/performance.md "Mixed precision, buffer donation, and transfer
# pipelining"): bf16-vs-float32 build/dispatch arms with per-machine
# MAE deltas, prefetch-depth overlap ratios, and the donate on/off
# output-delta evidence
bench-precision:
	python benchmarks/fleet_throughput.py --machines 8 --epochs 3 \
		--sequential-sample 2 \
		--precision-sweep float32,bf16 --prefetch-sweep 0,2 \
		--donation-arms > benchmarks/results_precision_cpu_r15.json

# SLO-gated serving bench (docs/observability.md "Plane rollup and
# control signals"): the open-loop load test evaluated against the
# example error-budget spec — the result JSON (and trajectory.json via
# bench-summary) carries pass/fail + per-objective burn rates, and the
# target's exit code is the gate
bench-slo:
	python benchmarks/load_test.py --self-serve --open-loop --fleet 6 \
		--rps 4 --duration 15 --slo examples/slo_serving.yaml \
		--output benchmarks/results_load_test_slo_cpu_r16.json
	python benchmarks/consolidate.py
	python -c "import json,sys; slo=json.load(open('benchmarks/results_load_test_slo_cpu_r16.json')).get('slo') or {}; print('SLO', slo.get('spec'), 'ok' if slo.get('ok') else 'BUDGET EXHAUSTED', 'max_burn=%.2fx' % (slo.get('max_burn_rate') or 0)); sys.exit(0 if slo.get('ok') else 1)"

# the full game-day catalogue (docs/robustness.md "Game days"): six
# composed-failure scenarios with fault timelines and SLO budgets run
# against an in-process plane; exit code = number of failed scenarios,
# and bench-summary folds the per-scenario verdicts into trajectory.json
bench-gameday:
	python benchmarks/gameday.py \
		--output benchmarks/results_gameday_cpu_r19.json
	python benchmarks/consolidate.py

# phase-ledger time attribution (docs/observability.md "Time
# attribution"): drives a real server with the wall profiler sampling
# in-process and reports per-request ledger coverage, the host/device
# split, per-bracket overhead, and the sampled cost-seam ranking;
# bench-summary folds host_fraction into trajectory.json
bench-attribution:
	python benchmarks/attribution.py --duration 8 \
		--output benchmarks/results_attribution_cpu_r20.json
	python benchmarks/consolidate.py

# 2-worker crash-tolerant ledger build of the example fleet config
# (docs/robustness.md "Multi-worker builds") — the smoke proof that N
# worker processes coordinate through the shared-volume ledger. On the
# CPU, explicitly: N local workers cannot share one chip, and
# build-fleet refuses --workers N>1 anywhere else
build-multiworker:
	JAX_PLATFORMS=cpu \
	MACHINES="$$(cat examples/machines_fleet.yaml)" \
	OUTPUT_DIR=$${OUTPUT_DIR:-/tmp/gordo-tpu-multiworker} \
	python -m gordo_tpu.cli build-fleet --workers 2 --lease-ttl 15

images: $(addprefix image-,$(IMAGES))

image-%:
	docker build -f $(DOCKERFILE_$*) -t $(REGISTRY)/gordo-tpu-$*:$(TAG) .

push: $(addprefix push-,$(IMAGES))

push-%: image-%
	docker push $(REGISTRY)/gordo-tpu-$*:$(TAG)
