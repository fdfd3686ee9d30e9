# gordo-tpu build/test targets (reference parity: Makefile:1-40, collapsed
# to the one image the TPU workflow actually uses)

IMG_NAME ?= gordo-tpu
DOCKER_REGISTRY ?= ghcr.io/gordo-tpu
VERSION ?= $(shell python -c "import gordo_tpu; print(gordo_tpu.__version__)" 2>/dev/null || echo dev)

# the single image every workflow pod runs (template {{ image }})
image:
	docker build . -f Dockerfile -t $(IMG_NAME):$(VERSION)

push: image
	docker tag $(IMG_NAME):$(VERSION) $(DOCKER_REGISTRY)/$(IMG_NAME):$(VERSION)
	docker push $(DOCKER_REGISTRY)/$(IMG_NAME):$(VERSION)

# full suite on the 8-virtual-device CPU mesh (how CI runs; conftest.py
# forces JAX_PLATFORMS=cpu + xla_force_host_platform_device_count=8)
test:
	python -m pytest tests/ -q

# multichip sharding compile check on eight virtual CPU devices (the same
# entry runs unchanged on real chips: it uses the devices present and fails
# if there are fewer than asked for)
dryrun:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

# the quickest proof that the system starts on a TPU (fails without one)
chip-smoke:
	python chip_smoke.py

# render the example config through the real CLI and schema-validate the
# resulting Workflow docs — the no-cluster equivalent of `argo lint`
smoke:
	python -m gordo_tpu.cli workflow generate \
		--machine-config examples/config.yaml --project-name smoke-test \
		--client-start-date 2019-01-01T00:00:00Z \
		--client-end-date 2019-01-02T00:00:00Z \
		| python -m gordo_tpu.cli workflow validate -

# every Jinja branch of the workflow template rendered + linted; run after
# ANY edit under gordo_tpu/workflow/resources/ (round-4 postmortem: a
# template edit shipped unrendered and killed `workflow generate`)
render-gate:
	python -m pytest tests/gordo_tpu/test_workflow_template_render.py -q

# the chip benchmark's control flow on the CPU, at a tiny size: never a
# number (BENCHMARK.json + chipbench/ are the yardstick and need the chip:
# chipbench/README.md)
chipbench-rehearsal:
	JAX_PLATFORMS=cpu python3 -m chipbench.run --rehearsal \
		--manifest chipbench/rehearsal/manifest.json \
		--workload lstm_tiny.rehearsal --seed 1 --seconds 1 --trace 1

# metric <-> dashboard consistency, both directions: every catalog metric
# is plotted/documented somewhere, and every gordo_* name a dashboard
# panel queries exists in a metrics catalog (also runs in tier-1)
lint-dashboards:
	python scripts/lint_metric_names.py

# vocabulary + structure check on every committed chaos scenario
lint-chaos-scenarios:
	python scripts/lint_chaos_scenario.py

# one real chaos drill against a live 3-node stack: kill a node mid-ramp,
# assert the availability floor, failover bound, exact histogram merge and
# that the failover is visible as a hedge-arm span in one stitched trace
# (see docs/robustness.md "Chaos conductor"); tier-1 runs the same drill
# (scaled down) plus these invariants via tests/gordo_tpu/test_chaos_conductor.py
chaos-smoke:
	JAX_PLATFORMS=cpu python -m gordo_tpu.cli.cli chaos run \
		resources/chaos/kill_node_mid_ramp.yaml

# burst-profile a live event-loop server through its own debug surface
# and assert the capture contains the event-loop frames (see
# docs/observability.md "Profiling a live server")
profile-smoke:
	JAX_PLATFORMS=cpu python scripts/profile_smoke.py

.PHONY: image push test dryrun chip-smoke smoke render-gate chipbench-rehearsal \
	lint-dashboards lint-chaos-scenarios chaos-smoke profile-smoke
