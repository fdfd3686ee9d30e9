"""
Tier-1 lint gates.

- No bare ``except:`` in gordo_tpu/ (scripts/lint_bare_except.py): a bare
  except launders every exception — including KeyboardInterrupt and
  SystemExit — into one code path, which defeats the transient-vs-permanent
  classification the fault-domain layer (util/faults.py) depends on.
- Every registered metric carries a ``gordo_`` prefix and non-empty help
  text (scripts/lint_metric_names.py): metric names are a public API for
  dashboards and alerts; help strings are the operator docs at /metrics.
- Every ``GORDO_TPU_*`` env var read in gordo_tpu/ is documented under
  docs/ or README.md (scripts/lint_env_knobs.py): the knob count has
  outgrown anyone's memory, and an undocumented knob is undiscoverable
  at exactly the moment an operator needs it.
- Every ``gordo_*`` metric a generated Grafana dashboard plots exists in
  a metrics catalog (lint_metric_names.py --dashboards): a panel keyed
  on a renamed metric renders empty silently. Plus a tiny-budget fleet
  scrape smoke holding the merged /metrics exposition to the same
  naming bar.
- Every shipped-programs artifact manifest conforms to the build-to-serve
  contract (scripts/lint_artifact_manifest.py): known schema, complete
  host-fingerprint block, well-formed entries, no missing or orphaned
  ``.jaxprog`` files — a drifted manifest fails silently at cold-node
  boot, downgrading to the compile path.
"""

import json
import pathlib
import subprocess
import sys

REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]
LINT = REPO_ROOT / "scripts" / "lint_bare_except.py"
METRIC_LINT = REPO_ROOT / "scripts" / "lint_metric_names.py"
KNOB_LINT = REPO_ROOT / "scripts" / "lint_env_knobs.py"
MANIFEST_LINT = REPO_ROOT / "scripts" / "lint_artifact_manifest.py"
SCENARIO_LINT = REPO_ROOT / "scripts" / "lint_chaos_scenario.py"


def test_no_bare_except_in_gordo_tpu():
    result = subprocess.run(
        [sys.executable, str(LINT), "gordo_tpu"],
        cwd=str(REPO_ROOT),
        capture_output=True,
        text=True,
    )
    assert result.returncode == 0, (
        f"bare 'except:' introduced:\n{result.stdout}{result.stderr}"
    )


def test_lint_flags_bare_except(tmp_path):
    bad = tmp_path / "offender.py"
    bad.write_text(
        "try:\n    pass\nexcept:\n    pass\n"
    )
    result = subprocess.run(
        [sys.executable, str(LINT), str(tmp_path)],
        cwd=str(REPO_ROOT),
        capture_output=True,
        text=True,
    )
    assert result.returncode == 1
    assert "offender.py:3" in result.stdout


def test_lint_accepts_typed_except(tmp_path):
    ok = tmp_path / "fine.py"
    ok.write_text(
        "try:\n    pass\nexcept Exception:\n    pass\n"
        "try:\n    pass\nexcept (ValueError, KeyError) as exc:\n    raise\n"
    )
    result = subprocess.run(
        [sys.executable, str(LINT), str(tmp_path)],
        cwd=str(REPO_ROOT),
        capture_output=True,
        text=True,
    )
    assert result.returncode == 0, result.stdout


# ------------------------------------------------------ metric-name lint
def _run_metric_lint(root):
    return subprocess.run(
        [sys.executable, str(METRIC_LINT), str(root)],
        cwd=str(REPO_ROOT),
        capture_output=True,
        text=True,
    )


def test_no_bad_metric_names_in_gordo_tpu():
    result = _run_metric_lint("gordo_tpu")
    assert result.returncode == 0, (
        f"bad metric registration introduced:\n{result.stdout}{result.stderr}"
    )


def test_metric_lint_flags_missing_prefix_and_help(tmp_path):
    bad = tmp_path / "offender.py"
    bad.write_text(
        "from prometheus_client import Counter, Histogram\n"
        'c = Counter("requests_total", "has help but no prefix")\n'
        'h = Histogram("gordo_good_name_seconds", "")\n'
        "from gordo_tpu.observability import telemetry\n"
        'g = telemetry.gauge("gordo_no_help_at_all")\n'
    )
    result = _run_metric_lint(tmp_path)
    assert result.returncode == 1
    assert "offender.py:2" in result.stdout and "prefix" in result.stdout
    assert "offender.py:3" in result.stdout and "help" in result.stdout
    assert "offender.py:5" in result.stdout


def test_metric_lint_accepts_prefixed_documented_metrics(tmp_path):
    ok = tmp_path / "fine.py"
    ok.write_text(
        "from prometheus_client import Counter\n"
        'c = Counter("gordo_things_total", "things that happened", ["kind"])\n'
        "from gordo_tpu.observability import telemetry\n"
        'h = telemetry.histogram(\n'
        '    name="gordo_thing_seconds", help="how long things took"\n'
        ")\n"
        "# variable names are unlintable and skipped (registry internals)\n"
        "name = 'dynamic'\n"
        "import collections\n"
        "counts = collections.Counter([1, 2, 2])\n"
    )
    result = _run_metric_lint(tmp_path)
    assert result.returncode == 0, result.stdout


# ------------------------------------------------------- env-knob lint
def _run_knob_lint(*args):
    return subprocess.run(
        [sys.executable, str(KNOB_LINT), *map(str, args)],
        cwd=str(REPO_ROOT),
        capture_output=True,
        text=True,
    )


def test_every_env_knob_in_gordo_tpu_is_documented():
    result = _run_knob_lint()  # defaults: gordo_tpu vs docs/ + README.md
    assert result.returncode == 0, (
        f"undocumented GORDO_TPU_* knob introduced:\n"
        f"{result.stdout}{result.stderr}"
    )


def test_knob_lint_flags_undocumented_knob(tmp_path):
    src = tmp_path / "src"
    docs = tmp_path / "docs"
    src.mkdir(), docs.mkdir()
    (src / "mod.py").write_text(
        'import os\n'
        'a = os.environ.get("GORDO_TPU_DOCUMENTED_KNOB")\n'
        'b = os.environ.get("GORDO_TPU_SECRET_KNOB")\n'
        '# constructed prefixes are skipped, expansions must be named:\n'
        'c = os.environ.get(f"GORDO_TPU_DYNAMIC_{a}")\n'
    )
    (docs / "page.md").write_text(
        "| `GORDO_TPU_DOCUMENTED_KNOB` | does things |\n"
    )
    result = _run_knob_lint(src, docs)
    assert result.returncode == 1
    assert "GORDO_TPU_SECRET_KNOB" in result.stdout
    assert "GORDO_TPU_DOCUMENTED_KNOB" not in result.stdout
    assert "GORDO_TPU_DYNAMIC_" not in result.stdout


def test_knob_lint_accepts_fully_documented_tree(tmp_path):
    src = tmp_path / "src"
    docs = tmp_path / "docs"
    src.mkdir(), docs.mkdir()
    (src / "mod.py").write_text(
        'import os\nx = os.environ.get("GORDO_TPU_FINE_KNOB")\n'
    )
    (docs / "page.md").write_text("`GORDO_TPU_FINE_KNOB` turns it on\n")
    result = _run_knob_lint(src, docs)
    assert result.returncode == 0, result.stdout


def test_metric_lint_flags_unbounded_label_cardinality(tmp_path):
    bad = tmp_path / "offender.py"
    bad.write_text(
        "from gordo_tpu.observability import telemetry\n"
        '# a bounded identity label (model names) is fine\n'
        'ok = telemetry.counter(\n'
        '    "gordo_fine_total", "per-model events", ("model",)\n'
        ")\n"
        '# per-request identity is a cardinality bomb\n'
        'bad = telemetry.counter(\n'
        '    "gordo_bomb_total", "per-trace events", ("trace_id",)\n'
        ")\n"
    )
    result = _run_metric_lint(tmp_path)
    assert result.returncode == 1
    assert "trace_id" in result.stdout and "unbounded" in result.stdout
    assert "gordo_fine_total" not in result.stdout


def test_metric_lint_catalog_coverage(tmp_path):
    """--catalog: every catalog metric must appear in a doc or dashboard."""
    catalog = tmp_path / "metrics.py"
    catalog.write_text(
        "from gordo_tpu.observability import telemetry\n"
        'a = telemetry.counter("gordo_plotted_total", "shown somewhere")\n'
        'b = telemetry.counter("gordo_orphan_total", "shown nowhere")\n'
    )
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "page.md").write_text("`gordo_plotted_total` counts things\n")
    result = subprocess.run(
        [
            sys.executable, str(METRIC_LINT), str(tmp_path),
            "--catalog", str(catalog), "--refs", str(docs),
        ],
        cwd=str(REPO_ROOT),
        capture_output=True,
        text=True,
    )
    assert result.returncode == 1
    assert "gordo_orphan_total" in result.stdout
    assert "gordo_plotted_total" not in result.stdout


def test_metric_lint_default_invocation_checks_real_catalog():
    """The bare invocation (what tier-1 runs) includes catalog coverage
    of observability/metrics.py against docs + dashboards AND the reverse
    dashboard-grounding check over resources/grafana/dashboards."""
    result = subprocess.run(
        [sys.executable, str(METRIC_LINT)],
        cwd=str(REPO_ROOT),
        capture_output=True,
        text=True,
    )
    assert result.returncode == 0, (
        f"metric catalog drifted from docs/dashboards:\n"
        f"{result.stdout}{result.stderr}"
    )


# ------------------------------------------------ dashboard grounding
def _dashboard_fixture(tmp_path, exprs):
    """A minimal dashboard JSON + a catalog registering two metrics."""
    catalog = tmp_path / "catalog.py"
    catalog.write_text(
        "from gordo_tpu.observability import telemetry\n"
        'a = telemetry.counter("gordo_real_total", "a real counter")\n'
        'b = telemetry.histogram("gordo_real_seconds", "a real histogram")\n'
    )
    dashboards = tmp_path / "dashboards"
    dashboards.mkdir()
    (dashboards / "dash.json").write_text(json.dumps({
        "panels": [
            {"targets": [{"expr": expr} for expr in exprs]},
        ],
    }))
    return dashboards, catalog


def _run_dashboard_lint(tmp_path, dashboards, catalog):
    # an explicit (empty-of-offenders) root keeps the default-tree catalog
    # checks out of the way; only the dashboard grounding is under test
    return subprocess.run(
        [
            sys.executable, str(METRIC_LINT), str(tmp_path / "dashboards"),
            "--dashboards", str(dashboards),
            "--dashboard-catalogs", str(catalog),
        ],
        cwd=str(REPO_ROOT),
        capture_output=True,
        text=True,
    )


def test_dashboard_lint_flags_uncataloged_metric(tmp_path):
    dashboards, catalog = _dashboard_fixture(tmp_path, [
        'rate(gordo_real_total[5m])',
        'sum(rate(gordo_ghost_total[5m]))',  # nothing registers this
    ])
    result = _run_dashboard_lint(tmp_path, dashboards, catalog)
    assert result.returncode == 1
    assert "gordo_ghost_total" in result.stdout
    assert "render empty" in result.stdout
    assert "gordo_real_total" not in result.stdout


def test_dashboard_lint_accepts_cataloged_and_label_positions(tmp_path):
    dashboards, catalog = _dashboard_fixture(tmp_path, [
        # histogram suffixes resolve to the base family; gordo_*-shaped
        # tokens in label positions (selector bodies, by-clauses) are
        # labels, not metric references
        'histogram_quantile(0.99, sum by (le, gordo_name) '
        '(rate(gordo_real_seconds_bucket{gordo_name="m"}[5m])))',
        'sum(gordo_real_total{gordo_project=~"$project"})',
    ])
    result = _run_dashboard_lint(tmp_path, dashboards, catalog)
    assert result.returncode == 0, result.stdout + result.stderr


def test_dashboard_lint_grounds_gateway_family(tmp_path):
    """The gateway dashboard's ``gordo_gateway_*`` exprs are grounded by
    the real catalog — and the reverse check is non-vacuous: against a
    catalog without the gateway registrations, every panel is flagged."""
    dashboards = tmp_path / "dashboards"
    dashboards.mkdir()
    source = (
        REPO_ROOT / "resources" / "grafana" / "dashboards"
        / "gordo_tpu_gateway.json"
    )
    (dashboards / "gordo_tpu_gateway.json").write_text(source.read_text())

    real_catalog = REPO_ROOT / "gordo_tpu" / "observability" / "metrics.py"
    result = _run_dashboard_lint(tmp_path, dashboards, real_catalog)
    assert result.returncode == 0, result.stdout + result.stderr

    gateway_free = tmp_path / "catalog.py"
    gateway_free.write_text(
        "from gordo_tpu.observability import telemetry\n"
        'a = telemetry.counter("gordo_real_total", "a real counter")\n'
    )
    result = _run_dashboard_lint(tmp_path, dashboards, gateway_free)
    assert result.returncode == 1
    assert "gordo_gateway_requests_total" in result.stdout
    assert "gordo_gateway_proxy_seconds" in result.stdout


# ------------------------------------------------ exemplar discipline
def _run_exemplar_lint(tmp_path, exposition_text):
    exposition = tmp_path / "metrics.txt"
    exposition.write_text(exposition_text)
    empty_root = tmp_path / "empty"
    empty_root.mkdir(exist_ok=True)
    # an explicit empty root keeps the default-tree checks out of the way;
    # only the exemplar discipline is under test
    return subprocess.run(
        [
            sys.executable, str(METRIC_LINT), str(empty_root),
            "--exposition", str(exposition),
        ],
        cwd=str(REPO_ROOT),
        capture_output=True,
        text=True,
    )


def test_exemplar_lint_accepts_real_renderer_output(tmp_path):
    """The telemetry renderer's own exemplar exposition is the reference:
    trace_id-only labels, bucket lines only, under the per-family cap."""
    from gordo_tpu.observability import telemetry, tracing

    registry = telemetry.MetricsRegistry()
    hist = registry.histogram(
        "gordo_exemplar_demo_seconds", "demo", buckets=(0.01, 0.1, 1.0)
    )
    for value in (0.005, 0.05, 0.5):
        with tracing.request_root():
            hist.observe(value)
    text = registry.render_text()
    assert " # {" in text, "renderer stopped emitting exemplars"
    result = _run_exemplar_lint(tmp_path, text)
    assert result.returncode == 0, result.stdout + result.stderr


def test_exemplar_lint_flags_foreign_labels(tmp_path):
    result = _run_exemplar_lint(
        tmp_path,
        'gordo_x_seconds_bucket{le="1"} 3 # {trace_id="a",user="bob"} '
        "0.5 1.0\n"
        'gordo_x_seconds_bucket{le="2"} 3 # {span_id="a"} 0.5 1.0\n',
    )
    assert result.returncode == 1
    assert "'user'" in result.stdout
    assert "'span_id'" in result.stdout
    assert "only ['trace_id']" in result.stdout


def test_exemplar_lint_flags_non_bucket_and_cap(tmp_path):
    over_cap = "\n".join(
        f'gordo_x_seconds_bucket{{le="{i}"}} 1 # {{trace_id="t{i}"}} 0.5 1.0'
        for i in range(17)
    )
    result = _run_exemplar_lint(
        tmp_path,
        'gordo_x_seconds_sum 1.2 # {trace_id="a"} 0.5 1.0\n' + over_cap,
    )
    assert result.returncode == 1
    assert "non-bucket sample 'gordo_x_seconds_sum'" in result.stdout
    assert "exposes 17 exemplars (cap 16)" in result.stdout


# -------------------------------------------- artifact-manifest lint
def _run_manifest_lint(*args):
    return subprocess.run(
        [sys.executable, str(MANIFEST_LINT), *map(str, args)],
        cwd=str(REPO_ROOT),
        capture_output=True,
        text=True,
    )


def _manifest_fixture(tmp_path, mutate=None):
    """A minimal valid artifact with a shipped-programs manifest; `mutate`
    edits the manifest dict (and may touch the dir) before writing."""
    programs_dir = tmp_path / "artifact" / "programs"
    programs_dir.mkdir(parents=True)
    fname = "abc123def456-n128-b1-c8.jaxprog"
    (programs_dir / fname).write_bytes(b"\x80\x04N.")
    manifest = {
        "schema_version": 1,
        "fingerprint": "c94e61e4dfe1",
        "platform": "cpu",
        "machine": "x86_64",
        "cpu_features": ["avx2", "fma"],
        "jaxlib": "0.4.37",
        "programs": [
            {
                "file": fname,
                "spec_key": "abc123def456",
                "n_pad": 128,
                "b_pad": 1,
                "capacity": 8,
                "x_shape": [1, 128, 4],
                "dtype": "float32",
                "compile_s": 0.25,
            }
        ],
    }
    if mutate:
        mutate(manifest, programs_dir)
    (programs_dir / "manifest.json").write_text(json.dumps(manifest))
    return tmp_path / "artifact"


def test_manifest_lint_default_invocation_passes():
    """The bare invocation (what tier-1 runs): build outputs are not
    checked in, so the repo-root scan finds nothing and passes — and a
    future round that DOES commit an artifact gets it linted for free."""
    result = _run_manifest_lint()
    assert result.returncode == 0, result.stdout + result.stderr


def test_manifest_lint_accepts_valid_artifact(tmp_path):
    artifact = _manifest_fixture(tmp_path)
    result = _run_manifest_lint(artifact)
    assert result.returncode == 0, result.stdout + result.stderr
    assert "1 artifact manifest(s) valid" in result.stdout


def test_manifest_lint_flags_missing_fingerprint_and_schema(tmp_path):
    def mutate(manifest, programs_dir):
        manifest["fingerprint"] = ""
        manifest["schema_version"] = 99

    artifact = _manifest_fixture(tmp_path, mutate)
    result = _run_manifest_lint(artifact)
    assert result.returncode == 1
    assert "fingerprint" in result.stdout
    assert "schema_version" in result.stdout


def test_manifest_lint_flags_missing_and_orphaned_files(tmp_path):
    def mutate(manifest, programs_dir):
        # indexed but absent on disk
        manifest["programs"].append(
            {**manifest["programs"][0], "file": "ghost-n128-b4-c8.jaxprog"}
        )
        # on disk but unindexed
        (programs_dir / "orphan-n1024-b1-c8.jaxprog").write_bytes(b"x")

    artifact = _manifest_fixture(tmp_path, mutate)
    result = _run_manifest_lint(artifact)
    assert result.returncode == 1
    assert "ghost-n128-b4-c8.jaxprog" in result.stdout
    assert "does not exist" in result.stdout
    assert "orphan-n1024-b1-c8.jaxprog" in result.stdout
    assert "orphaned" in result.stdout


def test_manifest_lint_real_shipped_artifact_passes(tmp_path, monkeypatch):
    """Ground truth: a manifest written by the REAL build-side shipper
    passes the lint — the lint and programs.ship_programs can't drift
    apart without this failing."""
    pytest = __import__("pytest")
    np = __import__("numpy")
    from gordo_tpu.serializer import programs as programs_mod

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")

    class _Estimator:
        pass

    import jax.numpy as jnp

    from gordo_tpu.models.models import AutoEncoder

    spec = AutoEncoder(kind="feedforward_hourglass").build_spec(4, 4)
    from gordo_tpu.ops.nn import init_model_params

    estimator = _Estimator()
    estimator.spec_ = spec
    estimator.params_ = init_model_params(
        __import__("jax").random.PRNGKey(0), spec
    )
    artifact = tmp_path / "artifact"
    artifact.mkdir()
    (artifact / "metadata.json").write_text(json.dumps({
        "dataset": {"tags": ["a", "b", "c", "d"]},
        "metadata": {"build_metadata": {"model": {"model_offset": 0}}},
    }))
    shipped = programs_mod.ship_programs(
        estimator, str(artifact), expected_fleet=1,
        bucket_rows=(128,), fuse_widths=(1,),
    )
    assert shipped == 1
    result = _run_manifest_lint(artifact)
    assert result.returncode == 0, result.stdout + result.stderr


def test_fleet_scrape_smoke(tmp_path, monkeypatch):
    """Tiny-budget fleet-scrape smoke: flush this process's shard, render
    the merged exposition (the exact bytes a no-prometheus /metrics
    serves), and hold every exposed family to the lint's naming bar."""
    import re

    from gordo_tpu.observability import shared, telemetry

    monkeypatch.setenv(shared.ENV_DIR, str(tmp_path))
    shared.reset_for_tests()
    try:
        telemetry.counter(
            "gordo_server_lint_smoke_total", "scrape-smoke probe"
        ).inc()
        text = shared.render_fleet_text()
        assert "gordo_server_fleet_workers 1" in text
        assert "gordo_server_lint_smoke_total 1" in text
        for line in text.splitlines():
            if not line or line.startswith("#"):
                continue
            name = re.split(r"[{\s]", line, maxsplit=1)[0]
            assert name.startswith("gordo_"), line
    finally:
        shared.reset_for_tests()


# ---------------------------------------------------- chaos-scenario lint
def _run_scenario_lint(*paths):
    return subprocess.run(
        [sys.executable, str(SCENARIO_LINT), *map(str, paths)],
        cwd=str(REPO_ROOT),
        capture_output=True,
        text=True,
    )


def test_chaos_scenario_lint_committed_scenarios_pass():
    """The bare invocation (what tier-1 runs): every scenario under
    resources/chaos/ parses against the conductor's live vocabulary."""
    result = _run_scenario_lint()
    assert result.returncode == 0, result.stdout + result.stderr


def test_chaos_scenario_lint_flags_bad_vocabulary(tmp_path):
    bad = tmp_path / "bad_action.yaml"
    bad.write_text(
        "name: bad\n"
        "load:\n  phases:\n    - {shape: flat, qps: 5, duration: 2}\n"
        "timeline:\n  - {at: 1.0, action: reboot_node, node: 0}\n"
        "invariants:\n  - {check: availability, min: 0.9}\n"
    )
    result = _run_scenario_lint(bad)
    assert result.returncode == 1
    assert "reboot_node" in result.stdout

    bad_site = tmp_path / "bad_site.yaml"
    bad_site.write_text(
        "name: bad-site\n"
        "fault_plan:\n  rules:\n    - {site: not_a_site, error: transient}\n"
        "invariants:\n  - {check: availability}\n"
    )
    result = _run_scenario_lint(bad_site)
    assert result.returncode == 1
    assert "not_a_site" in result.stdout


def test_chaos_scenario_lint_flags_structural_problems(tmp_path):
    # no invariants = asserts nothing; late action = never fires
    empty = tmp_path / "no_invariants.yaml"
    empty.write_text(
        "name: hollow\n"
        "load:\n  phases:\n    - {shape: flat, qps: 5, duration: 2}\n"
    )
    late = tmp_path / "late_action.yaml"
    late.write_text(
        "name: late\n"
        "load:\n  phases:\n    - {shape: flat, qps: 5, duration: 2}\n"
        "timeline:\n  - {at: 99.0, action: kill_node, node: 0}\n"
        "invariants:\n  - {check: availability}\n"
    )
    result = _run_scenario_lint(empty, late)
    assert result.returncode == 1
    assert "no invariants" in result.stdout
    assert "fires after the load ends" in result.stdout


def test_chaos_scenario_lint_caps_horizon(tmp_path):
    slow = tmp_path / "marathon.yaml"
    slow.write_text(
        "name: marathon\n"
        "load:\n  phases:\n    - {shape: flat, qps: 5, duration: 600}\n"
        "invariants:\n  - {check: availability}\n"
    )
    result = _run_scenario_lint(slow)
    assert result.returncode == 1
    assert "exceeds" in result.stdout
