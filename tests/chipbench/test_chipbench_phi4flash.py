"""The configuration ``phi4_mini_flash_3_8b`` and what it brings to the
benchmark: its file against the published sizes and its plain model, its
parameter and operation counts against hand counts, its readers on what a
traced run hands them, the scan's roofline count at a hand-counted shape, and
a CPU rehearsal of a tiny configuration of its kind through the harness
(control flow only, never a number)."""

import importlib
import json
import os
from types import SimpleNamespace

import pytest

from chipbench import flops, run, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = os.path.join(HERE, "phi4flash_manifest.json")
CELL = "phi4_mini_flash_3_8b.single_build"
TINY = "phi4flash_tiny.rehearsal"

NEW_READERS = {
    "ssm_scan_share", "ssm_proj_share", "diff_attention_share", "gmu_share", "ssm_scan_roofline",
}
# BENCHMARK.json's entries that read every cell of the fleet build, the new
# one appended; not ``optimizer_share``: here XLA fuses the update into the
# weight gradients' product fusions, which take the products' scopes
# (PERF.md section 6)
SHARED_READERS = {
    "fetch_ms_per_machine", "window_compiles", "fleet_step_ms_per_machine", "fleet_step_mfu",
    "persist_ms_per_machine", "device_idle_share", "build_tail_ms_per_machine",
    "setup_trace_s", "setup_lower_s", "setup_backend_s", "pull_gbps",
}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "chipbench", "configs", "phi4_mini_flash_3_8b.json")) as fh:
        return json.load(fh)


# the catalog's config of Phi-4-mini-flash-reasoning, every key
CATALOG = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240,
    "layer_norm_eps": 1e-05, "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40, "num_hidden_layers": 32,
    "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
    "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False, "vocab_size": 200064,
}


def test_file_holds_the_published_sizes(config):
    reduced = {"num_hidden_layers": 6, "vocab_size": 0}
    for key, value in CATALOG.items():
        assert config["published"][key] == value, key
        assert config[key] == reduced.get(key, value), key
    assert set(config["reduced"]) == set(reduced) | {"epochs"} == set(config["reduced_how"])
    # the model block runs the layers held at the published widths: none is cut
    model, held = config["model"], config["layers_held"]
    assert held == model["layers_held"] == list(range(14, 20))
    assert len(held) == config["num_hidden_layers"]
    assert model["num_hidden_layers"] == CATALOG["num_hidden_layers"]
    assert (model["d_model"], model["ff_dim"]) == (CATALOG["hidden_size"], CATALOG["intermediate_size"])
    assert (model["num_heads"], model["num_kv_heads"]) == (40, 20)
    assert model["head_dim"] * model["num_heads"] == CATALOG["hidden_size"]
    assert (model["sliding_window"], model["mb_per_layer"]) == (512, 2)
    assert model["norm_eps"] == CATALOG["layer_norm_eps"]
    assert (model["d_state"], model["d_conv"], model["expand"], model["dt_rank"]) == (16, 4, 2, 160)
    assert model["kind"] == "sambay_model" and config["reference"] == "sambay"
    assert (model["lookback_window"], model["batch_size"], model["compute_dtype"]) == (256, 16, "bfloat16")
    # the rate is in the file twice: what the program reads, what the reference follows
    assert model["optimizer_kwargs"]["learning_rate"] == config["optimizer"]["learning_rate"]
    assert "pipeline stages" in config["deployment"]
    # every size the config does not give, and every departure, has its line
    assert {"mamba_sizes", "mamba_init", "biases", "memory", "differential_attention",
            "lambda_init", "cross_attention", "init", "precision", "learning_rate"} <= set(config["assumed"])


def test_the_plain_model_is_the_published_layout(config):
    from chipbench.configs import sambay

    assert [entry[:3] for entry in sambay.layout(config["model"])] == [
        ("mamba", 0, ""), ("attention", 512, ""), ("mamba", 0, "memory"),
        ("attention", 0, "kv"), ("gmu", 0, ""), ("cross_attention", 0, ""),
    ]


def test_parameter_count_is_the_cut_by_hand(config):
    import jax

    from chipbench.configs import sambay

    shapes = jax.eval_shape(
        lambda key: sambay.init_params(key, config["model"], config["n_tags"]),
        jax.random.PRNGKey(0),
    )
    sizes = [sum(int(a.size) for a in layer.values()) for layer in shapes]
    d, ff, di = 2560, 10240, 5120
    block = 4 * d + 3 * d * ff  # two LayerNorms, the MLP
    mixer = d * 2 * di + di * 5 + di * 192 + 161 * di + di * 16 + di + di * d
    attention = 2 * d * 2560 + 2 * d * 1280 + 4 * 64 + 128
    cross = 2 * d * 2560 + 4 * 64 + 128
    gmu = 2 * d * di
    assert mixer == 41_241_600
    assert sizes[1:7] == [block + mixer, block + attention, block + mixer, block + attention,
                          block + gmu, block + cross]
    assert sizes[1:7] == [119_895_040, 98_314_624, 119_895_040, 98_314_624, 104_867_840, 91_761_024]
    assert sizes[0] == 8 * d + d and sizes[-3] == 2 * d and sizes[-1] == d * 8 + 8
    assert sum(sizes) == 633_096_840


def test_forward_flops_by_hand(config):
    """The work the model requires a window: every position up to layer 17,
    layers 18 and 19 at the last position (the head reads nothing else of
    them; cross-attention's last query against every key)."""
    t, d, ff, di = 256, 2560, 10240, 5120
    mlp = 6 * d * ff
    mamba = 2 * (d * 2 * di + di * 192 + 160 * di + di * d) + mlp
    pair_key = 20 * 2 * (2 * 64 + 2 * 128)  # two maps a query pair
    attention = 2 * d * (2 * 2560 + 2 * 1280) + mlp
    causal = 0.5 * t * t
    gmu = 2 * 2 * d * di + mlp
    cross = 2 * d * 2 * 2560 + mlp
    hand = (2 * 8 * d * t + 2 * mamba * t + 2 * attention * t + 2 * pair_key * causal
            + gmu + cross + pair_key * t + 2 * d * 8)
    assert flops.forward_flops_per_window(config) == pytest.approx(hand, rel=1e-12)
    assert hand / 1e9 == pytest.approx(224.719, abs=0.001)
    # a build of 1,152 rows: 5,679 forward passes (three folds, the final fit, the folds' predictions)
    build = flops.build_flops_per_machine(config, traffic.Traffic.load("single_build").rows)
    assert build == pytest.approx(5679 * hand, rel=1e-12)


def test_the_scans_roofline_count_at_a_small_shape():
    """``ssm_scan_bytes`` at d_inner 4, d_state 2, three positions in one
    scan: u (2), Δ (4) a channel, B and C (2) a state, a position, A and D (4)
    once, read twice with their gradients written once (3x), y and dy (2 a
    channel a position)."""
    from chipbench.configs import sambay

    model = {"d_model": 2, "expand": 2, "d_state": 2, "ff_dim": 1, "num_heads": 2,
             "num_kv_heads": 2, "head_dim": 1, "d_conv": 4, "dt_rank": 1, "norm_eps": 1e-5}
    nbytes = sambay.ssm_scan_bytes(model, 3)
    inputs = 3 * (2 * 4 + 4 * 4 + 2 * 2 + 2 * 2) + 4 * (4 * 2 + 4)
    assert nbytes == 3 * inputs + 2 * 3 * 2 * 4
    # two scans of the same positions count A and D twice
    assert sambay.ssm_scan_bytes(model, 3, 2) == nbytes + 3 * 4 * (4 * 2 + 4)


def test_the_cell_is_in_the_manifest_with_its_files():
    cell = run.load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    assert cell["chips"] == 1 and cell["traffic"].chunk_machines == 1
    assert cell["traffic"].rows == 1152 and cell["traffic"].check_machines == 1
    assert {m["name"] for m in cell["per_layer"]} == NEW_READERS | SHARED_READERS
    assert {m["name"] for m in cell["end_to_end"]} == {
        "machines_per_min", "machine_ready_p95_s", "setup_s"
    }
    assert set(cell["file"]["limits"]) and cell["file"]["trace"]["detail_seconds"] > 0
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        accepted = json.load(fh)
    assert sum(w["chips"] == 4 for w in accepted["workloads"]) == 0
    # the five entries of this configuration come last, on this cell alone
    assert [m["name"] for m in accepted["per_layer"][-5:]] == [
        "ssm_scan_share", "ssm_proj_share", "diff_attention_share", "gmu_share", "ssm_scan_roofline"
    ]
    for metric in accepted["per_layer"]:
        listed = CELL in metric["workloads"]
        assert listed == (metric["name"] in NEW_READERS | SHARED_READERS), metric["name"]
        if metric["name"] in NEW_READERS:
            assert metric["workloads"] == [CELL] and metric["source"] == "device_trace"
            assert metric["moves"] == "machines_per_min" and metric["unit"] == "%"
            importlib.import_module(f"chipbench.metrics.{metric['name']}")
        if metric["name"] in SHARED_READERS:
            assert metric["workloads"][-1] == CELL
    # the tests' manifest holds the tiny twin alone, with BENCHMARK.json's
    # entries on the twin
    with open(MANIFEST) as fh:
        own = json.load(fh)
    assert [c["name"] for c in own["configs"]] == ["phi4flash_tiny"]
    assert [w["name"] for w in own["workloads"]] == [TINY]
    by_name = {m["name"]: m for m in accepted["per_layer"] + accepted["end_to_end"]}
    for metric in own["end_to_end"] + own["per_layer"]:
        assert metric["workloads"] == [TINY]
        rest = {k: v for k, v in metric.items() if k != "workloads"}
        assert rest == {k: v for k, v in by_name[metric["name"]].items() if k != "workloads"}
    listed = {m["name"] for m in run.load_cell(MANIFEST, TINY)["per_layer"]}
    assert listed == NEW_READERS | SHARED_READERS


def _ctx(scope_s, counters=None, step_s=10.0, kind="TPU v5 lite", config=None):
    counters = counters or {}
    cell = {"config": config or {"model": {}}, "traffic": SimpleNamespace(chunk_machines=1)}
    trace = SimpleNamespace(
        detail_s=sum(scope_s.values()), scope_s=scope_s, op_s={}, op_scope={},
        module_s={"jit_one_machine": step_s}, busy_s=step_s, n_devices=1,
        program_seconds=lambda name: step_s,
    )
    return {
        "cell": cell, "device_kind": kind, "machines": 1,
        "before": {k: 0.0 for k in counters}, "after": counters, "trace": trace,
    }


def _read(name, ctx):
    return importlib.import_module(f"chipbench.metrics.{name}").read(ctx)


@pytest.mark.parametrize("name, scopes", [
    ("ssm_scan_share", ("ssm_scan",)),
    ("ssm_proj_share", ("ssm_in", "ssm_out")),
    ("diff_attention_share", ("diff_attention", "...qd,...kd->...qk", "...qk,...kd->...qd")),
    ("gmu_share", ("gmu",)),
])
def test_share_readers_read_their_scopes(name, scopes):
    scope_s = {
        "": 2.0, "ssm_scan": 1.5, "ssm_in": 1.0, "ssm_out": 0.5, "diff_attention": 0.75,
        "...qd,...kd->...qk": 0.25, "...qk,...kd->...qd": 0.25, "gmu": 0.5,
        "optimizer_update": 1.25,
    }
    total = sum(scope_s.values())
    assert _read(name, _ctx(scope_s)) == pytest.approx(100 * sum(scope_s[s] for s in scopes) / total)
    # a program that lacks the scopes (the parent commit, a stale compile cache)
    assert _read(name, _ctx({"": 3.0, "attention": 1.0, "...qd,...kd->...qk": 1.0})) is None
    # and a run with no detail cut at all
    assert _read(name, _ctx({})) is None


def test_the_scan_roofline_reads_the_window_against_the_count(config):
    """The window's scan seconds (the step's device seconds times the cut's
    share under ``ssm_scan``) against the least time for the positions the
    counters saw."""
    from chipbench.configs import sambay

    counters = {
        "gordo_build_ssm_positions_total": 240 * 4096.0,
        "gordo_build_ssm_layer_steps_total": 240.0,
    }
    scope_s = {"": 3.0, "ssm_scan": 1.0}  # a quarter of the cut
    ctx = _ctx(scope_s, counters, step_s=20.0, config=config)
    least = sambay.ssm_scan_bytes(config["model"], 240 * 4096.0, 240.0) / 819e9
    got = _read("ssm_scan_roofline", ctx)
    assert got == pytest.approx(100 * least / (20.0 * 0.25))
    assert 0 < got <= 100
    # nothing to read: no scope in the cut, no scan counted, a host's run
    assert _read("ssm_scan_roofline", _ctx({"": 1.0}, counters, config=config)) is None
    assert _read("ssm_scan_roofline", _ctx(scope_s, {}, config=config)) is None
    assert _read("ssm_scan_roofline", _ctx(scope_s, counters, kind="cpu", config=config)) is None


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_a_tiny_configuration_of_the_kind(capsys, trace):
    from gordo_tpu.observability import metrics as catalog

    steps = catalog.SSM_LAYER_STEPS.value()
    positions = catalog.SSM_POSITIONS.value()
    kv = catalog.SHARED_STATE_READS.value(kind="kv")
    memory = catalog.SHARED_STATE_READS.value(kind="memory")
    code = run.main([
        "--workload", TINY, "--seed", str(4_300_000_000 + trace),
        "--seconds", "0.3", "--trace", str(trace), "--rehearsal", "--manifest", MANIFEST,
    ])
    out = capsys.readouterr()
    assert code == 0
    line = json.loads(out.out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    # two Mamba layers, one cross-attention block and one gated memory unit a live step
    steps = catalog.SSM_LAYER_STEPS.value() - steps
    assert steps > 0 and steps % 2 == 0
    assert catalog.SSM_POSITIONS.value() - positions == steps / 2 * 2 * 16 * 32
    assert catalog.SHARED_STATE_READS.value(kind="kv") - kv == steps / 2
    assert catalog.SHARED_STATE_READS.value(kind="memory") - memory == steps / 2
    if trace:
        # a host's trace names no scope: the new metrics are left out
        assert not NEW_READERS & set(line["metrics"])
        assert "fleet_step_ms_per_machine" in line["metrics"]


def test_the_plain_model_trims_the_cross_decoder_to_the_last_position():
    """The plain model computes layers 18 and 19 at the last position alone:
    the whole model's last position, in value and in every leaf's gradient,
    and the earlier positions still reach it through the memory and the keys
    and values."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import reference
    from chipbench.configs import sambay

    with open(os.path.join(ROOT, "chipbench", "rehearsal", "phi4flash_tiny.json")) as fh:
        model = json.load(fh)["model"]
    params = sambay.init_params(jax.random.PRNGKey(2), model, 4)
    x = jax.random.normal(jax.random.PRNGKey(3), (3, 32, 4))
    mm = reference.matmul("float32")
    s, layers = sambay.sizes(model), sambay.layout(model)

    def whole(params, x):
        h = mm(x, params[0]["kernel"]) + params[0]["bias"]
        shared = {}
        for spec, p in zip(layers, params[1:-3]):
            h, kept = sambay._block(s, spec, p, h, shared, mm)
            shared.update(kept)
        h = sambay._layer_norm(h[:, -1], params[-3]["scale"], params[-3]["bias"], s["eps"])
        return mm(h, params[-1]["kernel"]) + params[-1]["bias"]

    def trimmed(params, x):
        return sambay.forward(model, params, x, mm)

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(trimmed(params, x), whole(params, x), rtol=1e-5, atol=1e-6)
        grads = [
            jax.grad(lambda p, x, f=f: jnp.sum(jnp.sin(f(p, x))), argnums=(0, 1))(params, x)
            for f in (whole, trimmed)
        ]
    for a, b in zip(jax.tree_util.tree_leaves(grads[0]), jax.tree_util.tree_leaves(grads[1])):
        np.testing.assert_allclose(b, a, rtol=1e-4, atol=1e-6 * (1 + np.abs(a).max()))
    assert np.abs(np.asarray(grads[1][1])[:, :-1]).max() > 0


def test_the_plain_models_weights_are_drawn_in_one_program():
    """One compiled program a (model, tags) draws every leaf, strongly typed
    float32 (a weakly typed leaf compiles the reference's step a second time)."""
    import jax
    import jax.numpy as jnp

    from chipbench.configs import sambay

    with open(os.path.join(ROOT, "chipbench", "rehearsal", "phi4flash_tiny.json")) as fh:
        model = json.load(fh)["model"]
    first = sambay.init_params(jax.random.PRNGKey(0), model, 4)
    fn = sambay._init_fn(json.dumps(model, sort_keys=True), 4)
    compiled = fn._cache_size()
    sambay.init_params(jax.random.PRNGKey(1), dict(model), 4)
    assert fn._cache_size() == compiled >= 1
    for leaf in jax.tree_util.tree_leaves(first):
        assert leaf.dtype == jnp.float32 and not leaf.weak_type
