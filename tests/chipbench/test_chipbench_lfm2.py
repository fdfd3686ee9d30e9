"""The configuration ``lfm2_8b_a1b`` and what it brings to the benchmark: its
file against the published sizes, its operation count against a hand count,
its readers on what a traced run hands them, and a CPU rehearsal of a tiny
configuration of its kind through the harness (control flow only, never a
number)."""

import importlib
import json
import os
from types import SimpleNamespace

import pytest

from chipbench import flops, run, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = os.path.join(HERE, "lfm2_manifest.json")
CELL = "lfm2_8b_a1b.single_build"


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "chipbench", "configs", "lfm2_8b_a1b.json")) as fh:
        return json.load(fh)


def test_file_holds_the_published_sizes(config):
    published = {
        "conv_L_cache": 3, "hidden_size": 2048, "intermediate_size": 7168,
        "max_position_embeddings": 128000, "moe_intermediate_size": 1792,
        "norm_eps": 1e-05, "num_attention_heads": 32, "num_dense_layers": 2,
        "num_experts_per_tok": 4, "num_key_value_heads": 8, "rope_theta": 1000000,
        "routed_scaling_factor": 1,
    }
    reduced = {"num_hidden_layers": (24, 5), "num_experts": (32, 8), "vocab_size": (65536, 0)}
    for key, value in published.items():
        assert config[key] == value and config["published"][key] == value, key
    for key, (was, held) in reduced.items():
        assert config["published"][key] == was and config[key] == held, key
    assert set(config["reduced"]) == set(reduced) | {"epochs"} == set(config["reduced_how"])
    assert len(config["layer_types"]) == 24 == len(config["published"]["layer_types"])
    # the model block runs the layers held, at those widths
    model, held = config["model"], config["layers_held"]
    kinds = {"conv": "conv", "full_attention": "attention"}
    assert model["operators"] == [kinds[config["layer_types"][i]] for i in held]
    assert model["ffns"] == [
        "dense" if i < config["num_dense_layers"] else "routed" for i in held
    ]
    assert len(held) == config["num_hidden_layers"]
    assert (model["d_model"], model["ff_dim"], model["expert_dim"]) == (2048, 7168, 1792)
    assert (model["num_heads"], model["num_kv_heads"], model["head_dim"]) == (32, 8, 64)
    assert (model["num_experts"], model["top_k"], model["experts_held"]) == (32, 4, 8)
    assert (model["conv_kernel"], model["rope_theta"], model["norm_eps"]) == (3, 1e6, 1e-5)
    # the rate is in the file twice: what the program reads, what the reference follows
    assert model["optimizer_kwargs"]["learning_rate"] == config["optimizer"]["learning_rate"]
    assert "four chips" in config["deployment"]


def test_parameter_count_is_the_cut_the_issue_states(config):
    import jax

    from chipbench.configs import lfm2_moe

    shapes = jax.eval_shape(
        lambda key: lfm2_moe.init_params(key, config["model"], config["n_tags"]),
        jax.random.PRNGKey(0),
    )
    sizes = [sum(int(a.size) for a in layer.values()) for layer in shapes]
    assert sizes[1:6] == [60_827_648, 98_635_936, 104_933_408, 104_933_408, 104_933_408]
    assert sum(sizes) == 474_300_680


def test_forward_flops_by_hand(config):
    t, d = 256, 2048
    conv = 2 * d * 3 * d * t + 2 * d * d * t
    attention = 2 * d * (2 * 32 + 2 * 8) * 64 * t + 2 * t * t * 32 * 64  # causal half of 4·T²·H·Dh
    dense = 3 * 2 * d * 7168 * t
    routed = 2 * d * 32 * t + 1.0 * 3 * 2 * d * 1792 * t  # 4 x 8 / 32 = 1 assignment a token
    hand = 2 * 8 * d * t + (conv + dense) + (attention + routed) + 3 * (conv + routed) + 2 * d * 8
    assert flops.forward_flops_per_window(config) == hand
    assert (conv + dense) / 1e9 == pytest.approx(31.14, abs=0.005)
    assert (attention + routed) / 1e9 == pytest.approx(11.31, abs=0.005)
    assert (conv + routed) / 1e9 == pytest.approx(14.26, abs=0.005)
    assert hand / 1e9 == pytest.approx(85.24, abs=0.005)
    # a build of 1,152 rows: three folds and the final fit, and the folds' predictions
    build = flops.build_flops_per_machine(config, traffic.Traffic.load("single_build").rows)
    # 3 x (33 + 321 + 609 + 897) training windows + 3 x 33 predicted = 5,679 forward passes
    assert build == 5679 * hand and build / 1e15 == pytest.approx(0.4841, abs=0.00005)
    # the experts at the load a run's counters read, not the even router's
    from chipbench.configs import lfm2_moe

    quarter = hand - 4 * 0.75 * 3 * 2 * d * 1792 * t
    assert lfm2_moe.forward_flops_per_window(config, held_load=0.25) == quarter
    assert lfm2_moe.forward_flops_per_window(config, held_load=1.0) == hand
    assert quarter / 1e9 == pytest.approx(68.33, abs=0.005)


READERS = {
    "moe_expert_share", "moe_route_share", "gated_conv_share", "optimizer_share",
    "moe_held_load", "moe_load_imbalance", "attention_fwd_roofline", "fleet_step_mfu_routed",
}


def test_the_cell_is_in_the_manifest_with_its_files():
    cell = run.load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    assert cell["chips"] == 1 and cell["traffic"].chunk_machines == 1
    assert cell["traffic"].rows == 1152 and cell["traffic"].check_machines == 1
    listed = {m["name"] for m in cell["per_layer"]}
    assert {"fleet_step_ms_per_machine", "fleet_step_mfu", "device_idle_share",
            "persist_ms_per_machine", "build_tail_ms_per_machine"} <= listed
    assert "fetch_wait_ms_per_machine" not in listed  # one chunk a build: nothing to read
    assert {m["name"] for m in cell["end_to_end"]} == {
        "machines_per_min", "machine_ready_p95_s", "setup_s"
    }
    # ``BENCHMARK.json`` cannot take the readers: a PR that is not a
    # ``benchmark`` PR appends to ``per_layer``, and the accepted
    # ``test_chipbench_fetch_wait_metric.py`` holds ``fetch_wait_ms_per_machine``
    # to the list's end. So the tests' manifest declares them, for the cell and
    # for its tiny twin: ``--manifest tests/chipbench/lfm2_manifest.json`` reads
    # them on the chip, and a ``benchmark`` PR moves the entries over.
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        accepted = json.load(fh)
    with open(MANIFEST) as fh:
        own = json.load(fh)
    assert not READERS & {m["name"] for m in accepted["per_layer"]}
    # the cell and its configuration are the accepted manifest's, key for key
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        assert own[key] == accepted[key], key
    assert own["configs"][0] == next(c for c in accepted["configs"] if c["name"] == "lfm2_8b_a1b")
    assert own["workloads"][0] == next(w for w in accepted["workloads"] if w["name"] == CELL)
    by_name = {m["name"]: m for m in accepted["per_layer"]}
    for metric in own["per_layer"]:
        assert metric["workloads"] == [CELL, "lfm2_tiny.rehearsal"]
        if metric["name"] in READERS:
            assert metric["moves"] == "machines_per_min"
            importlib.import_module(f"chipbench.metrics.{metric['name']}")
        else:
            rest = {k: v for k, v in metric.items() if k != "workloads"}
            assert rest == {k: v for k, v in by_name[metric["name"]].items() if k != "workloads"}
    for name in (CELL, "lfm2_tiny.rehearsal"):
        assert READERS <= {m["name"] for m in run.load_cell(MANIFEST, name)["per_layer"]}


def _ctx(scope_s, op_s, op_scope, counters=None, model=None):
    cell = {
        "config": {"model": model or {}},
        "traffic": SimpleNamespace(chunk_machines=1),
    }
    counters = counters or {}
    return {
        "cell": cell, "device_kind": "TPU v5 lite",
        "before": {k: 0.0 for k in counters}, "after": counters,
        "trace": SimpleNamespace(
            detail_s=sum(scope_s.values()), scope_s=scope_s, op_s=op_s, op_scope=op_scope
        ),
    }


def _read(name, ctx):
    return importlib.import_module(f"chipbench.metrics.{name}").read(ctx)


def test_share_readers_read_scopes_and_the_compilers_kernels():
    scope_s = {"": 4.0, "moe_experts": 1.0, "moe_router": 0.5, "moe_dispatch": 1.5,
               "gated_conv": 2.0, "optimizer_update": 1.0}
    # the grouped products run under no scope: the compiler names them itself
    op_s = {"ragged-dot-none.3": [2.0, 10], "ragged-dot-metadata": [0.5, 10], "fusion.1": [1.5, 3]}
    ctx = _ctx(scope_s, op_s, {op: "" for op in op_s})
    assert _read("moe_expert_share", ctx) == pytest.approx(100 * 3.5 / 10)
    assert _read("moe_route_share", ctx) == pytest.approx(20.0)
    assert _read("gated_conv_share", ctx) == pytest.approx(20.0)
    assert _read("optimizer_share", ctx) == pytest.approx(10.0)
    # a program that lacks the scopes (the parent commit, a stale compile cache)
    bare = _ctx({"": 3.0, "lstm_cell": 1.0}, {"fusion.1": [4.0, 3]}, {"fusion.1": ""})
    for name in ("moe_expert_share", "moe_route_share", "gated_conv_share", "optimizer_share"):
        assert _read(name, bare) is None


def test_counter_readers_and_a_program_without_the_counters(config):
    counters = {
        "gordo_build_moe_assignments_total{where=held}": 4096.0 * 4,
        "gordo_build_moe_assignments_total{where=absent}": 4096.0 * 12,
        "gordo_build_moe_tokens_total": 4096.0 * 4,
        "gordo_build_moe_peak_load_total": 4 * 768.0,
    }
    ctx = _ctx({}, {}, {}, counters, config["model"])
    assert _read("moe_held_load", ctx) == pytest.approx(1.0)
    # four layer-steps whose fullest expert took 768 of a mean 512
    assert _read("moe_load_imbalance", ctx) == pytest.approx(1.5)
    parent = _ctx({}, {}, {}, {"compiles": 0.0}, config["model"])
    assert _read("moe_held_load", parent) is None
    assert _read("moe_load_imbalance", parent) is None


def test_routed_mfu_counts_the_experts_at_the_counters_load(config, monkeypatch):
    from chipbench.metrics import fleet_step_mfu, fleet_step_mfu_routed

    counters = {
        "gordo_build_moe_assignments_total{where=held}": 4096.0,
        "gordo_build_moe_tokens_total": 4096.0 * 4,
    }
    ctx = _ctx({}, {}, {}, counters)
    ctx["cell"]["config"] = config
    monkeypatch.setattr(fleet_step_mfu, "read", lambda ctx: 26.4)
    # a quarter of an assignment a token: 68.33 of the 85.24 GFLOP a window
    assert fleet_step_mfu_routed.read(ctx) == pytest.approx(26.4 * 68.33 / 85.24, rel=1e-4)
    even = dict(counters, **{"gordo_build_moe_assignments_total{where=held}": 4096.0 * 4})
    ctx = _ctx({}, {}, {}, even)
    ctx["cell"]["config"] = config
    assert fleet_step_mfu_routed.read(ctx) == pytest.approx(26.4)
    # a program without the counters, or a run with no device time to hold it to
    bare = _ctx({}, {}, {}, {"compiles": 0.0})
    bare["cell"]["config"] = config
    assert fleet_step_mfu_routed.read(bare) is None
    monkeypatch.setattr(fleet_step_mfu, "read", lambda ctx: None)
    assert fleet_step_mfu_routed.read(ctx) is None


def test_attention_roofline_counts_the_forward_call(config):
    from chipbench.metrics import attention_fwd_roofline as reader

    operations, nbytes = reader.required(config["model"], 1)
    assert operations == 2 * 16 * 32 * 256 * 256 * 64
    assert nbytes == 2 * 16 * (2 * 32 + 2 * 8) * 256 * 64
    least = max(operations / 197e12, nbytes / 819e9)
    # at 256 rows a window the call is bound by its bytes, not by its products
    assert least == pytest.approx(nbytes / 819e9) and least > 2 * operations / 197e12
    op_s = {
        "vmap_jvp_attention__.1": [4 * 2 * least, 4],            # forward, at half its roofline
        "vmap_transpose_vmap_jvp_attention____.2": [9.0, 4],     # backward: not counted
        "vmap_attention_.5": [9.0, 1],                           # a fold's prediction: not counted
        "fusion.7": [9.0, 8],
    }
    ctx = _ctx({"attention": 1.0}, op_s, {op: "attention" for op in op_s}, model=config["model"])
    assert reader.read(ctx) == pytest.approx(50.0)
    assert reader.read(_ctx({"attention": 1.0}, {"fusion.7": [1.0, 2]}, {"fusion.7": "attention"},
                            model=config["model"])) is None
    assert reader.read(_ctx({"lstm_cell": 1.0}, {}, {}, model={"dims": [4]})) is None


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_a_tiny_configuration_of_the_kind(capsys, trace):
    from gordo_tpu.observability import metrics as catalog

    held = catalog.MOE_ASSIGNMENTS.value(where="held")
    absent = catalog.MOE_ASSIGNMENTS.value(where="absent")
    tokens = catalog.MOE_TOKENS.value()
    code = run.main([
        "--workload", "lfm2_tiny.rehearsal", "--seed", str(3_500_000_000 + trace),
        "--seconds", "0.3", "--trace", str(trace), "--rehearsal", "--manifest", MANIFEST,
    ])
    out = capsys.readouterr()
    assert code == 0
    line = json.loads(out.out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    # the counters moved in the builds, and held + absent = tokens x top_k (2) a routed layer
    held = catalog.MOE_ASSIGNMENTS.value(where="held") - held
    absent = catalog.MOE_ASSIGNMENTS.value(where="absent") - absent
    tokens = catalog.MOE_TOKENS.value() - tokens
    assert held > 0 and absent > 0 and held + absent == 2 * tokens
    if trace:
        metrics = line["metrics"]
        assert 0 < metrics["moe_held_load"]["value"] <= 2
        assert 1 <= metrics["moe_load_imbalance"]["value"] <= 4
        # a host's trace names no scope and no kernel: those metrics are left out
        assert not {"moe_expert_share", "attention_fwd_roofline"} & set(metrics)
        # nor has a host a published peak to hold the step to
        assert "fleet_step_mfu_routed" not in metrics
