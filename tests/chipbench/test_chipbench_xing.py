"""The configuration ``xing4_0_29b_a4b`` and what it brings to the benchmark:
its file against the published sizes, its parameter and operation counts
against hand counts, its readers on what a traced run hands them, and a CPU
rehearsal of a tiny configuration of its kind through the harness (control
flow only, never a number)."""

import importlib
import json
import os
from types import SimpleNamespace

import pytest

from chipbench import flops, run, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = os.path.join(HERE, "xing_manifest.json")
CELL = "xing4_0_29b_a4b.single_build"
TINY = "xing_tiny.rehearsal"


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "chipbench", "configs", "xing4_0_29b_a4b.json")) as fh:
        return json.load(fh)


def test_file_holds_the_published_sizes(config):
    published = {
        "first_k_dense_replace": 2, "hidden_size": 3584, "intermediate_size": 9216,
        "kv_lora_rank": 512, "max_position_embeddings": 262144,
        "moe_intermediate_size": 1024, "moe_layer_freq": 1, "n_group": 1,
        "n_shared_experts": 1, "num_attention_heads": 32, "num_experts_per_tok": 4,
        "num_key_value_heads": 32, "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
        "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30, "q_lora_rank": 768,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_theta": 10000, "routed_scaling_factor": 2, "topk_group": 1, "v_head_dim": 128,
        "ep_size": 1,
    }
    yarn = {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096, "type": "yarn",
    }
    reduced = {
        "num_hidden_layers": (40, 5), "n_routed_experts": (64, 8),
        "vocab_size": (131072, 0), "num_nextn_predict_layers": (1, 0),
    }
    for key, value in published.items():
        assert config[key] == value and config["published"][key] == value, key
    assert config["rope_scaling"] == yarn == config["published"]["rope_scaling"]
    for key, (was, held) in reduced.items():
        assert config["published"][key] == was and config[key] == held, key
    assert set(config["reduced"]) == set(reduced) | {"epochs"} == set(config["reduced_how"])
    # the model block runs the layers held, at those widths: none is cut
    model, held = config["model"], config["layers_held"]
    assert model["ffns"] == [
        "dense" if i < config["first_k_dense_replace"] else "routed" for i in held
    ]
    assert len(held) == config["num_hidden_layers"] == 5 and model["ffns"].count("routed") == 4
    assert (model["d_model"], model["ff_dim"], model["expert_dim"]) == (3584, 9216, 1024)
    assert (model["q_lora_rank"], model["kv_lora_rank"], model["num_heads"]) == (768, 512, 32)
    assert (model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]) == (128, 64, 128)
    assert (model["num_experts"], model["top_k"], model["experts_held"]) == (64, 4, 8)
    assert (model["shared_experts"], model["routed_scale"]) == (1, 2.0)
    assert (model["streams"], model["sinkhorn_iters"], model["hc_eps"], model["hc_clamp"]) == (4, 20, 1e-6, 30.0)
    assert (model["rope_theta"], model["norm_eps"], model["rope_scaling"]) == (1e4, 1e-6, yarn)
    assert model["kind"] == "latent_moe_model" and config["reference"] == "xing_moe"
    # the rate is in the file twice: what the program reads, what the reference follows
    assert model["optimizer_kwargs"]["learning_rate"] == config["optimizer"]["learning_rate"]
    assert "eight chips" in config["deployment"]
    # every departure the equations make has its line
    assert {"hc_eps", "hc_expansion", "hc_collapse", "hc_init", "hc_rmsnorm", "rope",
            "gate_normaliser", "expert_bias", "batch_size"} <= set(config["assumed"])


def test_parameter_count_is_the_cut_the_issue_states(config):
    import jax

    from chipbench.configs import xing_moe

    shapes = jax.eval_shape(
        lambda key: xing_moe.init_params(key, config["model"], config["n_tags"]),
        jax.random.PRNGKey(0),
    )
    sizes = [sum(int(a.size) for a in layer.values()) for layer in shapes]
    attention = 3584 * 768 + 768 + 768 * 6144 + 3584 * 576 + 512 + 512 * 8192 + 4096 * 3584
    mixing = 2 * (4 * 3584 * 24 + 27)  # phi, three alphas, b_pre 4, b_post 4, b_res 16
    expert = 3 * 3584 * 1024
    dense_layer = attention + 3 * 3584 * 9216 + 2 * 3584 + mixing
    routed_layer = attention + (3584 + 1) * 64 + 8 * expert + expert + 2 * 3584 + mixing
    assert attention == 28_411_136 and expert == 11_010_048
    assert sizes[2:7] == [dense_layer] + 4 * [routed_layer]
    assert (dense_layer, routed_layer) == (128_196_918, 128_426_358)
    assert sizes[0] == 8 * 3584 + 3584 and sizes[-1] == 3584 * 8 + 8 and sizes[-3] == 3584
    assert sum(sizes) == 641_966_870


def test_forward_flops_by_hand(config):
    t, d = 256, 3584
    projections = 2 * t * (d * 768 + 768 * 32 * 192 + d * 576 + 512 * 32 * 256 + 32 * 128 * d)
    scores = t * t * 32 * (192 + 128)  # causal half of 2·T²·H·(Dqk + Dv)
    mixing = 2 * (2 * 4 * d * 24 + 2 * 24 * d) * t  # two sublayers: x~ phi; H_pre X, H_res X, H_post^T y
    dense = 3 * 2 * d * 9216 * t
    # 4 x 8 / 64 = half an assignment a token to the held experts, and the shared expert's one
    routed = 2 * d * 64 * t + (0.5 + 1) * 3 * 2 * d * 1024 * t
    layer = projections + scores + mixing
    hand = 2 * 8 * d * t + (layer + dense) + 4 * (layer + routed) + 2 * d * 8
    assert flops.forward_flops_per_window(config) == hand
    assert projections / t / 1e6 == pytest.approx(56.82, abs=0.005)  # MFLOP a token
    assert (layer + dense) / 1e9 == pytest.approx(66.39, abs=0.005)
    assert (layer + routed) / 1e9 == pytest.approx(24.23, abs=0.005)
    assert hand / 1e9 == pytest.approx(163.33, abs=0.005)
    # a build of 1,152 rows: 5,679 forward passes (three folds, the final fit, the folds' predictions)
    build = flops.build_flops_per_machine(config, traffic.Traffic.load("single_build").rows)
    assert build == 5679 * hand and build / 1e15 == pytest.approx(0.9275, abs=0.00005)
    from chipbench.configs import xing_moe

    eighth = hand - 4 * 0.375 * 3 * 2 * d * 1024 * t
    assert xing_moe.forward_flops_per_window(config, held_load=0.125) == eighth
    assert xing_moe.forward_flops_per_window(config, held_load=0.5) == hand


NEW_READERS = {
    "mla_proj_share", "mla_attention_share", "hc_mix_share", "moe_shared_share",
    "hc_stochastic_gap",
}
SHARED_READERS = {
    "moe_expert_share", "moe_route_share", "optimizer_share", "moe_held_load",
    "moe_load_imbalance", "fleet_step_mfu_routed",
}
SEVEN = {
    "fetch_ms_per_machine", "window_compiles", "fleet_step_ms_per_machine", "fleet_step_mfu",
    "persist_ms_per_machine", "device_idle_share", "build_tail_ms_per_machine",
}
# the set-up's compile stages and the pull's rate: read in every cell
SETUP_AND_PULL = {"setup_trace_s", "setup_lower_s", "setup_backend_s", "pull_gbps"}


def test_the_cell_is_in_the_manifest_with_its_files():
    cell = run.load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    assert cell["chips"] == 1 and cell["traffic"].chunk_machines == 1
    assert cell["traffic"].rows == 1152 and cell["traffic"].check_machines == 1
    assert {m["name"] for m in cell["per_layer"]} == (
        SEVEN | NEW_READERS | SHARED_READERS | SETUP_AND_PULL
    )
    assert {m["name"] for m in cell["end_to_end"]} == {
        "machines_per_min", "machine_ready_p95_s", "setup_s"
    }
    assert set(cell["file"]["limits"]) and cell["file"]["trace"]["detail_seconds"] > 0
    # the readers are BENCHMARK.json's entries: this configuration's own on
    # this cell alone, the routed layer's and the optimizer's beside other cells
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        accepted = json.load(fh)
    assert sum(w["chips"] == 4 for w in accepted["workloads"]) == 0
    by_name = {m["name"]: m for m in accepted["per_layer"]}
    for name in NEW_READERS | SHARED_READERS:
        assert by_name[name]["moves"] == "machines_per_min", name
        assert (by_name[name]["workloads"] == [CELL]) == (name in NEW_READERS), name
        importlib.import_module(f"chipbench.metrics.{name}")
    # the tests' manifest holds the tiny twin alone, with BENCHMARK.json's
    # entries on the twin
    with open(MANIFEST) as fh:
        own = json.load(fh)
    assert [c["name"] for c in own["configs"]] == ["xing_tiny"]
    assert [w["name"] for w in own["workloads"]] == [TINY]
    accepted_e2e = {m["name"]: m for m in accepted["end_to_end"]}
    for metric in own["end_to_end"] + own["per_layer"]:
        assert metric["workloads"] == [TINY]
        rest = {k: v for k, v in metric.items() if k != "workloads"}
        same = by_name.get(metric["name"]) or accepted_e2e[metric["name"]]
        assert rest == {k: v for k, v in same.items() if k != "workloads"}
    listed = {m["name"] for m in run.load_cell(MANIFEST, TINY)["per_layer"]}
    assert listed == SEVEN | NEW_READERS | SHARED_READERS


def _ctx(scope_s, op_s=None, counters=None, config=None):
    op_s, counters = op_s or {}, counters or {}
    cell = {"config": config or {"model": {}}, "traffic": SimpleNamespace(chunk_machines=1)}
    return {
        "cell": cell, "device_kind": "TPU v5 lite",
        "before": {k: 0.0 for k in counters}, "after": counters,
        "trace": SimpleNamespace(
            detail_s=sum(scope_s.values()), scope_s=scope_s, op_s=op_s,
            op_scope={op: "" for op in op_s},
        ),
    }


def _read(name, ctx):
    return importlib.import_module(f"chipbench.metrics.{name}").read(ctx)


def test_share_readers_read_the_new_scopes():
    scope_s = {
        "": 2.0, "mla_down": 1.0, "mla_up": 1.5, "mla_out": 0.5, "attention": 2.0,
        "hc_coeff": 0.75, "hc_mix": 1.25, "moe_shared": 1.0, "moe_experts": 0.5,
        "moe_router": 0.25, "moe_dispatch": 0.75, "optimizer_update": 2.5, "rms_norm": 1.0,
        # the XLA path's two products: jnp.einsum names them itself, inside ``attention``
        "...qd,...kd->...qk": 0.5, "...qk,...kd->...qd": 0.5,
    }
    op_s = {"ragged-dot-none.3": [1.0, 10], "fusion.1": [1.0, 3]}
    ctx = _ctx(scope_s, op_s)
    total = sum(scope_s.values())
    want = {
        "mla_proj_share": 3.0, "mla_attention_share": 3.0, "hc_mix_share": 2.0,
        "moe_shared_share": 1.0, "moe_expert_share": 1.5, "moe_route_share": 1.0,
        "optimizer_share": 2.5,
    }
    for name, seconds in want.items():
        assert _read(name, ctx) == pytest.approx(100 * seconds / total), name
    # no share can pass 100 %, and together they cannot either: a cut's scopes are disjoint
    assert sum(_read(name, ctx) for name in want) <= 100.0
    # a program that lacks the scopes (the parent commit, a stale compile cache)
    bare = _ctx({"": 3.0, "lstm_cell": 1.0}, {"fusion.1": [4.0, 3]})
    for name in ("mla_proj_share", "mla_attention_share", "hc_mix_share", "moe_shared_share"):
        assert _read(name, bare) is None
    # the products' names alone (another model's attention) are not this reader's
    assert _read("mla_attention_share", _ctx({"": 1.0, "...qd,...kd->...qk": 1.0})) is None
    # and a run with no detail cut at all
    assert _read("hc_mix_share", _ctx({})) is None


def test_the_gap_reader_is_the_two_counters_ratio(config):
    counters = {
        "gordo_build_hc_sublayer_steps_total": 120.0 * 10,
        "gordo_build_hc_stochastic_gap_total": 120.0 * 10 * 2.5e-6,
        "gordo_build_moe_assignments_total{where=held}": 4096.0 * 2,
        "gordo_build_moe_tokens_total": 4096.0 * 4,
        "gordo_build_moe_peak_load_total": 4 * 384.0,
    }
    ctx = _ctx({}, counters=counters, config=config)
    assert _read("hc_stochastic_gap", ctx) == pytest.approx(2.5e-6)
    # the routed layer's readers read this configuration with no edit: half
    # an assignment a token is the even router's load on 8 of 64 experts
    assert _read("moe_held_load", ctx) == pytest.approx(0.5)
    assert _read("moe_load_imbalance", ctx) == pytest.approx(4 * 384.0 * 8 / (4096.0 * 2))
    parent = _ctx({}, counters={"compiles": 0.0}, config=config)
    assert _read("hc_stochastic_gap", parent) is None
    # sublayer-steps counted and nothing summed yet: a gap of zero, not a missing one
    still = dict(counters, **{"gordo_build_hc_stochastic_gap_total": 0.0})
    assert _read("hc_stochastic_gap", _ctx({}, counters=still, config=config)) == 0.0


def test_routed_mfu_reads_this_configurations_count(config, monkeypatch):
    from chipbench.metrics import fleet_step_mfu, fleet_step_mfu_routed

    counters = {
        "gordo_build_moe_assignments_total{where=held}": 4096.0 * 0.5,
        "gordo_build_moe_tokens_total": 4096.0 * 4,
    }
    monkeypatch.setattr(fleet_step_mfu, "read", lambda ctx: 30.0)
    ctx = _ctx({}, counters=counters, config=config)
    # an eighth of an assignment a token: 154.87 of the 163.33 GFLOP a window
    assert fleet_step_mfu_routed.read(ctx) == pytest.approx(30.0 * 154.87 / 163.33, rel=1e-4)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_a_tiny_configuration_of_the_kind(capsys, trace):
    from gordo_tpu.observability import metrics as catalog

    steps = catalog.HC_SUBLAYER_STEPS.value()
    gap = catalog.HC_STOCHASTIC_GAP.value()
    layer_steps = catalog.MOE_LAYER_STEPS.value()
    code = run.main([
        "--workload", TINY, "--seed", str(3_700_000_000 + trace),
        "--seconds", "0.3", "--trace", str(trace), "--rehearsal", "--manifest", MANIFEST,
    ])
    out = capsys.readouterr()
    assert code == 0
    line = json.loads(out.out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    # two blocks of two sublayers a step, one of them routed
    steps = catalog.HC_SUBLAYER_STEPS.value() - steps
    layer_steps = catalog.MOE_LAYER_STEPS.value() - layer_steps
    assert steps > 0 and steps == 4 * layer_steps
    assert 0 <= catalog.HC_STOCHASTIC_GAP.value() - gap < 1e-3 * steps
    if trace:
        metrics = line["metrics"]
        assert 0 <= metrics["hc_stochastic_gap"]["value"] < 1e-3
        assert 0 < metrics["moe_held_load"]["value"] <= 2
        # a host's trace names no scope: those metrics are left out
        assert not {"mla_proj_share", "hc_mix_share", "moe_shared_share"} & set(metrics)
        assert "fleet_step_mfu_routed" not in metrics


@pytest.mark.parametrize("unmoved", [None, "shared_w1", "hc_op_alpha"])
def test_the_leaf_look_reads_what_the_comparison_reads(unmoved):
    """``scripts/leaf_gap_look.py`` splits ``check``'s ``weights`` and ``leaf``
    leaf by leaf: its worst counted leaf is ``leaf``, its counted leaves add up
    to ``weights``; a leaf as large as the median one that the program leaves
    unmoved reads 1 there, a leaf far smaller than the median reads next to 0
    (what the ``leaf`` limit can and cannot see)."""
    import numpy as np

    from chipbench import check

    look = importlib.import_module("scripts.leaf_gap_look")
    rng = np.random.default_rng(37)
    shapes = {"shared_w1": (6, 8), "w1": (3, 6, 8), "router": (6, 4), "hc_op_alpha": (3,), "wo": (8, 6)}
    init = [{k: rng.normal(size=s) for k, s in shapes.items()}, {}]
    moved = [{k: v + 0.1 * rng.normal(size=v.shape) for k, v in init[0].items()}, {}]
    moved[0]["hc_op_alpha"] = init[0]["hc_op_alpha"] + 1e-3
    ref = {
        "init": init, "params": moved, "first_grad": [{k: np.ones(s) for k, s in shapes.items()}, {}],
        "loss": 1.0, "output": np.ones((2, 8)), "aggregate_threshold": 1.0,
        "feature_thresholds": np.ones(8), "scaler": {"span": np.ones(8)},
    }
    ours = [{k: v + 1e-3 * rng.normal(size=v.shape) for k, v in moved[0].items()}, {}]
    if unmoved:
        ours[0][unmoved] = init[0][unmoved]
    observed = dict(check.as_observed(ref, np.zeros((2, 8))), params=ours)
    numbers = check.gaps(observed, ref, np.zeros((2, 8)))
    table = look.by_leaf(ours, ref)
    counted = [leaf for leaf in table["leaves"] if leaf["counted"]]
    assert max(leaf["leaf_gap"] for leaf in counted) == pytest.approx(numbers["leaf"])
    total = sum(leaf["diff_sq"] for leaf in counted) / sum(leaf["reference_change"] ** 2 for leaf in counted)
    assert total**0.5 == pytest.approx(numbers["weights"])
    assert sum(g["share_of_squared_gap"] for g in table["groups"].values()) == pytest.approx(1.0)
    assert {leaf["leaf"]: leaf["group"] for leaf in counted} == {
        "shared_w1": "shared_expert", "w1": "experts", "router": "router",
        "hc_op_alpha": "mixing", "wo": "latent",
    }
    want = {None: (0.0, 0.05), "shared_w1": (0.99, 1.01), "hc_op_alpha": (0.0, 0.05)}[unmoved]
    assert want[0] <= numbers["leaf"] <= want[1]


@pytest.mark.parametrize("ffn", ["dense", "routed"])
def test_the_last_block_owes_the_last_position_alone(ffn):
    """The plain model's last block, computed at the last position alone, is
    the whole block's last position, in value and in every leaf's gradient:
    what the reference saves is work nothing reads."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import reference
    from chipbench.configs import xing_moe

    with open(os.path.join(ROOT, "chipbench", "rehearsal", "xing_tiny.json")) as fh:
        tiny = json.load(fh)
    model = dict(tiny["model"], ffns=[ffn])
    p = xing_moe.init_params(jax.random.PRNGKey(2), model, int(tiny["n_tags"]))[2]
    p = jax.tree_util.tree_map(
        lambda a: a + 0.3 * jax.random.normal(jax.random.PRNGKey(a.size), a.shape), p
    )
    x = jax.random.normal(
        jax.random.PRNGKey(3),
        (3, int(model["lookback_window"]), int(model["streams"]), int(model["d_model"])),
    )
    mm = reference.matmul("float32")

    def whole(p, x):
        return xing_moe._block(model, ffn, p, x, mm)[:, -1:]

    def trimmed(p, x):
        return xing_moe._block(model, ffn, p, x, mm, last_only=True)

    with jax.default_matmul_precision("highest"):
        a, b = whole(p, x), trimmed(p, x)
        assert a.shape == b.shape == (3, 1) + x.shape[2:]
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)
        grads = [
            jax.grad(lambda p, x, f=f: jnp.sum(jnp.sin(f(p, x))), argnums=(0, 1))(p, x)
            for f in (whole, trimmed)
        ]
    for name in grads[0][0]:
        g_whole, g_trimmed = (np.asarray(g[0][name]) for g in grads)
        np.testing.assert_allclose(
            g_whole, g_trimmed, rtol=1e-4, atol=1e-5 * (1 + np.abs(g_whole).max()), err_msg=name
        )
    # the other positions still give the last one their keys and values
    assert np.abs(np.asarray(grads[1][1])[:, :-1]).max() > 0
    np.testing.assert_allclose(
        np.asarray(grads[0][1]), np.asarray(grads[1][1]), rtol=1e-4, atol=1e-5
    )


def test_the_plain_models_weights_are_drawn_in_one_program():
    """One compiled program a (model, tags) draws every leaf, strongly typed
    float32 (a weakly typed leaf compiles the reference's step a second time)."""
    import jax
    import jax.numpy as jnp

    from chipbench.configs import xing_moe

    with open(os.path.join(ROOT, "chipbench", "rehearsal", "xing_tiny.json")) as fh:
        tiny = json.load(fh)
    model, tags = tiny["model"], int(tiny["n_tags"])
    first = xing_moe.init_params(jax.random.PRNGKey(0), model, tags)
    fn = xing_moe._init_fn(json.dumps(model, sort_keys=True), tags)
    compiled = fn._cache_size()
    xing_moe.init_params(jax.random.PRNGKey(1), dict(model), tags)
    assert fn._cache_size() == compiled >= 1
    for leaf in jax.tree_util.tree_leaves(first):
        assert leaf.dtype == jnp.float32 and not leaf.weak_type
