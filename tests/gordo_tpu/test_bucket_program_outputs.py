"""What leaves the chunk program: one copy of the parameters (the last
stage's, not a stack of every stage's), the final fit's losses, one
prediction a fold and the routed layers' counts; and that a stage inside the
scan is what the same fit gives alone."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gordo_tpu.models.factories.hybrid import hybrid_moe_model
from gordo_tpu.models.factories.latent import latent_moe_model
from gordo_tpu.models.factories.lstm_autoencoder import lstm_symmetric
from gordo_tpu.observability import metrics as metric_catalog
from gordo_tpu.ops import nn
from gordo_tpu.ops.train import make_masked_epoch_fn, make_optimizer, n_train_samples
from gordo_tpu.parallel import batch_trainer

N_ROWS, N_TAGS, LOOKBACK, BATCH = 64, 3, 4, 8
FOLDS = ((16, 16, 32), (32, 32, 48), (48, 48, 64))


def _lstm_spec():
    return lstm_symmetric(N_TAGS, lookback_window=LOOKBACK, dims=(6, 4), funcs=("tanh", "tanh"))


def _hybrid_spec():
    return hybrid_moe_model(
        N_TAGS, lookback_window=LOOKBACK, d_model=8, operators=["conv", "attention"],
        ffns=["routed", "routed"], ff_dim=8, expert_dim=8, num_heads=2, num_kv_heads=1,
        head_dim=4, num_experts=8, experts_held=2, expert_offset=2, top_k=4, attention="xla",
    )


def _latent_spec():
    return latent_moe_model(
        N_TAGS, lookback_window=LOOKBACK, d_model=8, ffns=["dense", "routed"], ff_dim=8,
        expert_dim=8, num_heads=2, q_lora_rank=6, kv_lora_rank=4, qk_nope_head_dim=4,
        qk_rope_head_dim=2, v_head_dim=4, num_experts=8, experts_held=2, expert_offset=2,
        top_k=4, streams=2, attention="xla",
    )


def _data(machines=2):
    rng = np.random.default_rng(3)
    X = rng.normal(size=(machines, N_ROWS, N_TAGS)).astype(np.float32)
    return X, np.arange(7, 7 + machines, dtype=np.uint32)


@pytest.mark.parametrize("make_spec", [_lstm_spec, _hybrid_spec, _latent_spec])
def test_outputs_hold_one_copy_of_the_parameters(make_spec):
    spec = make_spec()
    program = batch_trainer._bucket_program(spec, N_ROWS, FOLDS, 1, BATCH, True, True)
    X, seeds = _data()
    params, losses, preds, counted = jax.eval_shape(program, X, X, seeds)
    one = jax.eval_shape(lambda k: nn.init_model_params(k, spec), jax.random.PRNGKey(0))
    n_one = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(one))
    n_out = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params))
    assert n_out == 2 * n_one  # two machines, one stage each: not four
    assert losses.shape == (2, 1) and len(preds) == len(FOLDS)
    mixing = {"hc_sublayer_steps", "hc_stochastic_gap"}
    assert set(counted) == {
        _lstm_spec: set(), _hybrid_spec: set(nn.MOE_STATS),
        _latent_spec: set(nn.MOE_STATS) | mixing,
    }[make_spec]


def test_lstm_stages_are_what_stand_alone_fits_give():
    """The final fit's parameters and losses and every fold's prediction, out
    of the stage scan, against each stage run alone: scale on the stage's
    training rows, initialise from the stage's key, one masked epoch, predict
    the test slice."""
    spec = _lstm_spec()
    program = batch_trainer._bucket_program(spec, N_ROWS, FOLDS, 1, BATCH, True, True)
    X, seeds = _data()
    p_final, losses, preds, counted = program(X, X, seeds)
    assert counted == {}
    n_full = n_train_samples(spec, N_ROWS)
    epoch = jax.jit(make_masked_epoch_fn(spec, n_full, BATCH, True))
    opt = make_optimizer(spec.optimizer)
    stages = [(tr, te, te_end) for tr, te, te_end in FOLDS] + [(N_ROWS, 0, 16)]
    for lane in range(2):
        x = jnp.asarray(X[lane])
        rng = jax.random.fold_in(jax.random.PRNGKey(0), seeds[lane])
        for k, (tr_end, te_start, te_end) in enumerate(stages):
            k_init, k_fit = jax.random.split(jax.random.fold_in(rng, k))
            mn, mx = x[:tr_end].min(0), x[:tr_end].max(0)
            xs = (x - mn) / (mx - mn)
            params = nn.init_model_params(k_init, spec)
            params, _, loss, _ = epoch(
                params, opt.init(params), xs, x, jax.random.split(k_fit, 1)[0],
                jnp.asarray(n_train_samples(spec, tr_end)),
            )
            pred = batch_trainer._predict_windows(spec, params, xs[te_start:te_end])
            if k < len(FOLDS):
                np.testing.assert_allclose(
                    np.asarray(preds[k][lane]), np.asarray(pred), rtol=1e-5, atol=1e-6
                )
                continue
            np.testing.assert_allclose(float(losses[lane, 0]), float(loss), rtol=1e-6)
            for got, want in zip(
                jax.tree_util.tree_leaves(p_final), jax.tree_util.tree_leaves(params)
            ):
                np.testing.assert_allclose(
                    np.asarray(got[lane]), np.asarray(want), rtol=1e-5, atol=1e-7
                )


def test_routed_counts_leave_the_program_and_reach_the_counters():
    spec = _hybrid_spec()
    program = batch_trainer._bucket_program(spec, N_ROWS, FOLDS, 1, BATCH, True, True)
    X, seeds = _data()
    *_, counted = program(X, X, seeds)
    counted = jax.device_get(counted)
    # live steps a machine: ceil(windows / batch) of each stage; two routed layers
    steps = sum(-(-n_train_samples(spec, rows) // BATCH) for rows in (16, 32, 48, 64))
    tokens = BATCH * LOOKBACK
    for lane in range(2):
        assert counted["moe_layer_steps"][lane] == 2 * steps
        assert counted["moe_tokens"][lane] == 2 * steps * tokens
        held, absent = counted["moe_held"][lane], counted["moe_absent"][lane]
        assert held + absent == 2 * steps * tokens * 4 and held > 0 and absent > 0
        # the fullest of two held experts took at least half of what was held
        assert held / 2 <= counted["moe_peak_load"][lane] <= held

    def read():
        return {
            "held": metric_catalog.MOE_ASSIGNMENTS.value(where="held"),
            "absent": metric_catalog.MOE_ASSIGNMENTS.value(where="absent"),
            "tokens": metric_catalog.MOE_TOKENS.value(),
            "layer_steps": metric_catalog.MOE_LAYER_STEPS.value(),
            "peak": metric_catalog.MOE_PEAK_LOAD.value(),
        }

    before = read()
    # a chunk of two lanes of which one is live: the padding lane is left out
    batch_trainer._note_layer_counts(counted, np.arange(2), 1)
    after = read()
    assert after["held"] - before["held"] == counted["moe_held"][0]
    assert after["absent"] - before["absent"] == counted["moe_absent"][0]
    assert after["tokens"] - before["tokens"] == counted["moe_tokens"][0]
    assert after["layer_steps"] - before["layer_steps"] == 2 * steps
    assert after["peak"] - before["peak"] == counted["moe_peak_load"][0]
    batch_trainer._note_layer_counts({}, np.arange(2), 2)  # nothing routed: nothing added
    assert read() == after
