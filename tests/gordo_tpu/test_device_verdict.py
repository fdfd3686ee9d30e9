"""The divergence verdict is computed where the parameters are: one bool a
lane from a small program queued straight behind the chunk's own, equal to
what ``faults.params_non_finite`` says of the lane's slice on the host; the
host walks a lane's leaves only to name the leaf of one the device flagged."""

import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gordo_tpu.observability import telemetry
from gordo_tpu.parallel import BatchedModelBuilder, batch_trainer, default_mesh
from gordo_tpu.util import faults

from test_build_stages import _machines
from test_fetch_stream import _persisted

LANES = ("nan", "+inf", "-inf", "finite", "finite too", "nan in bfloat16")


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    monkeypatch.delenv(faults.PLAN_ENV, raising=False)
    faults.reset_plan()
    telemetry.reset()
    yield
    faults.reset_plan()
    telemetry.reset()


def _crafted_stack():
    """Six lanes of a parameter tree with a matrix, a vector, a bfloat16
    leaf and an integer leaf that holds the largest values an int32 can."""
    n = len(LANES)
    rng = np.random.RandomState(0)
    kernel = rng.randn(n, 5, 7).astype(np.float32)
    bias = rng.randn(n, 7).astype(np.float32)
    taps = rng.randn(n, 3, 4).astype(np.float32)
    kernel[0, 2, 3] = np.nan
    bias[1, 6] = np.inf
    kernel[2, 0, 0] = -np.inf
    taps[5, 1, 1] = np.nan
    steps = np.full((n, 2), np.iinfo(np.int32).max, np.int32)
    steps[3] = np.iinfo(np.int32).min
    return [
        {"kernel": kernel, "bias": bias},
        {"taps": jnp.asarray(taps, jnp.bfloat16), "steps": steps},
    ]


def test_device_flags_equal_the_host_walk_lane_by_lane():
    stack = _crafted_stack()
    flags = np.asarray(batch_trainer._verdict_program()(stack))
    assert flags.dtype == np.bool_ and flags.shape == (len(LANES),)
    on_host = [
        faults.params_non_finite(
            jax.tree_util.tree_map(lambda a: np.asarray(a)[j], stack)
        )
        is not None
        for j in range(len(LANES))
    ]
    assert on_host == [True, True, True, False, False, True]
    assert flags.tolist() == on_host


N, CHUNK = 4, 2
POISONED_LANE = 1  # of the second chunk


def _builder(prefix, out_dir, **kwargs):
    return BatchedModelBuilder(
        _machines(prefix, n=N),
        mesh=default_mesh(devices=jax.devices()[:1]),
        chunk_size=CHUNK,
        output_dir=str(out_dir),
        **kwargs,
    )


def _poison_chunk(monkeypatch, chunk: int):
    """The chunk program of the build, with a NaN written into the second
    leaf of ``POISONED_LANE`` in its ``chunk``-th execution. Returns the
    shape of that leaf in an artifact."""
    program_for = batch_trainer._program_for
    poisoned_leaf = []

    def poisoned_program_for(*args):
        program, key, cached = program_for(*args)
        calls = iter(range(N))

        def poisoning(*inputs):
            params, *rest = program(*inputs)
            if next(calls) != chunk:
                return (params, *rest)
            leaves, treedef = jax.tree_util.tree_flatten(params)
            poisoned_leaf.append(leaves[1].shape[1:])
            index = (POISONED_LANE,) + (0,) * (leaves[1].ndim - 1)
            leaves[1] = leaves[1].at[index].set(jnp.nan)
            return (jax.tree_util.tree_unflatten(treedef, leaves), *rest)

        return poisoning, key, cached

    monkeypatch.setattr(batch_trainer, "_program_for", poisoned_program_for)
    return poisoned_leaf


def test_poisoned_lane_is_quarantined_with_its_leaf_named(monkeypatch, tmp_path):
    poisoned_leaf = _poison_chunk(monkeypatch, chunk=1)
    walked = []
    params_non_finite = faults.params_non_finite

    def spy(params, losses=None):
        if params is not None:
            walked.append(threading.get_ident())
        return params_non_finite(params, losses)

    monkeypatch.setattr(faults, "params_non_finite", spy)
    builder = _builder("pz", tmp_path)
    results = builder.build()
    lost = f"pz-{CHUNK + POISONED_LANE}"
    others = [f"pz-{i}" for i in range(N) if f"pz-{i}" != lost]
    assert [m.name for _, m in results] == others
    # its neighbours' artifacts are on disk, nothing of it is
    assert _persisted(tmp_path) == others
    assert not os.path.exists(tmp_path / lost)
    [record] = builder.quarantine_records
    (shape,) = poisoned_leaf
    assert record.to_dict() == faults.QuarantineRecord(
        lost, faults.STAGE_TRAINING, "diverged",
        f"non-finite model parameters (leaf shape {shape})",
    ).to_dict()
    # the host walked the leaves of the flagged lane alone, in its pool job
    assert len(walked) == 1 and walked[0] != threading.get_ident()


def test_poisoned_lane_fails_a_fail_fast_build(monkeypatch, tmp_path):
    _poison_chunk(monkeypatch, chunk=0)
    lost = f"pzff-{POISONED_LANE}"
    builder = _builder("pzff", tmp_path, fail_fast=True)
    with pytest.raises(faults.DivergedModelError, match=f"{lost}: non-finite model"):
        builder.build()
    assert lost not in _persisted(tmp_path)


def test_verdict_is_enqueued_before_the_next_chunks_dispatch(monkeypatch, tmp_path):
    """Launched straight after its chunk's program, inside that chunk's
    ``launch`` stage, and part of what ``wait`` blocks on: launched later it
    would queue behind the next chunk's execution, and ``fetch`` would wait
    for both."""
    verdicts, waited_for = [], []
    program_for, verdict_program = batch_trainer._program_for, batch_trainer._verdict_program

    def noting_program_for(*args):
        program, key, cached = program_for(*args)

        def noting(*inputs):
            with telemetry.span("chunk_call"):
                return program(*inputs)

        return noting, key, cached

    def noting_verdict_program(out_sharding=None):
        verdict = verdict_program(out_sharding)

        def noting(params_stack):
            with telemetry.span("verdict_call"):
                verdicts.append(verdict(params_stack))
            return verdicts[-1]

        return noting

    block_until_ready = jax.block_until_ready

    def noting_block(outputs):
        waited_for.append(outputs)
        return block_until_ready(outputs)

    monkeypatch.setattr(batch_trainer, "_program_for", noting_program_for)
    monkeypatch.setattr(batch_trainer, "_verdict_program", noting_verdict_program)
    monkeypatch.setattr(jax, "block_until_ready", noting_block)
    telemetry.start_trace()
    assert len(_builder("vq", tmp_path).build()) == N
    by_name = {}
    for event in telemetry.stop_trace()["traceEvents"]:
        if event["tid"] == threading.get_ident():
            by_name.setdefault(event["name"], []).append(
                (event["ts"], event["ts"] + event["dur"])
            )
    chunks = N // CHUNK
    launches, stacks = sorted(by_name["launch"]), sorted(by_name["stack_h2d"])
    calls, flags = sorted(by_name["chunk_call"]), sorted(by_name["verdict_call"])
    assert [len(x) for x in (launches, stacks, calls, flags)] == [chunks] * 4
    for k in range(chunks):
        # program, then verdict, both inside the chunk's own launch stage
        assert launches[k][0] <= calls[k][0] and calls[k][1] <= flags[k][0]
        assert flags[k][1] <= launches[k][1]
    # ... so before anything of the next chunk is stacked or launched,
    # and before the wait for its own chunk
    assert flags[0][1] <= stacks[1][0]
    assert flags[-1][1] <= min(start for start, _ in by_name["wait"])
    assert len(waited_for) == chunks == len(verdicts)
    for outputs, verdict in zip(waited_for, verdicts):
        assert outputs[-1] is verdict
        assert np.asarray(verdict).tolist() == [False] * CHUNK
