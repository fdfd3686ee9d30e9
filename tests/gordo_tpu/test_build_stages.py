"""The fleet build read from inside: the build thread's stages as spans
that tile ``build()`` (and as ``gordo.`` marks in a profiler session), and
the named scopes in the chunk program."""

import glob
import os
import re
import threading

import jax
import pytest
import yaml

from gordo_tpu.observability import metrics as metric_catalog
from gordo_tpu.observability import telemetry
from gordo_tpu.parallel import BatchedModelBuilder, batch_trainer, default_mesh
from gordo_tpu.workflow.normalized_config import NormalizedConfig

ONCE_A_BUILD = (
    "plan", "fetch_stage", "validate_stage", "bucket_prep", "compile",
    "train", "tail", "drain",
)
ONCE_A_CHUNK = ("stack_h2d", "launch", "wait", "d2h", "slice")
# once a chunk after the first: the wait for the chunk's own fetches
AFTER_THE_FIRST = ("fetch_wait",)
LEAVES = (
    "plan", "fetch_stage", "validate_stage", "bucket_prep", "drain",
) + ONCE_A_CHUNK + AFTER_THE_FIRST
CHUNKS = 2


HOURGLASS = """gordo_tpu.models.models.AutoEncoder:
                kind: feedforward_hourglass"""


def _machines(prefix, estimator=HOURGLASS, n=2 * CHUNKS, end="2019-01-02T00:00:00+00:00"):
    blocks = "".join(
        f"""
  - name: {prefix}-{i}
    dataset:
      tags: [{prefix}-{i}-a, {prefix}-{i}-b, {prefix}-{i}-c]
      train_start_date: '2019-01-01T00:00:00+00:00'
      train_end_date: '{end}'
      data_provider: {{type: RandomDataProvider}}
    model:
      gordo_tpu.models.anomaly.diff.DiffBasedAnomalyDetector:
        require_thresholds: true
        base_estimator:
          sklearn.pipeline.Pipeline:
            steps:
            - sklearn.preprocessing.MinMaxScaler
            - {estimator}
                epochs: 1
"""
        for i in range(n)
    )
    config = yaml.safe_load("machines:" + blocks)
    return NormalizedConfig(config, project_name="stages").machines


def _build(prefix, out_dir):
    """Four machines in two chunks of two on one device, on this thread."""
    return BatchedModelBuilder(
        _machines(prefix),
        mesh=default_mesh(devices=jax.devices()[:1]),
        chunk_size=2,
        output_dir=str(out_dir),
    ).build()


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.reset()
    yield
    telemetry.reset()


def _phase_counts():
    return {
        phase: metric_catalog.BUILD_PHASE_SECONDS.count(phase=phase)
        for phase in ONCE_A_BUILD + ONCE_A_CHUNK + AFTER_THE_FIRST
    }


def test_build_thread_stages_tile_the_build(tmp_path):
    telemetry.start_trace()
    assert len(_build("tile", tmp_path)) == 2 * CHUNKS
    events = [
        e for e in telemetry.stop_trace()["traceEvents"]
        if e["tid"] == threading.get_ident()
    ]
    by_name = {}
    for event in events:
        by_name.setdefault(event["name"], []).append(
            (event["ts"], event["ts"] + event["dur"])
        )
    for name in ONCE_A_BUILD:
        assert len(by_name[name]) == 1, name
    for name in ONCE_A_CHUNK:
        assert len(by_name[name]) == CHUNKS, name
    for name in AFTER_THE_FIRST:
        assert len(by_name[name]) == CHUNKS - 1, name
    # each observed its wall under its own phase label
    assert _phase_counts() == {
        **{name: 1 for name in ONCE_A_BUILD},
        **{name: CHUNKS for name in ONCE_A_CHUNK},
        **{name: CHUNKS - 1 for name in AFTER_THE_FIRST},
    }

    # the leaves are contiguous and non-overlapping, and cover the build
    (build_start, build_end), = by_name["batched_build"]
    leaves = sorted(iv for name in LEAVES for iv in by_name[name])
    for (_, end), (start, _) in zip(leaves, leaves[1:]):
        assert start >= end - 1.0  # microseconds; one clock read apart
    covered = sum(end - start for start, end in leaves)
    assert covered >= 0.95 * (build_end - build_start)
    assert leaves[0][0] >= build_start and leaves[-1][1] <= build_end

    # the fetch stage ends with the first chunk's machines, before anything
    # is stacked; a later chunk waits for its own just before it is stacked
    (_, fetch_stage_end), = by_name["fetch_stage"]
    stacks = sorted(by_name["stack_h2d"])
    assert fetch_stage_end <= stacks[0][0]
    for (_, wait_end), (stack_start, _) in zip(by_name["fetch_wait"], stacks[1:]):
        assert stacks[0][1] <= wait_end <= stack_start

    # the parents: compile holds the first chunk's stack_h2d + launch, train
    # ends with the last wait, and tail starts there and holds what follows
    (compile_start, compile_end), = by_name["compile"]
    assert compile_start <= by_name["stack_h2d"][0][0]
    assert by_name["launch"][0][1] <= compile_end
    (_, train_end), = by_name["train"]
    last_wait_end = max(end for _, end in by_name["wait"])
    assert last_wait_end <= train_end
    (tail_start, tail_end), = by_name["tail"]
    assert tail_start >= last_wait_end
    # the tail is the last chunk's pull and submits, then the pool's drain:
    # nothing is done to an artifact once its own job has returned
    last = lambda name: max(by_name[name])  # noqa: E731
    (drain_start, drain_end), = by_name["drain"]
    assert tail_start <= last("d2h")[0] and last("slice")[1] <= drain_start
    assert drain_end <= tail_end
    assert "finalize" not in by_name
    # what _build_all does after its last bucket is under batched_build alone
    assert build_end - tail_end <= 0.01 * (build_end - build_start)


def _files(machine_dir):
    return {
        name: os.stat(os.path.join(machine_dir, name)).st_mtime_ns
        for name in sorted(os.listdir(machine_dir))
    }


def test_each_artifact_is_written_once(tmp_path, monkeypatch):
    """A machine is final when its own pool job returns: one ``dump`` a
    machine, no second pass over any ``metadata.json``, and no file of it
    touched afterwards (against the job's return, not a chunk's ``wait``,
    which a tiny CPU model can beat)."""
    from gordo_tpu import serializer

    dumped, rewritten = [], []
    dump, dump_metadata = serializer.dump, serializer.dump_metadata

    def counted_dump(obj, dest_dir, metadata=None):
        dumped.append(os.path.basename(dest_dir))
        return dump(obj, dest_dir, metadata=metadata)

    def counted_dump_metadata(dest_dir, metadata):
        rewritten.append(os.path.basename(dest_dir))
        return dump_metadata(dest_dir, metadata)

    # batch_trainer calls both through the package (dump writes its own
    # metadata.json inside the serializer module, past this name)
    monkeypatch.setattr(serializer, "dump", counted_dump)
    monkeypatch.setattr(serializer, "dump_metadata", counted_dump_metadata)

    when_final, job_threads = {}, set()
    assemble_and_persist = BatchedModelBuilder._assemble_and_persist

    def job(self, plan, *args, **kwargs):
        built = assemble_and_persist(self, plan, *args, **kwargs)
        when_final[plan.machine.name] = _files(tmp_path / plan.machine.name)
        job_threads.add(threading.get_ident())
        return built

    monkeypatch.setattr(BatchedModelBuilder, "_assemble_and_persist", job)
    names = [machine.name for _, machine in _build("once", tmp_path)]
    assert len(names) == 2 * CHUNKS
    assert sorted(dumped) == sorted(names)
    assert rewritten == []
    assert threading.get_ident() not in job_threads
    for name in names:
        assert {"model.pkl", "metadata.json"} <= set(when_final[name])
        assert _files(tmp_path / name) == when_final[name], name


def test_spans_off_observes_no_stage_and_allocates_no_span(tmp_path):
    assert not telemetry.spans_enabled()
    for name in batch_trainer._STAGE_SECONDS:
        assert batch_trainer._stage(name, machines=1) is telemetry._NULL_SPAN
    assert len(_build("off", tmp_path)) == 2 * CHUNKS
    assert set(_phase_counts().values()) == {0}
    phases = {p for (p,), _ in metric_catalog.BUILD_PHASE_SECONDS.snapshot()}
    assert not phases & set(batch_trainer._STAGE_SECONDS)


def test_profiler_session_holds_the_stages_as_gordo_marks(tmp_path):
    """Whoever opens a jax.profiler session turns spans on: the session then
    holds the program's stages on its own clock, prefix ``gordo.``."""
    from jax.profiler import ProfileData

    telemetry.enable_spans()
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        _build("marks", tmp_path / "out")
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        os.path.join(tmp_path, "trace", "plugins", "profile", "*", "*.xplane.pb")
    )
    names = {
        event.name
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines
        for event in line.events
        if event.name.startswith(telemetry.PROFILER_MARK)
    }
    assert {"gordo.fetch_stage", "gordo.wait", "gordo.tail"} <= names


# estimator block -> the scopes of docs/observability.md its spec reaches
SCOPES = {
    "hourglass": (HOURGLASS, ("dense", "optimizer_update", "fold_predict")),
    "lstm": (
        """gordo_tpu.models.models.LSTMAutoEncoder:
                kind: lstm_symmetric
                dims: [4]
                funcs: [tanh]
                lookback_window: 4""",
        ("lstm_input_proj", "lstm_cell", "lstm_weight_grad", "dense",
         "window_gather", "optimizer_update", "fold_predict"),
    ),
    "transformer": (
        """gordo_tpu.models.models.TransformerAutoEncoder:
                kind: transformer_model
                d_model: 8
                num_heads: 2
                ff_dim: 8
                num_blocks: 1
                lookback_window: 4""",
        ("attention", "dense", "window_gather", "optimizer_update", "fold_predict"),
    ),
    "hybrid": (
        """gordo_tpu.models.models.TransformerAutoEncoder:
                kind: hybrid_moe_model
                d_model: 8
                operators: [conv, attention]
                ffns: [dense, routed]
                ff_dim: 8
                expert_dim: 8
                num_heads: 2
                num_kv_heads: 1
                head_dim: 4
                num_experts: 4
                experts_held: 2
                top_k: 2
                lookback_window: 4""",
        ("gated_conv", "attention", "rms_norm", "moe_router", "moe_dispatch",
         "moe_experts", "dense", "window_gather", "optimizer_update",
         "fold_predict"),
    ),
    "latent": (
        """gordo_tpu.models.models.TransformerAutoEncoder:
                kind: latent_moe_model
                d_model: 8
                ffns: [dense, routed]
                ff_dim: 8
                expert_dim: 8
                num_heads: 2
                q_lora_rank: 6
                kv_lora_rank: 4
                qk_nope_head_dim: 4
                qk_rope_head_dim: 2
                v_head_dim: 4
                num_experts: 4
                experts_held: 2
                top_k: 2
                streams: 2
                lookback_window: 4""",
        ("mla_down", "mla_up", "attention", "mla_out", "hc_coeff", "hc_mix",
         "moe_shared", "rms_norm", "moe_router", "moe_dispatch", "moe_experts",
         "dense", "window_gather", "optimizer_update", "fold_predict"),
    ),
}


@pytest.mark.parametrize("kind", sorted(SCOPES))
def test_bucket_program_carries_the_named_scopes(kind):
    """The lowered chunk program names, in its debug info, every scope of
    the table in docs/observability.md that the spec reaches."""
    import numpy as np

    estimator, scopes = SCOPES[kind]
    (machine,) = _machines(f"scope-{kind}", estimator, n=1)
    plan = batch_trainer._plan_machine(machine)
    n_rows, n_tags = 64, 3
    program = batch_trainer._bucket_program(
        plan.spec, n_rows, ((16, 16, 32), (32, 32, 48), (48, 48, 64)),
        1, 8, True, True,
    )
    X = np.zeros((2, n_rows, n_tags), np.float32)
    text = program.lower(X, X, np.zeros((2,), np.uint32)).as_text(debug_info=True)
    for scope in scopes:
        assert re.search(rf'[("/]{scope}[)/]', text), scope
    # forward and backward need no scope of their own: JAX writes them
    assert "jvp(dense)" in text and "transpose(jvp(dense))" in text
