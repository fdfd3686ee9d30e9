import numpy as np
import pytest
import yaml
from sklearn.decomposition import PCA
from sklearn.pipeline import FeatureUnion, Pipeline
from sklearn.preprocessing import MinMaxScaler

from gordo_tpu import serializer
from gordo_tpu.models.models import AutoEncoder
from gordo_tpu.serializer.resolver import UnsafeImportError, locate


def test_from_definition_basic_pipeline():
    definition = yaml.safe_load(
        """
        sklearn.pipeline.Pipeline:
          steps:
            - sklearn.preprocessing.MinMaxScaler
            - gordo_tpu.models.models.AutoEncoder:
                kind: feedforward_hourglass
        """
    )
    pipe = serializer.from_definition(definition)
    assert isinstance(pipe, Pipeline)
    assert isinstance(pipe.steps[0][1], MinMaxScaler)
    assert isinstance(pipe.steps[1][1], AutoEncoder)
    assert pipe.steps[1][1].kind == "feedforward_hourglass"


def test_from_definition_feature_union():
    definition = yaml.safe_load(
        """
        sklearn.pipeline.FeatureUnion:
          - sklearn.decomposition.PCA:
              n_components: 2
          - sklearn.preprocessing.MinMaxScaler
        """
    )
    union = serializer.from_definition(definition)
    assert isinstance(union, FeatureUnion)
    assert isinstance(union.transformer_list[0][1], PCA)


def test_gordo_compat_alias():
    """Reference gordo configs resolve to gordo_tpu classes unmodified."""
    definition = yaml.safe_load(
        """
        gordo.machine.model.anomaly.diff.DiffBasedAnomalyDetector:
          require_thresholds: false
          base_estimator:
            gordo.machine.model.models.KerasAutoEncoder:
              kind: feedforward_hourglass
        """
    )
    from gordo_tpu.models.anomaly.diff import DiffBasedAnomalyDetector

    det = serializer.from_definition(definition)
    assert isinstance(det, DiffBasedAnomalyDetector)
    assert isinstance(det.base_estimator, AutoEncoder)


def test_into_definition_roundtrip():
    pipe = Pipeline(
        [
            ("step_0", MinMaxScaler()),
            ("step_1", AutoEncoder(kind="feedforward_hourglass", epochs=2)),
        ]
    )
    definition = serializer.into_definition(pipe)
    pipe2 = serializer.from_definition(definition)
    definition2 = serializer.into_definition(pipe2)
    assert definition == definition2
    assert isinstance(pipe2.steps[1][1], AutoEncoder)
    assert pipe2.steps[1][1].kwargs["epochs"] == 2


def test_into_definition_anomaly_detector_roundtrip():
    """Regression: DiffBasedAnomalyDetector.__getattr__ delegates unknown
    attributes to base_estimator; into_definition must not pick up the base
    estimator's into_definition hook through that delegation (it used to
    flatten the wrapper, producing a definition that can't be re-loaded)."""
    definition = {
        "gordo_tpu.models.anomaly.diff.DiffBasedAnomalyDetector": {
            "base_estimator": {
                "gordo_tpu.models.models.AutoEncoder": {
                    "kind": "feedforward_hourglass",
                    "epochs": 1,
                }
            }
        }
    }
    obj = serializer.from_definition(definition)
    d2 = serializer.into_definition(obj)
    key = "gordo_tpu.models.anomaly.diff.DiffBasedAnomalyDetector"
    assert "base_estimator" in d2[key], d2
    # and the definition reconstructs — the full CLI round-trip
    obj2 = serializer.from_definition(d2)
    assert type(obj2).__name__ == "DiffBasedAnomalyDetector"
    assert obj2.base_estimator.kwargs["epochs"] == 1


def test_function_transformer_roundtrip():
    definition = yaml.safe_load(
        """
        sklearn.preprocessing.FunctionTransformer:
          func: gordo_tpu.models.transformer_funcs.general.multiply_by
          kw_args:
            factor: 2
        """
    )
    ft = serializer.from_definition(definition)
    assert np.allclose(ft.transform(np.array([1.0, 2.0])), [2.0, 4.0])
    definition2 = serializer.into_definition(ft)
    key = "sklearn.preprocessing._function_transformer.FunctionTransformer"
    assert (
        definition2[key]["func"]
        == "gordo_tpu.models.transformer_funcs.general.multiply_by"
    )


def test_unsafe_import_rejected():
    with pytest.raises(UnsafeImportError):
        locate("os.system")
    with pytest.raises((UnsafeImportError, ImportError)):
        serializer.from_definition({"subprocess.Popen": {"args": ["ls"]}})


def test_dump_load_roundtrip(tmp_path):
    pipe = Pipeline([("mm", MinMaxScaler())])
    X = np.random.rand(10, 2)
    pipe.fit(X)
    serializer.dump(pipe, tmp_path, metadata={"foo": "bar"})
    pipe2 = serializer.load(tmp_path)
    assert np.allclose(pipe2.transform(X), pipe.transform(X))
    assert serializer.load_metadata(tmp_path) == {"foo": "bar"}


def test_dumps_loads_roundtrip():
    model = AutoEncoder(kind="feedforward_symmetric")
    blob = serializer.dumps(model)
    model2 = serializer.loads(blob)
    assert isinstance(model2, AutoEncoder)
    assert model2.kind == "feedforward_symmetric"


def test_step_with_empty_yaml_body_constructs_no_arg():
    """`- sklearn.preprocessing.MinMaxScaler:` (trailing colon, empty body)
    parses to {path: None} — must construct with no args, not TypeError."""
    import yaml

    from gordo_tpu import serializer

    definition = yaml.safe_load(
        """
sklearn.pipeline.Pipeline:
  steps:
    - sklearn.preprocessing.MinMaxScaler:
    - gordo_tpu.models.models.AutoEncoder:
        kind: feedforward_hourglass
"""
    )
    pipe = serializer.from_definition(definition)
    assert type(pipe.steps[0][1]).__name__ == "MinMaxScaler"


# ------------------------------------------------- the artifact's format
# One format, pickle protocol 5: a leaf goes from the array the build holds
# to ``file.write`` with no copy in the pickler. The artifact is the one the
# fleet build writes: estimator in a Pipeline in a DiffBasedAnomalyDetector.
_WIDE = """gordo_tpu.models.models.AutoEncoder:
                kind: feedforward_model
                encoding_dim: [1024, 1024]
                encoding_func: [tanh, tanh]
                decoding_dim: [8]
                decoding_func: [tanh]"""
_BIG_LEAF = (1024, 1024)  # 4 MB of float32


def _format_machines(**kwargs):
    from test_build_stages import _machines

    return _machines("fmt", _WIDE, n=2, **kwargs)


def _format_builder(machines, out_dir, register_dir):
    import jax

    from gordo_tpu.parallel import BatchedModelBuilder, default_mesh

    return BatchedModelBuilder(
        machines,
        mesh=default_mesh(devices=jax.devices()[:1]),
        chunk_size=2,
        output_dir=str(out_dir),
        model_register_dir=str(register_dir),
    )


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    """One fleet build of two machines in one chunk: ``(root, model, name)``
    of the first, its artifact under ``root/out/<name>`` and registered."""
    root = tmp_path_factory.mktemp("format")
    results = _format_builder(
        _format_machines(), root / "out", root / "registry"
    ).build()
    assert [m.name for _, m in results] == ["fmt-0", "fmt-1"]
    model, machine = results[0]
    return root, model, machine.name


def _leaves(model):
    import jax

    return jax.tree_util.tree_leaves(model.base_estimator.steps[-1][1].params_)


def _same_leaves(loaded, model):
    got, want = _leaves(loaded), _leaves(model)
    assert len(got) == len(want) and _BIG_LEAF in [leaf.shape for leaf in got]
    for a, b in zip(got, want):
        assert isinstance(a, np.ndarray)
        assert (a.dtype, a.shape) == (b.dtype, b.shape)
        np.testing.assert_array_equal(a, b)


def _probe_rows():
    import pandas as pd

    return pd.DataFrame(np.random.RandomState(0).rand(20, 3))


def _dump_load(built, tmp_path, monkeypatch):
    root, model, name = built
    _same_leaves(serializer.load(root / "out" / name), model)
    serializer.dump(model, tmp_path)  # again, from the model in memory
    _same_leaves(serializer.load(tmp_path), model)


def _dumps_loads(built, tmp_path, monkeypatch):
    _, model, _ = built
    blob = serializer.dumps(model)
    assert blob[:2] == b"\x80\x05"
    _same_leaves(serializer.loads(blob), model)


def _protocol_5_one_buffer_a_leaf(built, tmp_path, monkeypatch):
    import pickletools

    root, model, name = built
    with open(root / "out" / name / "model.pkl", "rb") as f:
        stream = f.read()
    assert stream[:2] == b"\x80\x05"
    ops = [(op.name, arg) for op, arg, _ in pickletools.genops(stream)]
    assert ("SHORT_BINUNICODE", "_frombuffer") in ops
    for leaf in _leaves(model):
        if leaf.nbytes < 1 << 20:
            continue
        # the array the build sliced from device_get's stack is read-only:
        # one bytes opcode holds it, whole
        held = [
            name_ for name_, arg in ops
            if isinstance(arg, (bytes, bytearray)) and len(arg) == leaf.nbytes
        ]
        assert held == ["BINBYTES"], (leaf.shape, held)


def _dump_hands_the_leaf_to_write_uncopied(built, tmp_path, monkeypatch):
    """Not through ``tobytes``: ``file.write`` is given a buffer over the
    leaf's own memory."""
    import os

    _, model, _ = built
    big = [leaf for leaf in _leaves(model) if leaf.shape == _BIG_LEAF]
    shared = []

    class Recording:
        def __init__(self, f):
            self._f = f

        def write(self, data):
            if not isinstance(data, bytes) and memoryview(data).nbytes >= 1 << 20:
                view = np.frombuffer(data, np.uint8)
                shared.append(any(np.shares_memory(view, leaf) for leaf in big))
            return self._f.write(data)

        def __getattr__(self, name):
            return getattr(self._f, name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return self._f.__exit__(*exc)

    fdopen = os.fdopen
    monkeypatch.setattr(os, "fdopen", lambda fd, mode: Recording(fdopen(fd, mode)))
    serializer.dump(model, tmp_path)
    monkeypatch.undo()
    assert shared == [True] * len(big) and big
    _same_leaves(serializer.load(tmp_path), model)


def _parents_format_still_loads(built, tmp_path, monkeypatch):
    import pickle

    _, model, _ = built
    with open(tmp_path / "model.pkl", "wb") as f:
        pickle.dump(model, f, protocol=4)  # what serializer.dump wrote before
    loaded = serializer.load(tmp_path)
    _same_leaves(loaded, model)
    assert all(leaf.flags.writeable for leaf in _leaves(loaded))
    blob = pickle.dumps(model, protocol=4)
    _same_leaves(serializer.loads(blob), model)


def _loaded_leaves_are_read_only_and_predict(built, tmp_path, monkeypatch):
    root, model, name = built
    loaded = serializer.load(root / "out" / name)
    assert not any(leaf.flags.writeable for leaf in _leaves(loaded))
    rows = _probe_rows()
    np.testing.assert_allclose(
        loaded.predict(rows), model.predict(rows), rtol=1e-5, atol=1e-6
    )
    scored = loaded.anomaly(rows, rows)
    assert np.isfinite(scored["total-anomaly-scaled"].to_numpy()).all()


def _loaded_stacks_into_the_param_bank(built, tmp_path, monkeypatch):
    import jax

    from gordo_tpu.server.batcher import _ParamBank

    root, model, name = built
    bank = _ParamBank()
    trees = [
        serializer.load(root / "out" / n).base_estimator.steps[-1][1].params_
        for n in ("fmt-0", "fmt-1")
    ]
    assert [bank.slot_of(tree) for tree in trees] == [0, 1]  # restack, then in place
    for slot, tree in enumerate(trees):
        for stacked, leaf in zip(
            jax.tree_util.tree_leaves(bank.stacked), jax.tree_util.tree_leaves(tree)
        ):
            assert not leaf.flags.writeable
            np.testing.assert_array_equal(np.asarray(stacked[slot]), leaf)


def _loaded_warm_starts_a_build(built, tmp_path, monkeypatch):
    """The data window moved, so the full key misses and the warm key finds
    the artifact: its read-only leaves are the chunk's initial weights."""
    import jax

    from gordo_tpu.observability import metrics as mc
    from gordo_tpu.parallel import BatchedModelBuilder

    root, model, _ = built
    warmed = []
    maybe_warm_params = BatchedModelBuilder._maybe_warm_params

    def spy(self, machine, spec):
        params = maybe_warm_params(self, machine, spec)
        warmed.append(params)
        return params

    monkeypatch.setattr(BatchedModelBuilder, "_maybe_warm_params", spy)
    before = mc.WARM_STARTS.value()
    results = _format_builder(
        _format_machines(end="2019-01-03T00:00:00+00:00"),
        tmp_path / "out", root / "registry",
    ).build()
    assert [m.name for _, m in results] == ["fmt-0", "fmt-1"]
    assert mc.WARM_STARTS.value() - before == 2
    assert len(warmed) == 2 and None not in warmed
    for tree in warmed:
        assert not any(
            leaf.flags.writeable for leaf in jax.tree_util.tree_leaves(tree)
        )
    _same_leaves(serializer.load(root / "out" / "fmt-0"), model)  # untouched


_FORMAT_CASES = {
    case.__name__.lstrip("_"): case
    for case in (
        _dump_load,
        _dumps_loads,
        _protocol_5_one_buffer_a_leaf,
        _dump_hands_the_leaf_to_write_uncopied,
        _parents_format_still_loads,
        _loaded_leaves_are_read_only_and_predict,
        _loaded_stacks_into_the_param_bank,
        _loaded_warm_starts_a_build,
    )
}


@pytest.mark.parametrize("case", sorted(_FORMAT_CASES))
def test_artifact_format(case, built, tmp_path, monkeypatch):
    _FORMAT_CASES[case](built, tmp_path, monkeypatch)
