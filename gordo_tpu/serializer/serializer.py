"""
Artifact persistence: dump/load a trained pipeline to/from a directory.

Reference parity: gordo/serializer/serializer.py:22-170 — ``dump`` writes
``model.pkl`` + ``metadata.json``; ``load`` reads them back; ``dumps/loads``
are the raw-bytes forms used by the /download-model route.

Our JAX estimators implement ``__getstate__``/``__setstate__`` so their
parameter pytrees go into the pickle as host numpy arrays (the TPU-native
analog of the reference's h5-inside-pickle trick,
gordo/machine/model/models.py:183-208). Pickle remains the envelope because
arbitrary fitted sklearn preprocessing steps must round-trip too.

One format, protocol 5: numpy reduces a contiguous array to a
``PickleBuffer`` there, and the C pickler hands a large in-band buffer
straight to ``file.write``, where protocol 4 copied every array into a
fresh ``bytes`` first (``tobytes``). ``pickle.load`` reads either, so an
artifact from before still loads. A leaf written from a read-only array
(what ``jax.device_get`` returns) loads read-only.
"""

import os
import pickle
from typing import Any, Optional, Union

PICKLE_PROTOCOL = 5

try:
    import simplejson
except ImportError:  # pragma: no cover - environment-dependent
    from gordo_tpu.util import _simplejson as simplejson


def dumps(model: Any) -> bytes:
    """Serialize a model/pipeline to bytes (loadable with :func:`loads`)."""
    return pickle.dumps(model, protocol=PICKLE_PROTOCOL)


def loads(bytes_object: bytes) -> Any:
    """Load a model from bytes produced by :func:`dumps`."""
    return pickle.loads(bytes_object)


def metadata_path(source_dir: Union[os.PathLike, str]) -> Optional[str]:
    """Locate metadata.json in ``source_dir`` or one directory above."""
    possible_paths = [
        os.path.join(source_dir, "metadata.json"),
        os.path.join(source_dir, "..", "metadata.json"),
    ]
    return next((p for p in possible_paths if os.path.exists(p)), None)


def load_metadata(source_dir: Union[os.PathLike, str]) -> dict:
    """Load metadata.json saved next to a dumped model."""
    path = metadata_path(source_dir)
    if path is None:
        raise FileNotFoundError(
            f"Metadata file in source dir: '{source_dir}' not found in or up one directory."
        )
    with open(path, "r") as f:
        return simplejson.load(f)


def load(source_dir: Union[os.PathLike, str]) -> Any:
    """Load a model dumped by :func:`dump`."""
    with open(os.path.join(source_dir, "model.pkl"), "rb") as f:
        return pickle.load(f)


def _atomic_write(final: str, write_fn, mode: str) -> None:
    """temp + rename with a UNIQUE temp name: two concurrent writers (a
    retried pod overlapping a live one, dumping the same machine) must not
    share a tmp path — a fixed name would let the rename promote the other
    writer's partial bytes. The temp is cleaned up on failure."""
    import tempfile

    fd, tmp = tempfile.mkstemp(
        dir=os.path.dirname(final), prefix=os.path.basename(final) + ".tmp-"
    )
    # mkstemp creates 0600 and os.replace keeps that mode — restore the
    # umask-derived permissions a plain open() would have given, or a
    # server running as a different user can no longer read the artifact
    umask = os.umask(0)
    os.umask(umask)
    os.fchmod(fd, 0o666 & ~umask)
    try:
        with os.fdopen(fd, mode) as f:
            write_fn(f)
        os.replace(tmp, final)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def dump_metadata(dest_dir: Union[os.PathLike, str], metadata: dict) -> None:
    """Write ``metadata.json`` atomically (temp + rename): an artifact whose
    registry entry already exists must never be observable half-written —
    a crashed fleet build resumes by loading exactly these files."""
    os.makedirs(dest_dir, exist_ok=True)
    _atomic_write(
        os.path.join(dest_dir, "metadata.json"),
        lambda f: simplejson.dump(metadata, f, default=str),
        "w",
    )


def dump(obj: object, dest_dir: Union[os.PathLike, str], metadata: dict = None):
    """Serialize ``obj`` (and optional metadata) into ``dest_dir``.

    The pickle is written atomically (temp + rename) like the metadata: a
    crash mid-write must never leave a truncated ``model.pkl`` at a path a
    registry entry or server revision already points to."""
    os.makedirs(dest_dir, exist_ok=True)
    _atomic_write(
        os.path.join(dest_dir, "model.pkl"),
        lambda f: pickle.dump(obj, f, protocol=PICKLE_PROTOCOL),
        "wb",
    )
    if metadata is not None:
        dump_metadata(dest_dir, metadata)
