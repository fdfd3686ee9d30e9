"""
Numpy-native serving codec: the hot-path decode/encode fast lane.

Most of an anomaly POST's latency used to be host-side JSON→pandas→JSON
work, not compute. This module short-circuits that work for the canonical
request/response shapes while guaranteeing **byte-identical JSON** to the
pandas path (asserted by tests/gordo_tpu/test_fast_codec.py):

- decode: a rectangular ``X`` (list-of-lists) or a flat column dict
  (``{tag: {key: value}}`` — :func:`server.utils.dataframe_to_dict` output)
  parses straight into one contiguous float64 ndarray with single-pass
  shape validation; no ``pd.DataFrame.from_dict``, no ``pd.concat``.
  Multi-level / ragged / non-numeric payloads return ``None`` and take the
  pandas path unchanged.
- encode: a response frame (or an unassembled ``RawFrame`` straight off
  the model, via :func:`encode_raw`) serializes off its numeric blocks —
  the nested response dict is built with the exact ``dataframe_to_dict``
  idioms (shared key list, NaN/Inf → ``None`` via one vectorized
  ``np.isfinite`` pass) and emitted in one C ``json.dumps`` call, instead
  of ``to_numpy(dtype=object)`` + a recursive sanitize + generic dumps.

Gate: ``GORDO_TPU_FAST_CODEC`` (default **on**; ``0`` restores the pandas
path exactly). Per-request override: ``X-Gordo-Codec: pandas|fast`` header
(honored only while the env gate is on) — this is what gives
``benchmarks/load_test.py --codec`` a server-side A/B without a redeploy.
Usage is counted by ``gordo_server_fast_codec_total`` /
``gordo_server_fast_codec_fallback_total`` (bridged into ``/metrics``).
"""

import functools
import json
import logging
import os
from typing import List, Optional

import dateutil.parser
import numpy as np
import pandas as pd

from gordo_tpu import native
from gordo_tpu.models.utils import timestamp_columns

logger = logging.getLogger(__name__)

# json.dumps' own key/string escaper (C speed, ensure_ascii semantics) —
# used to render template keys byte-identically to the dict path
_escape = json.encoder.encode_basestring_ascii

try:  # pragma: no cover - environment-dependent
    from orjson import loads as _loads
except ImportError:
    _loads = json.loads

_dumps = json.dumps


def loads(body):
    """Parse a JSON request body straight off the socket buffer —
    orjson when importable, the stdlib C decoder otherwise. Accepts
    bytes/bytearray/memoryview/str; raises ``ValueError`` on malformed
    JSON (``orjson.JSONDecodeError`` and ``json.JSONDecodeError`` are
    both ValueError subclasses). The fast lane (server/fastlane.py) uses
    this so a request body is parsed exactly once, with no intermediate
    werkzeug Request object.

    Byte-parity guard: orjson rejects the non-standard ``NaN`` /
    ``Infinity`` literals the stdlib decoder (and therefore the WSGI
    lane) accepts — on an orjson parse error the stdlib decoder gets the
    final word, so both lanes accept exactly the same payloads."""
    if _loads is json.loads:
        return _loads(body)
    try:
        return _loads(body)
    except ValueError:
        if isinstance(body, memoryview):
            body = bytes(body)
        return json.loads(body)


def enabled() -> bool:
    """The process-level gate: ``GORDO_TPU_FAST_CODEC`` unset/``1`` = on."""
    return os.environ.get("GORDO_TPU_FAST_CODEC", "1").lower() not in (
        "0",
        "false",
        "no",
    )


def request_enabled(request) -> bool:
    """Whether THIS request takes the fast lane: the env gate, minus a
    per-request ``X-Gordo-Codec: pandas`` opt-out (the load-test A/B
    switch). ``GORDO_TPU_FAST_CODEC=0`` is absolute — the header cannot
    re-enable a disabled codec."""
    if not enabled():
        return False
    return request.headers.get("X-Gordo-Codec", "").lower() != "pandas"


# ------------------------------------------------------------------- decode
def _parse_index(keys: List[str]) -> Optional[pd.Index]:
    """The exact index-coercion chain of ``dataframe_from_dict`` (bulk
    ISO8601 → per-element isoparse → int), so fast- and pandas-decoded
    frames carry interchangeable indexes."""
    idx = pd.Index(keys)
    try:
        return pd.to_datetime(idx, format="ISO8601")
    except (TypeError, ValueError):
        pass
    try:
        return idx.map(dateutil.parser.isoparse)
    except (TypeError, ValueError):
        pass
    try:
        return idx.map(int)
    except (TypeError, ValueError):
        return None


def decode_dataframe(data) -> Optional[pd.DataFrame]:
    """Parse a canonical payload into a DataFrame via one contiguous
    float64 ndarray; ``None`` means "not canonical — use the pandas path".

    Canonical shapes: a rectangular list-of-lists (row-major), or a flat
    dict of columns ``{name: {index_key: value}}`` whose columns share one
    key sequence. ``null`` cells become NaN exactly like pandas.
    """
    if isinstance(data, list):
        try:
            arr = np.asarray(data, dtype=np.float64)
        except (TypeError, ValueError):
            return None
        if arr.ndim != 2 or arr.shape[0] == 0:
            return None
        # RangeIndex here vs the pandas path's int64 Index: identical keys
        # ("0".."n-1") on the wire, identical .values for the model
        return pd.DataFrame(arr)
    if not isinstance(data, dict) or not data:
        return None
    first_keys: Optional[list] = None
    columns = []
    for name, col in data.items():
        if not isinstance(col, dict) or not col:
            return None
        if first_keys is None:
            first_keys = list(col)
        elif len(col) != len(first_keys) or list(col) != first_keys:
            # ragged / reordered columns: pandas aligns these by label —
            # genuinely irregular, not worth mirroring here
            return None
        try:
            values = np.array(list(col.values()), dtype=np.float64)
        except (TypeError, ValueError):
            # non-numeric cells, or nested dicts (a multi-level payload)
            return None
        if values.ndim != 1:
            return None
        columns.append(values)
    index = _parse_index(first_keys)
    if index is None:
        return None
    frame = pd.DataFrame(
        np.column_stack(columns), index=index, columns=list(data), copy=False
    )
    if not frame.index.is_monotonic_increasing:
        frame.sort_index(inplace=True)
    return frame


def decode_body_xy(body):
    """One native pass over a raw request body straight into float64
    DataFrames — no ``json.loads``, no intermediate lists. Two canonical
    grammars: the rect shape ``{"X": [[...]]}`` / ``{"X": ..., "y": ...}``
    (RangeIndex frames, exactly what ``decode_dataframe`` yields for
    list-of-lists payloads) and the flat column-dict shape
    ``{"X": {name: {key: num}}}`` (the frame ``decode_dataframe`` yields
    for dict payloads: parsed index, payload column order, sorted when
    non-monotonic). Returns ``(X, y_or_None)`` or ``None`` when the body
    matches neither strict grammar — the caller then goes through
    ``loads`` + ``decode_dataframe``, which is always parity-safe."""
    if not isinstance(body, (bytes, bytearray, memoryview)):
        return None
    if not isinstance(body, bytes):
        body = bytes(body)
    parsed = native.parse_xy(body)
    if parsed is not None:
        X_arr, y_arr = parsed
        X = pd.DataFrame(X_arr)
        y = pd.DataFrame(y_arr) if y_arr is not None else None
        return X, y
    cols = native.parse_columns(body)
    if cols is None:
        return None
    arr, names, keys = cols
    index = _parse_index(keys)
    if index is None:
        # decode_dataframe would bail to the pandas path here too
        return None
    X = pd.DataFrame(arr, index=index, columns=names, copy=False)
    if not X.index.is_monotonic_increasing:
        X.sort_index(inplace=True)
    return X, None


# ------------------------------------------------------------------- encode
#
# Encoding builds the exact nested dict ``dataframe_to_dict`` would build
# (same setdefault/zip idioms, NaN/Inf pre-substituted with None) and hands
# it to the stdlib C encoder in ONE ``json.dumps`` call — measured faster
# than stitching per-column fragments in Python, and byte-parity with
# ``simplejson.dumps(..., ignore_nan=True)`` holds by construction: both
# encoders emit identical separators, float reprs, and key coercions for
# str/int keys and float/int/bool/str/None leaves. Column values come off
# the frame's numeric blocks (or a RawFrame's raw blocks) via ``tolist``,
# never through an object-dtype conversion.


def _is_key(value) -> bool:
    kind = type(value)
    return kind is str or kind is int


@functools.lru_cache(maxsize=64)
def _range_keys(n: int) -> tuple:
    """Pre-stringified "0".."n-1" index keys: every RangeIndex response of
    n rows shares one tuple, and str keys dump measurably faster than the
    encoder's int-key coercion (identical bytes either way)."""
    return tuple(str(i) for i in range(n))


def _index_keys(index: pd.Index) -> Optional[list]:
    """Row keys exactly as ``dataframe_to_dict`` derives them."""
    if isinstance(index, pd.DatetimeIndex):
        return index.astype(str).tolist()
    if isinstance(index, pd.RangeIndex) and index.start == 0 and index.step == 1:
        return _range_keys(len(index))
    keys = index.tolist()
    for key in keys:
        if not _is_key(key):
            return None
    return keys


def _float_columns(values: np.ndarray) -> list:
    """Column lists off a (n_cols, n_rows) float block, non-finite cells
    replaced by None (simplejson ``ignore_nan`` serializes NaN/Inf as
    null; the C json encoder would emit invalid bare literals)."""
    finite = np.isfinite(values)
    if finite.all():
        return values.tolist()
    return [
        [v if ok else None for v, ok in zip(col, fin)]
        for col, fin in zip(values.tolist(), finite.tolist())
    ]


def _column_lists(df: pd.DataFrame) -> Optional[list]:
    """Per-column Python value lists, in column order, straight off the
    frame's blocks (no object-dtype conversion)."""
    cols: list = [None] * df.shape[1]
    for block in df._mgr.blocks:
        values = block.values
        if not isinstance(values, np.ndarray):
            return None  # extension arrays: pandas path handles them
        kind = values.dtype.kind
        positions = block.mgr_locs.as_array
        if kind == "f":
            for pos, col in zip(positions, _float_columns(values)):
                cols[pos] = col
        elif kind in "iub":
            for pos, col in zip(positions, values.tolist()):
                cols[pos] = col
        elif kind == "O":
            rows = values.tolist()
            for pos, col in zip(positions, rows):
                for v in col:
                    if v is not None and type(v) is not str:
                        return None  # arbitrary objects: pandas path
                cols[pos] = col
        else:
            return None  # datetime64 / timedelta / anything exotic
    return cols


def encode_dataframe(df: pd.DataFrame) -> Optional[str]:
    """The ``"data"`` JSON fragment — byte-identical to
    ``simplejson.dumps(dataframe_to_dict(df), ignore_nan=True)`` — or
    ``None`` when the frame isn't fast-serializable (the caller then takes
    the pandas path, which is always correct)."""
    try:
        index = df.index
        if len(index) == 0 or not index.is_unique or not df.columns.is_unique:
            # dict(zip(...)) / setdefault deduplicate repeated keys;
            # mirroring that here isn't worth it for a degenerate frame
            return None
        keys = _index_keys(index)
        if keys is None:
            return None
        cols = _column_lists(df)
        if cols is None:
            return None
        payload: dict = {}
        if isinstance(df.columns, pd.MultiIndex):
            for (top, sub), col in zip(df.columns, cols):
                if not _is_key(top) or not _is_key(sub):
                    return None
                payload.setdefault(top, {})[sub] = dict(zip(keys, col))
        else:
            for name, col in zip(df.columns, cols):
                if not _is_key(name):
                    return None
                payload[name] = dict(zip(keys, col))
        return _dumps(payload)
    except Exception:  # noqa: BLE001 — the fallback is always correct;
        # a fast-path crash must degrade to the pandas path, not a 500
        logger.debug("fast-codec encode bailed", exc_info=True)
        return None


def encode_raw(raw) -> Optional[str]:
    """``encode_dataframe`` for an unassembled :class:`models.utils.RawFrame`:
    the same ``"data"`` fragment, produced without ever building the pandas
    frame (byte-identical to ``encode_dataframe(raw.to_pandas())`` —
    asserted by tests/gordo_tpu/test_fast_codec.py). ``None`` falls back to
    the assembled path.

    For the canonical all-float RangeIndex response the fragment is
    rendered by the native template encoder (:func:`_encode_raw_native`) —
    precomputed JSON structure interleaved with CPython-repr-formatted
    doubles in C — cutting the dominant ``json.dumps`` cost. Everything
    else takes the pure-Python dict + ``json.dumps`` path below."""
    try:
        index = raw.index
        if not isinstance(index, pd.Index):
            index = pd.Index(index)
        if len(index) == 0 or not index.is_unique:
            return None
        keys = _index_keys(index)
        if keys is None:
            return None
        if not _native_poisoned:
            fragment = _encode_raw_native(raw, index, keys)
            if fragment is not None:
                return fragment
        return _encode_raw_python(raw, index, keys)
    except Exception:  # noqa: BLE001 — same degrade-don't-500 contract
        logger.debug("fast-codec raw encode bailed", exc_info=True)
        return None


def _encode_raw_python(raw, index: pd.Index, keys: list) -> Optional[str]:
    """The dict-building + one-shot ``json.dumps`` raw encode path (also
    the parity oracle for the native template encoder's self-check)."""
    start, end = timestamp_columns(index, raw.frequency)
    # the assembled frame carries ("start", "") / ("end", "") tuples,
    # so the dict path nests them under an empty sub-key
    payload: dict = {
        "start": {"": dict(zip(keys, start))},
        "end": {"": dict(zip(keys, end))},
    }
    for top, subs, values in raw.groups:
        if not _is_key(top):
            return None
        if len(subs) == 0 and values.shape[1] == 0:
            # a zero-column group contributes no columns to the assembled
            # frame, so its top-level key never appears in the dict path
            continue
        kind = values.dtype.kind
        if kind == "f":
            group_cols = _float_columns(values.T)
        elif kind in "iub":
            group_cols = values.T.tolist()
        else:
            return None
        if len(group_cols) != len(subs):
            return None
        group = payload.setdefault(top, {})
        for sub, col in zip(subs, group_cols):
            if not _is_key(sub):
                return None
            group[sub] = dict(zip(keys, col))
    return _dumps(payload)


# ------------------------------------------------------- native template path
#
# A serving model emits the same response STRUCTURE on every request — same
# groups, same column names, same row count, RangeIndex — only the float
# values change. So all the JSON structure (braces, keys, the all-null
# start/end time columns) is precomputed once per (group-structure, n_rows)
# as a byte template with a value slot per float, and the native kernel
# interleaves template chunks with repr-formatted doubles
# (PyOS_double_to_string — CPython's own formatter, so bytes match
# json.dumps by construction; NaN/Inf render as null, matching the
# ignore_nan substitution). Guard rails: the first render of each template
# is compared byte-for-byte against the pure-Python path, and any mismatch
# permanently poisons the native encoder for the process.

_native_checked: set = set()
_native_poisoned = False


def _build_template(sig: tuple, keys: tuple, start, end):
    """(template bytes, per-value chunk lengths) for group structure
    ``sig = ((top, (sub, ...)), ...)`` over pre-stringified row ``keys``.
    ``start``/``end`` are the timestamp-column value lists (``None`` =
    all-null, the RangeIndex case) — they are static per request, so they
    live in the template; only the float values go through the C
    formatter."""
    esc_keys = [_escape(k) for k in keys]

    def _obj(col) -> str:
        if col is None:
            return "{" + ", ".join(f"{ek}: null" for ek in esc_keys) + "}"
        return "{" + ", ".join(
            f"{ek}: " + ("null" if v is None else _escape(v))
            for ek, v in zip(esc_keys, col)
        ) + "}"

    chunks: list = []  # static text; chunks[i] precedes value i
    cur = [f'{{"start": {{"": {_obj(start)}}}, "end": {{"": {_obj(end)}}}']
    for top, subs in sig:
        cur.append(f", {_escape(top)}: {{")
        for j, sub in enumerate(subs):
            if j:
                cur.append(", ")
            cur.append(f"{_escape(sub)}: {{")
            for i, ek in enumerate(esc_keys):
                if i:
                    cur.append(", ")
                cur.append(f"{ek}: ")
                chunks.append("".join(cur))
                cur = []
            cur.append("}")
        cur.append("}")
    cur.append("}")
    chunks.append("".join(cur))  # trailing chunk after the last value
    byte_chunks = [c.encode("ascii") for c in chunks]
    template = b"".join(byte_chunks)
    pre_lens = np.array([len(c) for c in byte_chunks], dtype=np.int32)
    return template, pre_lens


@functools.lru_cache(maxsize=32)
def _native_template(sig: tuple, n: int):
    """Cached ``_build_template`` for a RangeIndex(n) response — every
    response of this (structure, n_rows) shares one template. Keyed
    indexes (timestamps) change per request, so those templates are built
    per call in :func:`_encode_raw_native` instead."""
    return _build_template(sig, _range_keys(n), None, None)


def _encode_raw_native(raw, index: pd.Index, keys) -> Optional[str]:
    """Render the fragment via the native template encoder, or ``None``
    when the structure isn't template-able / the library isn't built."""
    global _native_poisoned
    sig_items = []
    blocks = []
    for top, subs, values in raw.groups:
        if type(top) is not str or values.ndim != 2:
            return None
        if len(subs) == 0 and values.shape[1] == 0:
            continue  # dropped by the assembled frame (see Python path)
        if (
            values.dtype.kind != "f"
            or values.shape[1] != len(subs)
            or values.shape[0] != len(index)
            or any(type(sub) is not str for sub in subs)
        ):
            return None
        sig_items.append((top, tuple(subs)))
        blocks.append(values)
    if not sig_items:
        return None
    tops = [item[0] for item in sig_items]
    if len(set(tops)) != len(tops):
        return None  # duplicate groups merge in the dict path; template can't
    if "start" in tops or "end" in tops:
        return None  # would merge into the timestamp columns' dicts
    sig = tuple(sig_items)
    if (
        isinstance(index, pd.RangeIndex)
        and index.start == 0
        and index.step == 1
    ):
        template, pre_lens = _native_template(sig, len(index))
    else:
        # keyed (timestamp) index: keys and start/end values change per
        # request, so the template is built per call — still a win, the
        # n_rows of template text amortize over n_cols of C-formatted
        # float columns
        start, end = timestamp_columns(index, raw.frequency)
        try:
            str_keys = tuple(
                k if type(k) is str else str(k) for k in keys
            )
            template, pre_lens = _build_template(sig, str_keys, start, end)
        except TypeError:
            return None  # non-str-coercible template text: dict path
    # column-major per group: group -> column -> rows, matching the
    # template's key nesting order
    vals = np.concatenate(
        [v.T.astype(np.float64, copy=False).ravel() for v in blocks]
    )
    rendered = native.encode_template(template, pre_lens, vals)
    if rendered is None:
        return None
    fragment = rendered.decode("ascii")
    if (sig, len(index)) not in _native_checked:
        # first render of this template shape: byte-compare against the
        # Python oracle; a mismatch disables the native encoder for good
        _native_checked.add((sig, len(index)))
        expected = _encode_raw_python(raw, index, list(keys))
        if fragment != expected:
            _native_poisoned = True
            logger.error(
                "native template encoder mismatch for %r (n=%d); "
                "disabling native encode for this process",
                tops,
                len(index),
            )
            return None
    return fragment


def splice_response_body(data_fragment: str, rest_json: str) -> str:
    """Assemble ``{"data": <fragment>, <rest...>}`` from the pre-encoded
    data fragment and the (simplejson-encoded) remaining payload fields,
    preserving the exact separators ``json.dumps`` would emit."""
    if rest_json == "{}":
        return '{"data": ' + data_fragment + "}"
    return '{"data": ' + data_fragment + ", " + rest_json[1:]
