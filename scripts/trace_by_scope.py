"""Device self time by named scope: trace_by_scope.py <trace.xplane.pb> [rows]
Charges each ``XLA Ops`` event's self time (a ``while`` keeps what its children
leave) to the innermost scope of docs/observability.md in its ``op_name`` and
to a pass: JAX writes ``jvp(...)`` round a forward under differentiation and
``transpose(jvp(...))`` round its backward; neither is a plain forward. Events
come from ``jax.profiler.ProfileData``; the ``op_name`` is the stat ``tf_op`` of
the event's *metadata* (libtpu 0.0.34), which ProfileData does not expose: that
one map is read off the file's wire format."""
import re
import sys
from collections import defaultdict

SCOPES = ("lstm_input_proj|lstm_cell|lstm_weight_grad|dense|attention|window_gather"
          "|optimizer_update|fold_predict")
_SCOPE = re.compile(r"[/(](%s)(?=[/)])" % SCOPES)
_NOISE = {"body", "cond", "closed_call", "vmap()", "jvp()", "transpose(jvp())"}


def _varint(buf, i):
    value = shift = 0
    while True:
        value |= (buf[i] & 0x7F) << shift
        shift, i = shift + 7, i + 1
        if buf[i - 1] < 0x80:
            return value, i


def _fields(buf):
    """(field number, int | bytes | None for fixed-width) of one message."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        if key & 7 == 0:
            value, i = _varint(buf, i)
        elif key & 7 == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        else:
            value, i = None, i + (8 if key & 7 == 1 else 4)
        yield key >> 3, value


def op_names(path):
    """{event name (the HLO's text): op_name} of the TPU planes. XSpace.planes=1;
    XPlane.name=2, .event_metadata=4, .stat_metadata=5 (maps: key=1, value=2);
    X*Metadata.name=2, .stats=5; XStat.metadata_id=1, .str_value=5, .ref_value=7."""
    out = {}
    for plane in (list(_fields(v)) for n, v in _fields(open(path, "rb").read()) if n == 1):
        tpu = dict(plane)[2].startswith(b"/device:TPU:")
        entries = [(n, dict(_fields(v))) for n, v in plane if n in (4, 5) and tpu]
        stat_name = {e[1]: dict(_fields(e[2]))[2].decode() for n, e in entries if n == 5}
        for meta in (list(_fields(e[2])) for n, e in entries if n == 4):
            for stat in (dict(_fields(v)) for n, v in meta if n == 5):
                if stat_name.get(stat.get(1)) == "tf_op":
                    op_name = stat[5].decode() if 5 in stat else stat_name.get(stat.get(7), "")
                    out[dict(meta)[2].decode()] = op_name
    return out


def classify(event_name, op_name):
    """(innermost scope, or kind @ end of op_name; 'bwd' | 'fwd' | 'plain')."""
    way = "bwd" if "transpose(" in op_name else "fwd" if "jvp(" in op_name else "plain"
    hits = _SCOPE.findall(op_name)
    if hits:
        return hits[-1], way
    kind = re.sub(r"[.\d]*(\.clone)*[.\d]*$", "", event_name.split(" = ")[0].lstrip("%"))
    tail = [p for p in op_name.rstrip(":").split("/")[1:] if p not in _NOISE]
    return f"- {kind} @ {'/'.join(tail[-2:])}", way


def self_seconds(events):  # [(name, start_ns, end_ns)] -> {name: self seconds}
    out, stack = defaultdict(float), []
    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])) + [("", float("inf"), 0)]:
        while stack and stack[-1][2] <= start:
            done, a, b, inner = stack.pop()
            out[done] += (b - a - inner) * 1e-9
            if stack:
                stack[-1][3] += b - a
        stack.append([name, start, end, 0])
    return out


def main(path, rows=30):
    from jax.profiler import ProfileData
    names, table = op_names(path), defaultdict(float)
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines if plane.name.startswith("/device:TPU:") else ():
            if line.name == "XLA Ops":
                events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events if e.duration_ns > 0]
                for name, seconds in self_seconds(events).items():
                    table[classify(name, names.get(name, ""))] += seconds
    total = sum(table.values()) or 1.0
    print(f"{'scope, or - kind @ end of op_name':<70}{'pass':<6}{'self s':>9}{'share':>8}")
    table["total", ""] = total
    for (scope, way), seconds in sorted(table.items(), key=lambda kv: -kv[1])[:int(rows)]:
        print(f"{scope:<70}{way:<6}{seconds:>9.4f}{100 * seconds / total:>7.1f}%")


if __name__ == "__main__":
    main(*sys.argv[1:])
