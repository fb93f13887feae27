"""Device seconds of the traced discovery by named scope.

The program wraps the sub-phases of its jitted stages in `jax.named_scope`
under fixed names (`rdfind_tpu.obs.tracer.DEVICE_SCOPES`), so every HLO op
carries its scope in the op-name path that the profiler keeps as a stat of
the op's event or of the event's metadata (`OP_NAME_STATS`).  Within the
harness's window span (`trace.WINDOW_SPAN`), each device plane's "XLA Ops"
events are read straight from the trace file's XSpace bytes (the profiler's
Python view does not expose the metadata stats), and each instant of an op
goes to the op that started last among those running then (the innermost,
where ops nest), so no device time counts twice; an op counts for the first
of the scope names on its op-name path.  The seconds are summed over the
device planes.

`run.py` leaves the traced discovery's `.xplane.pb` under
`benchmark/.data/trace/<cell>/xplane` until the next traced run of the
cell; the reduction is cached once per file, so the metrics that read it
cost one load.  A program without the scope names (one older than them)
reads nothing: every metric is then None, never an error.
"""

from __future__ import annotations

import functools
import gzip
import heapq
import os

import numpy as np

from benchmark import trace

TRACE_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          ".data", "trace")
# The stat that holds an op's `jit(...)/<scope>/.../<op>` path.
OP_NAME_STATS = ("tf_op",)


def scope_names() -> tuple | None:
    """The program's device scope names, or None for a program without."""
    try:
        from rdfind_tpu.obs.tracer import DEVICE_SCOPES
    except ImportError:
        return None
    return tuple(DEVICE_SCOPES)


def trace_file(cell: str) -> str | None:
    """The `.xplane.pb` of the cell's last traced discovery, if any."""
    for dirpath, _, files in os.walk(os.path.join(TRACE_ROOT, cell)):
        for name in sorted(files):
            if name.endswith(".xplane.pb"):
                return os.path.join(dirpath, name)
    return None


def read(run, scope: str):
    """Device seconds under `scope` in the run's traced discovery, or None
    when nothing ran under it."""
    scopes = scope_names()
    if run.trace is None or scopes is None or scope not in scopes:
        return None
    path = trace_file(run.cell["name"])
    if path is None:
        return None
    seconds = float(by_scope(path, scopes).get(scope, 0.0))
    return seconds if seconds > 0 else None


# -- XSpace wire format (tsl/profiler/protobuf/xplane.proto) ------------------

def _varint(buf, i: int):
    result = shift = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf, lo: int, hi: int):
    """(field number, value) of one message; a length-delimited value is
    its (start, end) in `buf`, fixed-width values are skipped."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield field, value
        elif wire == 2:
            n, i = _varint(buf, i)
            yield field, (i, i + n)
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _stats(buf, spans) -> list:
    """[(stat metadata id, str value or ("ref", id))] of XStat messages."""
    out = []
    for span in spans:
        mid, value = None, None
        for f, v in _fields(buf, *span):
            if f == 1:
                mid = v
            elif f == 5:
                value = _text(buf, v)
            elif f == 7:
                value = ("ref", v)
        if mid is not None and value is not None:
            out.append((mid, value))
    return out


def _map_entries(buf, spans):
    """(key, value span) of protobuf map entries."""
    for span in spans:
        key = value = None
        for f, v in _fields(buf, *span):
            if f == 1:
                key = v
            elif f == 2:
                value = v
        if key is not None and value is not None:
            yield key, value


def device_ops(buf):
    """{device plane name: (starts_ns, ends_ns, event names, op paths)} of
    each TPU plane's "XLA Ops" events, the path from `OP_NAME_STATS` on the
    event or its metadata ("" when neither has it)."""
    out = {}
    for f, span in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name = next((_text(buf, v) for g, v in _fields(buf, *span)
                     if g == 2), "")
        if not trace._DEVICE_PLANE.match(name):
            continue
        fields: dict = {}
        for g, v in _fields(buf, *span):
            fields.setdefault(g, []).append(v)
        stat_names = {}
        for key, value in _map_entries(buf, fields.get(5, [])):
            for g, v in _fields(buf, *value):
                if g == 2:
                    stat_names[key] = _text(buf, v)
        meta = {}  # metadata id -> (event name, op path)
        for key, value in _map_entries(buf, fields.get(4, [])):
            ev_name, stat_spans = "", []
            for g, v in _fields(buf, *value):
                if g == 2:
                    ev_name = _text(buf, v)
                elif g == 5:
                    stat_spans.append(v)
            meta[key] = (ev_name, _op_path(_stats(buf, stat_spans),
                                           stat_names))
        for line in fields.get(3, []):
            lf: dict = {}
            for g, v in _fields(buf, *line):
                lf.setdefault(g, []).append(v)
            if "".join(_text(buf, s) for s in lf.get(2, [])) != trace.OPS_LINE:
                continue
            base_ps = lf.get(3, [0])[0] * 1000
            starts, ends, names, paths = [], [], [], []
            for ev in lf.get(4, []):
                mid = offset = duration = 0
                stat_spans = []
                for g, v in _fields(buf, *ev):
                    if g == 1:
                        mid = v
                    elif g == 2:
                        offset = v
                    elif g == 3:
                        duration = v
                    elif g == 4:
                        stat_spans.append(v)
                ev_name, path = meta.get(mid, ("", ""))
                own = _op_path(_stats(buf, stat_spans), stat_names)
                starts.append((base_ps + offset) / 1000.0)
                ends.append((base_ps + offset + duration) / 1000.0)
                names.append(ev_name)
                paths.append(own or path)
            out[name] = (np.asarray(starts, np.float64),
                         np.asarray(ends, np.float64), names, paths)
    return out


def _op_path(stats, stat_names) -> str:
    for mid, value in stats:
        if stat_names.get(mid) in OP_NAME_STATS:
            if isinstance(value, tuple):
                return stat_names.get(value[1], "")
            return value
    return ""


# -- the reduction -----------------------------------------------------------

def _latest_start_wins(starts, ends, labels) -> dict:
    """Seconds per label over the union of [start, end) intervals (ns), each
    instant going to the interval that started last among those covering
    it."""
    out: dict = {}
    if len(starts) == 0:
        return out
    order = np.argsort(starts, kind="stable")
    bounds = np.unique(np.r_[starts, ends])
    heap: list = []
    j = 0
    for a, b in zip(bounds[:-1], bounds[1:]):
        while j < len(order) and starts[order[j]] <= a:
            k = order[j]
            heapq.heappush(heap, (-starts[k], -k, ends[k], labels[k]))
            j += 1
        while heap and heap[0][2] <= a:
            heapq.heappop(heap)
        if heap:
            label = heap[0][3]
            out[label] = out.get(label, 0.0) + (b - a) * 1e-9
    return out


def scope_of(path: str, scopes) -> str | None:
    for part in path.split("/"):
        if part in scopes:
            return part
    return None


def _raw(path: str) -> bytes:
    if path.endswith(".pbtxt.gz"):
        from jax.profiler import ProfileData

        with gzip.open(path, "rt") as f:
            return ProfileData.text_proto_to_serialized_xspace(f.read())
    with open(path, "rb") as f:
        return f.read()


@functools.lru_cache(maxsize=None)
def by_scope(path: str, scopes: tuple) -> dict:
    """{scope: device seconds} in the window of the trace at `path`, summed
    over the device planes; ops under none of `scopes` are left out."""
    lo, hi, _ = trace._window(list(trace.load(path).planes))
    total: dict = {}
    for starts, ends, _, paths in device_ops(memoryview(_raw(path))).values():
        keep = (ends > lo) & (starts < hi)
        labels = [scope_of(p, scopes) for p, k in zip(paths, keep) if k]
        got = _latest_start_wins(np.maximum(starts[keep], lo),
                                 np.minimum(ends[keep], hi), labels)
        for label, s in got.items():
            if label is not None:
                total[label] = total.get(label, 0.0) + s
    return total
