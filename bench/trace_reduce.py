"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

The events are read with ``jax.profiler.ProfileData``:

* device planes are those named ``/device:TPU:<n>``; their ``XLA Ops`` line
  holds one event per operation run on the device, named by its HLO
  instruction, and ``XLA Modules`` one per program;
* the benchmark's own host spans are the ``TraceAnnotation`` events named
  ``bench.*`` on the host plane; ``bench.window`` bounds the window.

``ProfileData`` does not show the stats that the trace keeps on each event's
metadata, where the HLO op name (``jit(f)/scope/...``, the ``tf_op`` stat)
lives, so :func:`op_names` reads those from the file's protobuf encoding.

From these: the busy time (the union of the device's op intervals inside the
window, averaged over the chips used), device time by scope (an op belongs
to a scope when the scope's name appears in its HLO instruction or its HLO
op name; a kernel is found by its name the same way) and by program, and the
idle gaps between ops, each attributed to the innermost ``bench.*`` span
open at the gap's midpoint.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
from collections import defaultdict
from typing import Dict, List, Tuple

import jax
import numpy as np

mark = jax.profiler.TraceAnnotation

WINDOW = "bench.window"
TOP = 10
# an HLO instruction that runs others: the trace shows it and, inside its
# interval, each op it runs, so sums by op leave it out
CONTAINER = re.compile(r" (while|conditional|call)\(")


# ---------------------------------------------------------------------------
# the protobuf encoding of an XSpace, as far as the op names need it
# (tsl/profiler/protobuf/xplane.proto: XSpace.planes = 1; XPlane.name = 2,
# .event_metadata = 4, .stat_metadata = 5, both maps of id -> message in
# field 2 of each entry; XEventMetadata.name = 2, .stats = 5;
# XStatMetadata.id = 1, .name = 2; XStat.metadata_id = 1, .str_value = 5,
# .ref_value = 7, which names a stat metadata entry holding the string)
# ---------------------------------------------------------------------------

def _varint(b: bytes, i: int):
    v = shift = 0
    while True:
        c = b[i]
        i += 1
        v |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return v, i


def _fields(b: bytes):
    """``(field number, value)`` of each field of one encoded message."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 1:
            v, i = b[i:i + 8], i + 8
        elif kind == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif kind == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {kind} in an XSpace")
        yield key >> 3, v


def _map_values(entry: bytes):
    return [v for f, v in _fields(entry) if f == 2]


def op_names(data: bytes, stat: str = "tf_op") -> Dict[str, Dict[str, str]]:
    """``{device plane: {event name: HLO op name}}`` from an encoded XSpace:
    the string stat ``stat`` of each device event's metadata."""
    out = {}
    for field, plane in _fields(data):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = v.decode()
            elif f == 4:
                events.extend(_map_values(v))
            elif f == 5:
                for m in _map_values(v):
                    d = dict(_fields(m))
                    stat_names[d.get(1, 0)] = d.get(2, b"").decode()
        if not name.startswith("/device:"):
            continue
        wanted = {k for k, s in stat_names.items() if s == stat}
        found = out.setdefault(name, {})
        for ev in events:
            ename, value = "", None
            for f, v in _fields(ev):
                if f == 2:
                    ename = v.decode()
                elif f == 5:
                    st = dict(_fields(v))
                    if st.get(1) in wanted:
                        value = (st[5].decode() if 5 in st
                                 else stat_names.get(st.get(7)))
            if value is not None:
                found[ename] = value
    return out


def _union(starts, ends):
    """Merge the intervals ``[starts[i], ends[i]]`` that overlap or touch;
    returns the merged starts and ends, in order."""
    starts, ends = np.asarray(starts, float), np.asarray(ends, float)
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    starts, reach = starts[order], np.maximum.accumulate(ends[order])
    first = np.ones(starts.size, bool)
    first[1:] = starts[1:] > reach[:-1]
    heads = np.flatnonzero(first)
    return starts[heads], reach[np.r_[heads[1:] - 1, starts.size - 1]]


def _op_label(name: str, text: str) -> str:
    """Instruction name, result type and HLO op name: short enough for the
    breakdown, and enough to find the op in the program."""
    head, _, rest = name.partition(" = ")
    op_name = text.partition("\n")[2]
    return " ".join(x for x in (head, rest.split(" ", 1)[0], op_name) if x)


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    op_s: Dict[str, float]               # device seconds of each HLO
                                         # instruction in the window, summed
                                         # over chips; containers left out
    op_text: Dict[str, str]              # each instruction and its HLO op name
    modules: Dict[str, Tuple[int, float]]
    gaps: List[Tuple[str, float]]        # (host span, seconds), longest first
    devices: int

    def _sum(self, keep) -> float:
        return sum(s for name, s in self.op_s.items()
                   if keep(name)) / self.devices

    def scope_s(self, scope: str) -> float:
        """Device seconds of ops under ``scope`` (by HLO instruction or op
        name), per chip."""
        return self._sum(lambda name: scope in self.op_text[name])

    def kernel_s(self, kernel: str) -> float:
        """Device seconds of the HLO instructions named ``kernel`` (a Pallas
        kernel's custom call is named for the kernel), per chip."""
        return self._sum(lambda name: name.startswith("%" + kernel + "."))

    def module_s(self, fragment: str) -> Tuple[int, float]:
        """(calls, device seconds per chip) of programs named ``fragment``."""
        n, s = 0, 0.0
        for name, (calls, secs) in self.modules.items():
            if fragment in name:
                n += calls
                s += secs
        return n, s / self.devices

    def breakdown(self) -> dict:
        by_label = defaultdict(float)
        for name, s in self.op_s.items():
            by_label[_op_label(name, self.op_text[name])] += s / self.devices
        top = sorted(by_label.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.gaps[:TOP]]}


def _device_planes(pd):
    return [p for p in pd.planes if p.name.startswith("/device:TPU:")]


def reduce(path: str, devices: int = 1) -> TraceSummary:
    """Reduce one ``.xplane.pb`` file."""
    with open(path, "rb") as f:
        return reduce_bytes(f.read(), devices)


def reduce_bytes(data: bytes, devices: int = 1) -> TraceSummary:
    """Reduce an encoded XSpace."""
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_serialized_xspace(data), devices,
                          op_names(data))


def _clipped(events, w0: float, w1: float):
    for ev in events:
        s = max(ev.start_ns * 1e-9, w0)
        e = min((ev.start_ns + ev.duration_ns) * 1e-9, w1)
        if e > s:
            yield ev, s, e


def reduce_profile(pd, devices: int = 1,
                   names: Dict[str, Dict[str, str]] | None = None
                   ) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData`` over the first ``devices``
    device planes; ``names`` gives each plane's HLO op names
    (:func:`op_names`).  Ops are summed by name as they are read, since a
    window of the certification cell holds millions of them."""
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        s = ev.start_ns * 1e-9
                        spans.append((ev.name, s, s + ev.duration_ns * 1e-9))
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW} span in the trace")
    w0, w1 = windows[0]

    planes = _device_planes(pd)[:devices]
    op_s, op_text, containers = defaultdict(float), {}, set()
    modules = defaultdict(lambda: [0, 0.0])
    busy, gaps = 0.0, {}
    for k, plane in enumerate(planes):
        hlo = (names or {}).get(plane.name, {})
        starts, ends = [], []
        for line in plane.lines:
            if line.name == "XLA Ops":
                for ev, s, e in _clipped(line.events, w0, w1):
                    starts.append(s)
                    ends.append(e)
                    name = ev.name
                    if name not in op_text:
                        op_text[name] = name + "\n" + hlo.get(name, "")
                        if CONTAINER.search(name):
                            containers.add(name)
                    if name not in containers:
                        op_s[name] += e - s
            elif line.name == "XLA Modules":
                for ev, s, e in _clipped(line.events, w0, w1):
                    modules[ev.name][0] += 1
                    modules[ev.name][1] += e - s
        ms, me = _union(starts, ends)
        busy += float(np.sum(me - ms))
        if k == 0:                     # gaps of the first chip
            gs, ge = np.r_[w0, me], np.r_[ms, w1]
            idle = ge > gs
            gaps = _by_span(spans, gs[idle], ge[idle])
    n = max(len(planes), 1)
    return TraceSummary(window_s=w1 - w0, busy_s=busy / n, op_s=dict(op_s),
                        op_text=op_text,
                        modules={k: (v[0], v[1]) for k, v in modules.items()},
                        gaps=sorted(gaps.items(), key=lambda kv: -kv[1]),
                        devices=n)


def _by_span(spans, starts, ends) -> Dict[str, float]:
    """Idle seconds by the innermost ``bench.*`` span (other than the
    window) open at each gap's midpoint; ``outside_spans`` where none is."""
    mid = 0.5 * (starts + ends)
    order = np.argsort(mid)
    sorted_mid = mid[order]
    label = np.full(mid.size, -1)
    width = np.full(mid.size, np.inf)
    named = [(n, s, e) for n, s, e in spans if n != WINDOW]
    for i, (_, s, e) in enumerate(named):
        idx = order[np.searchsorted(sorted_mid, s, side="left"):
                    np.searchsorted(sorted_mid, e, side="right")]
        inner = idx[width[idx] > e - s]
        label[inner] = i
        width[inner] = e - s
    out = defaultdict(float)
    for i in np.unique(label):
        name = named[i][0] if i >= 0 else "outside_spans"
        out[name] += float(np.sum((ends - starts)[label == i]))
    return dict(out)


def find_xplane(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise ValueError(f"no .xplane.pb under {directory}")
    return sorted(found)[-1]


def reduce_dir(directory: str, devices: int = 1) -> TraceSummary:
    return reduce(find_xplane(directory), devices)


def remove_dir(directory: str) -> None:
    shutil.rmtree(directory, ignore_errors=True)
