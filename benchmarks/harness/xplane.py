"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the per-layer metrics read.

Read with ``jax.profiler.ProfileData`` alone. All planes of one trace share a clock, so the host's
ranges (the benchmark's own ``bench.query.<qid>`` spans and the engine's per-operator
``TraceAnnotation`` ranges) can be laid over the device's idle gaps.

    python -m benchmarks.harness.xplane <file.xplane.pb>     # what is in a trace, and its reduction
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass

import numpy as np

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
QUERY_SPAN = "bench.query."
#: idle gaps shorter than this are summed under one name, not attributed one by one
SHORT_GAP_NS = 20_000
#: an operation's name is its whole HLO line in a TPU trace: the breakdown keeps this much of it
NAME_CHARS = 160
#: host events that only say that a thread exists or waits; they would cover every gap
HOST_NOISE = ("ThreadpoolListener::", "$", "Thread")


@dataclass
class Plane:
    name: str
    lines: dict  # line name -> (names list, start_ns array, end_ns array)


def newest_trace(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> list[Plane]:
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            names, starts, ends = [], [], []
            for event in line.events:
                names.append(event.name)
                starts.append(event.start_ns)
                ends.append(event.start_ns + event.duration_ns)
            key, n = line.name, 1
            while key in lines:  # threads may share a name
                n += 1
                key = f"{line.name}#{n}"
            lines[key] = (names, np.asarray(starts, np.float64), np.asarray(ends, np.float64))
        planes.append(Plane(plane.name, lines))
    return planes


def union(starts: np.ndarray, ends: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Merged, sorted intervals covering the same points as the given ones."""
    if len(starts) == 0:
        return np.empty(0), np.empty(0)
    order = np.argsort(starts, kind="stable")
    starts, ends = starts[order], np.maximum.accumulate(ends[order])
    first = np.concatenate(([True], starts[1:] > ends[:-1]))
    last = np.concatenate((first[1:], [True]))
    return starts[first], ends[last]


def clip(starts, ends, lo, hi):
    starts, ends = np.clip(starts, lo, hi), np.clip(ends, lo, hi)
    keep = ends > starts
    return starts[keep], ends[keep]


@dataclass
class Reduced:
    window_s: float
    busy_s: float           # union of device-op intervals in the window, averaged over the device planes
    launches: int           # program executions on the device planes in the window
    queries: int            # bench.query spans in the window
    op_seconds: dict        # device operation -> seconds in the window, summed over the device planes
    gap_seconds: dict       # host range -> idle seconds of the first device that fell under it
    devices: int

    @staticmethod
    def _top(table: dict) -> list:
        return [[name[:NAME_CHARS], seconds] for name, seconds in sorted(table.items(), key=lambda kv: -kv[1])[:10]]

    @property
    def device_ops(self) -> list:
        """[[name, seconds]]: the ten device operations that took most time."""
        return self._top(self.op_seconds)

    @property
    def idle_gaps(self) -> list:
        """[[host range, seconds]]: the device's idle time by what the host was doing, ten largest."""
        return self._top(self.gap_seconds)


def _host_events(planes: list[Plane]):
    names, starts, ends = [], [], []
    for plane in planes:
        if not plane.name.startswith(HOST_PLANE):
            continue
        for line_names, line_starts, line_ends in plane.lines.values():
            for i, name in enumerate(line_names):
                if name.startswith(HOST_NOISE) or line_ends[i] <= line_starts[i]:
                    continue
                names.append(name)
                starts.append(line_starts[i])
                ends.append(line_ends[i])
    return names, np.asarray(starts), np.asarray(ends)


def _with_module(plane: Plane, names: list, starts: np.ndarray) -> list:
    """Each operation's name behind that of the program it ran in: ``jit__filter/%fusion.10 = ...``. An
    operation's own name is an HLO line that says nothing of where it came from; the program's name is the
    jitted function's, without the fingerprint that changes from compile to compile."""
    if OPS_LINE not in plane.lines or MODULES_LINE not in plane.lines:
        return names
    m_names, m_starts, m_ends = plane.lines[MODULES_LINE]
    order = np.argsort(m_starts, kind="stable")
    at = np.searchsorted(m_starts[order], starts, side="right") - 1
    out = []
    for name, start, i in zip(names, starts, at):
        if i >= 0 and m_ends[order[i]] >= start:
            name = f"{m_names[order[i]].split('(')[0]}/{name}"
        out.append(name)
    return out


def reduce(planes: list[Plane]) -> Reduced:
    host_names, host_starts, host_ends = _host_events(planes)
    spans = [i for i, name in enumerate(host_names) if name.startswith(QUERY_SPAN)]
    if not spans:
        raise ValueError(f"the trace holds no {QUERY_SPAN}* span: nothing says where the traced window lies")
    lo, hi = host_starts[spans].min(), host_ends[spans].max()

    device_planes = [p for p in planes if p.name.startswith(DEVICE_PLANE)
                     and (OPS_LINE in p.lines or MODULES_LINE in p.lines)]
    if not device_planes:
        raise ValueError(f"the trace holds no {DEVICE_PLANE}* plane with an {OPS_LINE!r} line")
    busy_ns, launches, op_seconds = [], 0, {}
    gap_starts, gap_ends = np.empty(0), np.empty(0)
    for plane in device_planes:
        names, starts, ends = plane.lines.get(OPS_LINE) or plane.lines[MODULES_LINE]
        names = _with_module(plane, names, starts)
        inside = (ends > lo) & (starts < hi)
        for name, seconds in zip(np.asarray(names, object)[inside],
                                 (np.minimum(ends, hi) - np.maximum(starts, lo))[inside] / 1e9):
            op_seconds[name] = op_seconds.get(name, 0.0) + float(seconds)
        merged_starts, merged_ends = union(*clip(starts, ends, lo, hi))
        busy_ns.append(float((merged_ends - merged_starts).sum()))
        if MODULES_LINE in plane.lines:
            _, m_starts, m_ends = plane.lines[MODULES_LINE]
            launches += int(((m_starts >= lo) & (m_starts < hi)).sum())
        if plane is device_planes[0]:  # gaps are attributed on the first device
            gap_starts = np.concatenate(([lo], merged_ends))
            gap_ends = np.concatenate((merged_starts, [hi]))

    gap_seconds: dict = {}
    lengths = gap_ends - gap_starts
    short = lengths < SHORT_GAP_NS
    if short.any():
        gap_seconds[f"gaps under {SHORT_GAP_NS // 1000} us"] = float(lengths[short].sum() / 1e9)
    host_lengths = host_ends - host_starts
    for g_start, g_end in zip(gap_starts[~short], gap_ends[~short]):
        # the innermost host range that covers most of the gap says what the host was doing in it
        overlap = np.minimum(host_ends, g_end) - np.maximum(host_starts, g_start)
        covering = np.flatnonzero(overlap >= 0.5 * (g_end - g_start))
        if len(covering):
            name = host_names[covering[np.argmin(host_lengths[covering])]]
        else:
            name = "between-queries"
        gap_seconds[name] = gap_seconds.get(name, 0.0) + float((g_end - g_start) / 1e9)

    return Reduced(window_s=float((hi - lo) / 1e9), busy_s=float(np.mean(busy_ns) / 1e9), launches=launches,
                   queries=len(spans), op_seconds=op_seconds, gap_seconds=gap_seconds, devices=len(device_planes))


def describe(planes: list[Plane], limit: int = 12) -> str:
    out = []
    for plane in planes:
        out.append(f"PLANE {plane.name}")
        for line, (names, starts, ends) in plane.lines.items():
            out.append(f"  LINE {line}: {len(names)} events")
            totals: dict = {}
            for name, seconds in zip(names, (ends - starts) / 1e9):
                count, total = totals.get(name, (0, 0.0))
                totals[name] = (count + 1, total + seconds)
            for name, (count, total) in sorted(totals.items(), key=lambda kv: -kv[1][1])[:limit]:
                out.append(f"    {total:10.6f} s  x{count:<6d} {name[:140]}")
    return "\n".join(out)


if __name__ == "__main__":
    import json
    import sys
    loaded = load(sys.argv[1])
    print(describe(loaded))
    reduced = reduce(loaded)
    print(json.dumps({"window_s": reduced.window_s, "busy_s": reduced.busy_s, "launches": reduced.launches,
                      "queries": reduced.queries, "devices": reduced.devices, "device_ops": reduced.device_ops,
                      "idle_gaps": reduced.idle_gaps}, indent=1))
