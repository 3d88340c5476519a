"""The profiler trace of a measured window, reduced to what the per-layer
metrics read.

``capture`` records a ``jax.profiler`` trace into a temporary directory;
``load_xplane`` keeps from it the device operations (the ``XLA Ops`` line
of each ``/device:`` plane, named by their HLO instruction) and the
benchmark's own host spans (``TraceAnnotation`` names that start with
``bench.``); ``reduce`` turns those into:

- ``busy_s``: the union of the intervals in which an operation ran on a
  device, inside the window, averaged over the chips used;
- ``op_s``: device seconds per operation name inside the window (mean over
  the chips) and ``op_s_per_chip``;
- ``idle_by_span``: idle device seconds labelled by the innermost benchmark
  span open at the middle of each gap, and ``gaps``, the longest gaps.

Device and host events of one trace share a clock, so a gap is attributed to
what the host was doing during it.
"""
from __future__ import annotations

import collections
import contextlib
import glob
import os
import re
import shutil
import tempfile
from typing import Dict, List, NamedTuple, Optional, Sequence

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


class Event(NamedTuple):
    name: str
    start: float      # seconds on the trace's clock
    end: float


def op_name(hlo: str) -> str:
    """'%hamming_hist_pallas.1 = (s32[...]) custom-call(...)' ->
    'hamming_hist_pallas.1'."""
    return hlo.split(" = ", 1)[0].strip().lstrip("%")


def base_name(name: str) -> str:
    """An instruction name without its numeric suffix ('fusion.12' ->
    'fusion')."""
    return re.sub(r"(\.\d+)+$", "", name)


@contextlib.contextmanager
def capture():
    """Trace everything inside the block; yields a dict whose ``path`` is the
    ``.xplane.pb`` once the block has closed. The directory is removed by
    ``discard``."""
    import jax
    out = {"dir": tempfile.mkdtemp(prefix="chipbench-trace-"), "path": None}
    with jax.profiler.trace(out["dir"]):
        yield out
    found = glob.glob(os.path.join(out["dir"], "**", "*.xplane.pb"),
                      recursive=True)
    out["path"] = found[0] if found else None


def discard(cap: dict) -> None:
    shutil.rmtree(cap["dir"], ignore_errors=True)


def load_xplane(path: str) -> dict:
    """{'devices': {plane: [Event]}, 'spans': [Event]} from an .xplane.pb."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        Event(op_name(e.name), e.start_ns * 1e-9,
                              e.end_ns * 1e-9) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Event(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": spans}


def union(intervals: Sequence[tuple], lo: float, hi: float) -> List[tuple]:
    """Merged, sorted intervals clipped to [lo, hi]."""
    out: List[list] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def _innermost(spans: Sequence[Event], t: float) -> str:
    best: Optional[Event] = None
    for s in spans:
        if s.start <= t <= s.end and (best is None or s.start >= best.start):
            if s.name != WINDOW_SPAN or best is None:
                best = s
    return best.name if best is not None else "outside_spans"


def reduce(tr: dict, n_gaps: int = 10) -> dict:
    """The summary the per-layer metric readers take (see module doc)."""
    spans = tr["spans"]
    win = [s for s in spans if s.name == WINDOW_SPAN]
    planes = sorted(tr["devices"], key=_plane_order)
    evs = [e for p in planes for e in tr["devices"][p]]
    if win:
        lo, hi = win[0].start, win[0].end
    elif evs:
        lo, hi = min(e.start for e in evs), max(e.end for e in evs)
    else:
        lo = hi = 0.0
    busy, op_chip, idle, gaps = [], [], collections.Counter(), []
    for p in planes:
        u = union([(e.start, e.end) for e in tr["devices"][p]], lo, hi)
        busy.append(sum(e - s for s, e in u))
        ops = collections.Counter()
        for e in tr["devices"][p]:
            d = min(e.end, hi) - max(e.start, lo)
            if d > 0:
                ops[e.name] += d
        op_chip.append(dict(ops))
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                label = _innermost(spans, 0.5 * (s + e))
                idle[label] += (e - s) / len(planes)
                gaps.append((label, e - s))
    n = max(len(planes), 1)
    op_s = collections.Counter()
    for ops in op_chip:
        for k, v in ops.items():
            op_s[k] += v / n
    gaps.sort(key=lambda g: -g[1])
    return {"window_s": hi - lo, "chips": len(planes),
            "busy_s": sum(busy) / n, "busy_s_per_chip": busy,
            "op_s": dict(op_s), "op_s_per_chip": op_chip,
            "idle_by_span": dict(idle), "gaps": gaps[:n_gaps]}


def _plane_order(name: str):
    m = re.search(r"(\d+)$", name)
    return (int(m.group(1)) if m else 0, name)


def op_seconds(summary: dict, prefixes: Sequence[str],
               chip: Optional[int] = None) -> float:
    """Device seconds of the operations whose name starts with one of
    ``prefixes``: the mean over chips, or one chip's."""
    ops = summary["op_s"] if chip is None else summary["op_s_per_chip"][chip]
    return sum(v for k, v in ops.items() if k.startswith(tuple(prefixes)))


def breakdown(summary: dict, n: int = 10) -> dict:
    """The ``breakdown`` of a traced run's result line: the device
    operations that took most time (by instruction name, suffix dropped) and
    the idle seconds by what the host was doing."""
    ops = collections.Counter()
    for k, v in summary["op_s"].items():
        ops[base_name(k)] += v
    idle = sorted(summary["idle_by_span"].items(), key=lambda kv: -kv[1])
    return {"device_ops": [[k, v] for k, v in ops.most_common(n)],
            "idle_gaps": [[k, v] for k, v in idle[:n]]}
