"""Reduction of a profiler trace to the numbers the benchmark reports.

A traced run records the last ``TRACE_SECONDS`` of the window with
``jax.profiler`` (``capture``): a whole window of 51 s makes a trace that
takes minutes to write and read. The harness marks the traced part of the
window and what the host is doing with
``TraceAnnotation`` spans named ``bench/window``, ``bench/submit``,
``bench/flush`` and ``bench/sleep``. ``summarize`` reads the ``.xplane.pb``
with ``jax.profiler.ProfileData`` and keeps, inside the window:

* busy: the union of the intervals in which an operation ran on a device
  (the ``XLA Ops`` line of each ``/device:<kind>:<n>`` plane), averaged
  over devices; idle is the rest of the window;
* the device time of each jitted module per call (``XLA Modules``);
* the device time of each operation, summed by name (its own time: an
  operation nested in a ``while`` does not count twice);
* the host spans, so that each idle gap is named by the innermost span
  open at its midpoint.
"""
from __future__ import annotations

import bisect
import dataclasses
import pathlib
import re
import shutil
import tempfile

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
WINDOW = "bench/window"
TRACE_SECONDS = 15.0        # the traced tail of a window


class capture:
    """A profile into a fresh directory under ``TMPDIR``, from ``start()``
    to ``stop()``; then ``path`` is the ``.xplane.pb`` written and
    ``cleanup()`` removes the directory."""

    def __init__(self):
        self.dir = pathlib.Path(tempfile.mkdtemp(prefix="bench-trace-"))
        self.path = None
        self.started = False

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        self._ctx = jax.profiler.trace(str(self.dir), profiler_options=opts)
        self._ctx.__enter__()
        self.started = True

    def stop(self):
        self._ctx.__exit__(None, None, None)
        found = sorted(self.dir.rglob("*.xplane.pb"))
        self.path = found[-1] if found else None

    def cleanup(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge (n, 2) intervals into disjoint sorted ones."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.float64)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def _covered(busy: np.ndarray, lo: float, hi: float) -> float:
    """Length of [lo, hi) covered by the disjoint intervals ``busy``."""
    c = _clip(busy, lo, hi)
    return float((c[:, 1] - c[:, 0]).sum()) if len(c) else 0.0


@dataclasses.dataclass
class TraceSummary:
    window: tuple               # (start_ns, end_ns) of bench/window
    busy: list                  # per device: (n, 2) disjoint busy intervals
    ops: dict                   # op name -> device ns inside the window
    modules: dict               # module name -> [device ns per call]
    spans: list                 # [(name, start_ns, end_ns, depth)] bench/*

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        if not self.busy:
            return 0.0
        return float(np.mean([(b[:, 1] - b[:, 0]).sum() for b in self.busy])
                     ) * 1e-9

    def module_calls_ms(self, pattern: str) -> list:
        """Device ms of each call of every module whose name matches."""
        rx = re.compile(pattern)
        return [ns * 1e-6 for name, calls in self.modules.items()
                if rx.search(name) for ns in calls]

    def span_durations_ms(self, name: str) -> list:
        return [(e - s) * 1e-6 for n, s, e, _ in self.spans if n == name]

    def host_share(self, name: str) -> float | None:
        """Share of the time inside spans ``name`` in which no device
        operation ran (device 0)."""
        sp = [(s, e) for n, s, e, _ in self.spans if n == name]
        total = sum(e - s for s, e in sp)
        if not sp or total <= 0 or not self.busy:
            return None
        busy = sum(_covered(self.busy[0], s, e) for s, e in sp)
        return 1.0 - busy / total

    def _open_span(self, t: float) -> str:
        best, depth = "none", -1
        for n, s, e, d in self.spans:
            if n != WINDOW and s <= t < e and d > depth:
                best, depth = n, d
        return best

    def idle_gaps(self) -> list:
        """[(span open at the gap's midpoint, seconds)] for every gap in
        device 0's busy intervals inside the window, longest first."""
        if not self.busy:
            return []
        lo, hi = self.window
        b = self.busy[0]
        starts = np.concatenate([[lo], b[:, 1]])
        ends = np.concatenate([b[:, 0], [hi]])
        gaps = [(s, e) for s, e in zip(starts, ends) if e > s]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [(self._open_span(0.5 * (s + e)), (e - s) * 1e-9)
                for s, e in gaps]

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, ns * 1e-9] for n, ns in ops],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps()[:top]]}


def summarize(path) -> TraceSummary:
    """Read one ``.xplane.pb`` (see module docstring)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    spans = []
    devices = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            stack: list = []
            for ev in line.events:
                if not ev.name.startswith("bench/"):
                    continue
                while stack and stack[-1] <= ev.start_ns:
                    stack.pop()
                spans.append((ev.name, ev.start_ns, ev.end_ns, len(stack)))
                stack.append(ev.end_ns)
    win = [(s, e) for n, s, e, _ in spans if n == WINDOW]
    if not win:
        raise ValueError(f"no {WINDOW} span in {path}")
    lo, hi = win[0]
    busy, ops, modules = [], {}, {}
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        mods = sorted((ev.start_ns, ev.end_ns, ev.name) for ev in
                      (lines["XLA Modules"].events
                       if "XLA Modules" in lines else ()))
        for s, e, name in mods:
            if lo <= s < hi:
                modules.setdefault(name, []).append(e - s)
        starts = [m[0] for m in mods]
        iv = []
        stack: list = []           # enclosing ops: [end, name, self ns]
        for ev in sorted((lines["XLA Ops"].events if "XLA Ops" in lines
                          else ()), key=lambda ev: (ev.start_ns, -ev.end_ns)):
            s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
            if e <= s:
                continue
            iv.append((s, e))
            while stack and stack[-1][0] <= s:
                _, name, t = stack.pop()
                ops[name] = ops.get(name, 0.0) + t
            if stack:                  # an op inside a while or call op
                stack[-1][2] -= e - s
            stack.append([e, _op_name(ev.name, mods, starts, ev.start_ns),
                          e - s])
        for _, name, t in stack:
            ops[name] = ops.get(name, 0.0) + t
        busy.append(_union(np.asarray(iv, np.float64).reshape(-1, 2)))
    return TraceSummary((lo, hi), busy, ops, modules, spans)


def _op_name(hlo: str, mods: list, starts: list, t: float) -> str:
    """``<module>/<op>`` for an op event, whose name is its HLO text
    (``%fusion.12 = f32[..] fusion(..)``): the instruction's name, under
    the jitted module running at ``t`` without its fingerprint."""
    op = hlo.split(" = ", 1)[0].lstrip("%")
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and mods[i][0] <= t < mods[i][1]:
        return mods[i][2].split("(", 1)[0] + "/" + op
    return op
