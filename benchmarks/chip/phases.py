"""The program's own spans, launches and device scopes in a profile.

``trace.summarize`` keeps the harness's ``bench/*`` spans and names each
device op by its module and instruction. ``summarize`` here starts from
it, so every number ``trace.summarize`` gives stays as it is, and adds
what the program marks itself:

* the coalescer's ``coal/*`` host spans (``repro.serve.engine``) on the
  same per-thread nesting stack as ``bench/*``, so an idle gap is named by
  the innermost span of either kind;
* device 0's program launches (``XLA Modules``), counted per span by
  ``launches_in`` and, inside a span, by the innermost span open when the
  program started on the device (``launches_by_phase``);
* each device op's named scope (``probe/prep``, ``probe/rings``,
  ``probe/central``, ``probe/slab``: ``jax.named_scope`` in
  ``repro.core``), so that a module's device time splits by scope
  (``scope_ms``), and device op names carry it in ``breakdown``.

A profile's op events hold an instruction's HLO text without its metadata.
The scope comes from the compiled module's own text (``served_hlo``: the
``compiled.as_text()`` of each served shape, made after the window and
never inside it): an op is matched to an
instruction by its head, ``%name = shape opcode``, within the compiled
text that matches most of that program's ops. An instruction without a
scope of its own takes the most common scope of the instructions fused
into it, else that of its first operand that has one: a lowering that
drops the name stack (``cumsum``'s reduce-window, parameter copies) stays
with the work that feeds it. An op still without one takes the scope of
the op it runs inside (a ``while`` body's ops inside the ``while``).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re

import jax
import jax.numpy as jnp

from repro.core import estimator as E

from benchmarks.chip import trace

PREFIXES = ("bench/", "coal/")
SCOPE = re.compile(r"probe/(\w+)")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+) = (.*)$")
_NAME = re.compile(r"%[\w.\-]+")
_CALLS = re.compile(r"calls=(%[\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


class capture(trace.capture):
    """``trace.capture`` that also takes a counter dict (the coalescer's
    ``stats``) when the profile starts and stops; ``counters`` is then
    their difference, the work of the traced interval."""

    def __init__(self, stats: dict):
        super().__init__()
        self.stats = stats
        self.counters: dict = {}

    def start(self):
        self._before = dict(self.stats)
        super().start()

    def stop(self):
        super().stop()
        self.counters = {k: v - self._before.get(k, 0)
                         for k, v in self.stats.items()}


def served_hlo(coal, sizes) -> list:
    """Compiled HLO text of the probe step at each padded flush size, as
    the coalescer ``coal`` dispatches it (``E.estimate_batch_stats``)."""
    d = coal.state.x.shape[1]
    key = jax.random.fold_in(coal.key, 0)
    return [E.estimate_batch_stats.lower(
        coal.state, jnp.zeros((p, d), jnp.float32),
        jnp.zeros((p,), jnp.float32), coal.cfg, key).compile().as_text()
        for p in sizes]


def hlo_modules(text: str) -> list:
    """The compiled modules of a text that joins several, one each."""
    return [m for m in re.split(r"\n(?=HloModule )", text) if m.strip()]


def _close(text: str, i: int) -> int:
    """Index just past the parenthesis that closes the one at ``i``."""
    depth = 0
    for j in range(i, len(text)):
        depth += {"(": 1, ")": -1}.get(text[j], 0)
        if depth == 0:
            return j + 1
    return len(text)


def _split(text: str):
    """``(name, head, operands, rest)`` of an HLO instruction's text, or
    None; ``head`` is ``%name = shape opcode``."""
    m = _INSTR.match(text)
    if not m:
        return None
    name, rhs = m.groups()
    cut = _close(rhs, 0) if rhs.startswith("(") else rhs.find(" ")
    if cut < 0:
        return None
    shape, after = rhs[:cut], rhs[cut:].lstrip()
    paren = after.find("(")
    if paren <= 0:
        return None
    end = _close(after, paren)
    head = f"{name} = {shape} {after[:paren]}"
    return name, head, after[paren + 1:end - 1], after[end:]


def _own_scope(op_name: str | None) -> str | None:
    found = SCOPE.findall(op_name or "")
    return found[-1] if found else None


class HloScopes:
    """One compiled module's text: the heads of its instructions and the
    scope of each (module docstring)."""

    def __init__(self, text: str):
        first = text.split("\n", 1)[0].split()
        self.module = first[1].rstrip(",") if len(first) > 1 else ""
        self._instr: dict = {}        # name -> (head, operands, calls, own)
        self._members: dict = collections.defaultdict(list)
        comp = None
        for line in text.splitlines():
            if line.endswith("{") and not line.startswith(" "):
                comp = line.split()[1] if line.startswith("ENTRY") else \
                    line.split()[0]
                continue
            parts = _split(line)
            if parts is None:
                continue
            name, head, operands, rest = parts
            calls = _CALLS.search(rest)
            op = _OP_NAME.search(rest)
            self._instr[name] = (head, _NAME.findall(operands),
                                 calls.group(1) if calls else None,
                                 _own_scope(op.group(1) if op else None))
            self._members[comp].append(name)
        self.heads = {v[0]: k for k, v in self._instr.items()}
        self._memo: dict = {}

    def scope(self, name: str) -> str | None:
        if name in self._memo:
            return self._memo[name]
        self._memo[name] = None              # guards a cycle
        got = None
        if name in self._instr:
            _, operands, calls, got = self._instr[name]
            if got is None and calls is not None:
                inner = collections.Counter(
                    s for s in (self._instr[n][3]
                                for n in self._members.get(calls, ()))
                    if s is not None)
                if inner:
                    got = inner.most_common(1)[0][0]
            for o in operands if got is None else ():
                got = self.scope(o)
                if got is not None:
                    break
        self._memo[name] = got
        return got


@dataclasses.dataclass
class PhaseSummary(trace.TraceSummary):
    parents: list = dataclasses.field(default_factory=list)
    # device 0: every program launch, (start_ns, end_ns, module name)
    calls: list = dataclasses.field(default_factory=list)
    # device 0: every op, (call index, instruction, self ns, scope)
    op_events: list = dataclasses.field(default_factory=list)

    def launches_in(self, name: str) -> list:
        """Device-0 program launches starting inside each span ``name``;
        none from a trace without a device plane."""
        if not self.busy:
            return []
        starts = [c[0] for c in self.calls]
        return [bisect.bisect_left(starts, e) - bisect.bisect_left(starts, s)
                for n, s, e, _ in self.spans if n == name]

    def _tree(self):
        kids = collections.defaultdict(list)
        for i, p in enumerate(self.parents):
            if p is not None:
                kids[p].append(i)
        return kids

    def _roots(self, name: str) -> list:
        return [i for i, sp in enumerate(self.spans) if sp[0] == name]

    def launches_by_phase(self, name: str = "coal/flush") -> dict:
        """Launches per span ``name``, by the innermost span open when each
        program started on device 0 (``name`` itself: open in no child)."""
        kids, roots = self._tree(), self._roots(name)
        starts = [c[0] for c in self.calls]
        out: dict = collections.Counter()
        for r in roots:
            _, s, e, _ = self.spans[r]
            for t in starts[bisect.bisect_left(starts, s):
                            bisect.bisect_left(starts, e)]:
                i = r
                while True:
                    inner = [k for k in kids[i]
                             if self.spans[k][1] <= t < self.spans[k][2]]
                    if not inner:
                        break
                    i = inner[0]
                out[self.spans[i][0]] += 1
        return {k: v / len(roots) for k, v in out.items()} if roots else {}

    def idle_by_phase(self, name: str = "coal/flush") -> dict:
        """Device-0 idle seconds inside the spans ``name``, by the innermost
        span open (``name`` itself: idle inside it and in no child)."""
        if not self.busy:
            return {}
        kids = self._tree()

        def idle(i):
            _, s, e, _ = self.spans[i]
            return (e - s) - trace._covered(self.busy[0], s, e)

        out: dict = collections.Counter()
        todo = self._roots(name)
        while todo:
            i = todo.pop()
            out[self.spans[i][0]] += (idle(i) - sum(idle(k) for k in kids[i])
                                      ) * 1e-9
            todo.extend(kids[i])
        return dict(out)

    def _calls(self, pattern: str) -> set:
        """Calls of the modules whose name matches, started in the window."""
        rx = re.compile(pattern)
        lo, hi = self.window
        return {i for i, (s, _, m) in enumerate(self.calls)
                if rx.search(m) and lo <= s < hi}

    def scope_ms(self, pattern: str) -> dict:
        """Device ms per call of the modules whose name matches, split by
        scope (``""``: none), over the calls that start in the window."""
        sel = self._calls(pattern)
        out: dict = collections.Counter()
        for call, _, ns, scope in self.op_events:
            if call in sel:
                out[scope or ""] += ns
        return {k: v * 1e-6 / len(sel) for k, v in out.items()} if sel else {}

    def unscoped_ops(self, pattern: str, top: int = 10) -> list:
        """[(instruction, device ms per call)] of the unscoped ops of the
        modules that match, largest first."""
        sel = self._calls(pattern)
        out: dict = collections.Counter()
        for call, op, ns, scope in self.op_events:
            if call in sel and scope is None:
                out[op] += ns
        return [(op, ns * 1e-6 / len(sel)) for op, ns in out.most_common(top)]

    def breakdown(self, top: int = 10) -> dict:
        b = super().breakdown(top)
        b["launches_per_flush"] = self.launches_by_phase()
        b["idle_s_in_flush"] = self.idle_by_phase()
        return b


def summarize(path, hlo_text=()) -> PhaseSummary:
    """Read one ``.xplane.pb`` (module docstring); ``hlo_text``: the
    compiled text of each served program whose ops are to be scoped, one
    module each (``hlo_modules``)."""
    from jax.profiler import ProfileData
    base = trace.summarize(path)
    pd = ProfileData.from_file(str(path))
    spans, parents, dev0 = [], [], None
    for plane in pd.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            if dev0 is None:             # device 0, as trace.summarize
                dev0 = plane
            continue
        for line in plane.lines:
            stack: list = []
            for ev in line.events:
                if not ev.name.startswith(PREFIXES):
                    continue
                while stack and spans[stack[-1]][2] <= ev.start_ns:
                    stack.pop()
                parents.append(stack[-1] if stack else None)
                spans.append((ev.name, ev.start_ns, ev.end_ns, len(stack)))
                stack.append(len(spans) - 1)
    calls, op_events = [], []
    if dev0 is not None:
        lines = {ln.name: ln for ln in dev0.lines}
        calls = sorted((ev.start_ns, ev.end_ns, ev.name) for ev in
                       (lines["XLA Modules"].events
                        if "XLA Modules" in lines else ()))
        op_events = _scoped_ops(calls, lines["XLA Ops"].events
                                if "XLA Ops" in lines else (),
                                [HloScopes(t) for t in hlo_text])
    ops: dict = collections.Counter()
    lo, hi = base.window
    for call, op, ns, scope in op_events:
        if lo <= calls[call][0] < hi:
            mod = calls[call][2].split("(", 1)[0]
            ops["/".join([mod] + (["probe", scope] if scope else []) + [op])
                ] += ns
    return PhaseSummary(base.window, base.busy, dict(ops) or base.ops,
                        base.modules, spans, parents, calls, op_events)


def _scoped_ops(calls: list, events, hlo: list) -> list:
    """``[(call index, instruction, self ns, scope)]`` for device-0 op
    events that run inside a module call."""
    starts = [c[0] for c in calls]
    recs: list = []                  # [call, text, start, end, self, parent]
    stack: list = []
    for ev in sorted(events, key=lambda ev: (ev.start_ns, -ev.end_ns)):
        i = bisect.bisect_right(starts, ev.start_ns) - 1
        if i < 0 or ev.start_ns >= calls[i][1]:
            continue
        while stack and recs[stack[-1]][3] <= ev.start_ns:
            stack.pop()
        parent = stack[-1] if stack else None
        if parent is not None:
            recs[parent][4] -= ev.end_ns - ev.start_ns
        recs.append([i, ev.name, ev.start_ns, ev.end_ns,
                     ev.end_ns - ev.start_ns, parent])
        stack.append(len(recs) - 1)

    # each program (module name with its fingerprint) reads the compiled
    # text that holds most of its ops' heads
    heads = [_split(r[1]) for r in recs]
    by_prog: dict = collections.defaultdict(collections.Counter)
    for r, h in zip(recs, heads):
        prog = calls[r[0]][2]
        for k, t in enumerate(hlo):
            if h is not None and t.module == prog.split("(", 1)[0] and \
                    h[1] in t.heads:
                by_prog[prog][k] += 1
    chosen = {p: hlo[c.most_common(1)[0][0]] for p, c in by_prog.items()}

    out, scopes = [], []
    for r, h in zip(recs, heads):
        t = chosen.get(calls[r[0]][2])
        scope = t.scope(t.heads[h[1]]) if t is not None and h is not None \
            and h[1] in t.heads else None
        if scope is None and r[5] is not None:
            scope = scopes[r[5]]
        scopes.append(scope)
        out.append((r[0], h[0].lstrip("%") if h else r[1], r[4], scope))
    return out
