"""The program's own spans and scopes in a traced window.

The program marks its steps `genie.<step>` (src/repro/runtime/tracing.py):
host spans (`jax.profiler.TraceAnnotation`) on the threads that do the
work, and named scopes inside its jitted programs, which reach the
`op_name` of the HLO instructions.  This module reads both from the same
profiler trace the benchmark takes, so host spans and device ops share one
clock, and gives every device op of the analysis window exactly one scope:

  1. the innermost `genie.*` part of its own `tf_op` (op metadata), else
     the scope of its source line: the one scope that the program's ops
     and instructions of that file:line carry, when they carry one;
  2. else, for an op that calls computations (a fusion, a `while`), the
     scope that most of the computing instructions it calls carry, by
     op_name or source line, in the program's optimized HLO.  What a fusion
     only reads in (slices, reshapes, broadcasts of its operands) belongs to
     the step that made it, so data movement counts only in a fusion that
     does nothing else.  The TPU compiler rewrites some primitives without
     metadata (the vmapped scatter becomes 1-D scatters and `while` loops)
     and keeps only the bare primitive name on their regions (`scatter`):
     such a name gives the one scope that all the program's instructions of
     that primitive of known scope lie in, when there is one;
  3. else the scope that steps 1 and 2 give the first operand its HLO names
     that has one (one hop: a layout copy, a slice, a pad);
  4. else, for an op of a program whose HLO carries no `genie.*` scope at
     all (JAX's eager modules: the merge, the hashing, scalar conversions),
     the innermost `genie.*` host span open on the thread that launched
     the program run: the device's `XLA Modules` event and the host's
     `DoEnqueueProgram` share a `run_id`, and the host's flow events
     (`_p` producer, `_c` consumer ids) lead back from there to the thread
     that called JAX;
  5. else `unattributed`.

The device time of a scope is the time in which one of its ops is the
innermost op running (a `while` does not count the ops of its body), so
the scopes' times and the unattributed time add up to the busy time.

The analysis window, the device planes and the op lines are those of
bench/devtrace.py.  `analyse` finds the trace of a reduced window among the
traces the benchmark wrote (bench/out/trace/) by its `bench.search` spans,
and logs the whole split on standard error; the readers in bench/metrics/
take their numbers from it.  `python3 bench/scopes.py <trace>` prints the
split of any trace, windowed by its `genie.dispatch` spans where it has no
`bench.search` span (a live front end's).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import gzip
import heapq
import json
import os
import re
import shutil
import sys
import tempfile

import devtrace
import xspace

BENCH = os.path.dirname(os.path.abspath(__file__))
TRACE_ROOT = os.path.join(BENCH, "out", "trace")
PREFIX = "genie."
DISPATCH, GC = "genie.dispatch", "genie.gc"
UNATTRIBUTED = "unattributed"
NO_SPAN = "no span"
MODULE_LINE = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"
FLOW_HOPS = 8          # host flow hops from an enqueue back to a span
TOP = 10

_SCOPE = re.compile(r"genie\.[A-Za-z_]+")


def scope_of(op_name) -> str | None:
    """The innermost `genie.*` component of a JAX op path."""
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else None


@dataclasses.dataclass(frozen=True)
class HostEvent:
    name: str
    line: int          # index of the thread's line in the host plane
    start: int
    end: int
    stats: dict


@dataclasses.dataclass
class Analysis:
    """What the per-layer readers take from one traced window."""

    scope_ns: dict            # scope -> device ns, mean over device planes
    busy_ns: float            # sum of scope_ns (the union of op intervals)
    unattributed_ops: list    # [(op label, ns)], largest first
    source_ns: dict           # (scope, source line) -> ns
    dispatches: list          # genie.dispatch spans of the window's searches
    spans: list               # genie.* host spans in the window
    self_ns: dict             # span name -> self time in the window
    idle_gaps: list           # [(innermost span on the dispatch thread, s)]
    gc: dict                  # generation -> collections in the window

    @property
    def has_program_marks(self) -> bool:
        return bool(self.spans) or any(
            s.startswith(PREFIX) for s in self.scope_ns)

    def scope_per_row_us(self, scope: str, rows: int):
        ns = self.scope_ns.get(scope, 0.0)
        if not ns or not rows:
            return None
        return ns * 1e-3 / rows


# ---------------------------------------------------------------------------
# Reading the trace
# ---------------------------------------------------------------------------

def _host(data) -> list[list[HostEvent]]:
    """The host events the analysis uses, per thread line: the program's
    spans, program enqueues and flow ends."""
    lines = []
    for plane in data.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            idx, keep = len(lines), []
            for ev in line.events:
                stats = dict(ev.stats)
                if (ev.name.startswith(PREFIX) or ev.name == ENQUEUE
                        or "_p" in stats or "_c" in stats):
                    start = int(ev.start_ns)
                    keep.append(HostEvent(ev.name, idx, start,
                                          start + int(ev.duration_ns), stats))
            keep.sort(key=lambda e: (e.start, -e.end))
            lines.append(keep)
    return lines


def _device(data):
    """Per device plane that ran anything: (plane name, ops, modules), ops as
    devtrace.Op and modules as (start, end, name, run_id), both by start."""
    out = []
    for plane in data.planes:
        if not devtrace.DEVICE_PLANE.match(plane.name):
            continue
        ops, modules = [], []
        for line in plane.lines:
            if line.name in devtrace.OP_LINES:
                ops.extend(devtrace.Op(ev.name, int(ev.start_ns),
                                       int(ev.start_ns + ev.duration_ns))
                           for ev in line.events)
            elif line.name == MODULE_LINE:
                for ev in line.events:
                    start = int(ev.start_ns)
                    modules.append((start, start + int(ev.duration_ns), ev.name,
                                    dict(ev.stats).get("run_id")))
        if ops:
            out.append((plane.name, sorted(ops, key=lambda o: (o.start, -o.end)),
                        sorted(modules)))
    return out


class _Lines:
    """Enclosing-event lookups on the host's thread lines."""

    def __init__(self, lines: list[list[HostEvent]]):
        self.lines = lines
        self.starts = [[e.start for e in line] for line in lines]
        self.longest = [max((e.end - e.start for e in line), default=0)
                        for line in lines]
        self.producers = {}
        for line in lines:
            for e in line:
                if "_p" in e.stats:
                    self.producers[e.stats["_p"]] = e

    def enclosing(self, line: int, t: int) -> list[HostEvent]:
        """Events of a line open at time t, innermost (latest start) first."""
        events, starts = self.lines[line], self.starts[line]
        out = []
        i = bisect.bisect_right(starts, t) - 1
        while i >= 0 and starts[i] >= t - self.longest[line]:
            e = events[i]
            if e.start <= t < e.end or e.start == t:
                out.append(e)
            i -= 1
        return out

    def innermost_span(self, line: int, t: int):
        for e in self.enclosing(line, t):
            if e.name.startswith(PREFIX):
                return e
        return None

    def launcher(self, enqueue: HostEvent) -> str | None:
        """The innermost genie span open where the thread that called JAX
        launched the program run this enqueue belongs to."""
        line, t = enqueue.line, enqueue.start
        for _ in range(FLOW_HOPS):
            span = self.innermost_span(line, t)
            if span is not None:
                return span.name
            prod = next((self.producers[e.stats["_c"]]
                         for e in self.enclosing(line, t)
                         if e.stats.get("_c") in self.producers), None)
            if prod is None:
                return None
            line, t = prod.line, prod.start
        return None


# ---------------------------------------------------------------------------
# Op -> scope
# ---------------------------------------------------------------------------

def _instruction_name(op: str) -> str:
    return op.split()[0].lstrip("%") if op else ""


def _program_id(module_name: str):
    m = re.search(r"\((\d+)\)$", module_name)
    return int(m.group(1)) if m else None


# HLO opcodes that only move, place or make data; what a fusion of them
# reads in from its operands belongs to the step that produced it
DATA_MOVEMENT = frozenset({
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "reshape", "transpose", "slice", "dynamic-slice", "dynamic-update-slice",
    "broadcast", "copy", "copy-start", "copy-done", "pad", "concatenate",
    "iota", "reverse"})


def _primitive(op_name: str) -> str:
    return op_name.rsplit("/", 1)[-1].rstrip(":").strip()


def _majority(scopes) -> str | None:
    counts = collections.Counter(s for s in scopes if s)
    if not counts:
        return None
    return sorted(counts.items(), key=lambda kv: (-kv[1], kv[0]))[0][0]


class _Program:
    """Op -> scope lookups in one program: its optimized HLO and the op
    metadata the trace holds for it (`tf_op`, `source`), by instruction."""

    def __init__(self, prog: xspace.Program | None, stats: dict):
        self.prog, self.stats = prog, stats
        lines = collections.defaultdict(set)
        for st in stats.values():
            s = scope_of(st.get("tf_op"))
            if s and st.get("source"):
                lines[st["source"]].add(s)
        for ins in (prog.instructions.values() if prog else ()):
            s = scope_of(ins.op_name)
            if s and ins.source:
                lines[ins.source].add(s)
        # a source line all of whose scoped ops lie in one scope
        self.line_scope = {k: next(iter(v)) for k, v in lines.items()
                           if len(v) == 1}
        prims = collections.defaultdict(set)
        for ins in (prog.instructions.values() if prog else ()):
            s = scope_of(ins.op_name)
            if s:
                prims[_primitive(ins.op_name)].add(s)
        self.scoped = bool(prims)
        bare = [(st.get("tf_op") or "", st.get("source")) for st in stats.values()]
        bare += [(i.op_name, i.source)
                 for i in (prog.instructions.values() if prog else ())]
        for name, source in bare:
            if name and "/" not in name and source in self.line_scope:
                prims[_primitive(name)].add(self.line_scope[source])
        # a primitive all of whose instructions of known scope lie in one
        self.primitive_scope = {k: next(iter(v)) for k, v in prims.items()
                                if len(v) == 1}

    def _named(self, ins: xspace.Instruction) -> str | None:
        """The scope an HLO instruction's op_name or source line gives."""
        return scope_of(ins.op_name) or self.line_scope.get(ins.source)

    def _bare(self, instrs) -> str | None:
        """The scope of the primitives that instructions name without a path
        (the compiler kept only the primitive: `scatter`), when it is one."""
        found = {self.primitive_scope.get(_primitive(i.op_name)) for i in instrs
                 if i.op_name and "/" not in i.op_name}
        found.discard(None)
        return next(iter(found)) if len(found) == 1 else None

    def own(self, name: str) -> str | None:
        """Steps 1 and 2 for one instruction."""
        st = self.stats.get(name, {})
        s = scope_of(st.get("tf_op")) or self.line_scope.get(st.get("source"))
        ins = None if self.prog is None else self.prog.instructions.get(name)
        if s or ins is None:
            return s
        s = self._named(ins) or self._bare([ins])
        if s or not ins.called:
            return s
        subs = self.prog.calls(ins)
        computing = [i for i in subs if i.opcode not in DATA_MOVEMENT]
        return (_majority(self._named(i) for i in computing)
                or self._bare(subs)
                or (None if computing else
                    _majority(self._named(i) for i in subs)))

    def scope(self, name: str) -> str | None:
        """Steps 1 to 3."""
        s = self.own(name)
        if s or self.prog is None or name not in self.prog.instructions:
            return s
        for operand in self.prog.instructions[name].operands:
            s = self.own(operand)
            if s:
                return s
        return None


# ---------------------------------------------------------------------------
# The analysis
# ---------------------------------------------------------------------------

def _partition(ops, lo: int, hi: int):
    """(op, ns) pieces of [lo, hi) in which `op` is the innermost op running
    (the latest started of those open): together they cover the union of
    the ops' intervals.  `ops` are sorted by (start, -end)."""
    pieces = sorted((max(o.start, lo), min(o.end, hi), i)
                    for i, o in enumerate(ops) if min(o.end, hi) > max(o.start, lo))
    times = sorted({t for s, e, _ in pieces for t in (s, e)})
    out, open_, k = [], [], 0          # open_: heap of (-start, -index, end)
    for t0, t1 in zip(times, times[1:]):
        while k < len(pieces) and pieces[k][0] <= t0:
            s, e, i = pieces[k]
            heapq.heappush(open_, (-s, -i, e))
            k += 1
        while open_ and open_[0][2] <= t0:
            heapq.heappop(open_)
        if open_:
            out.append((ops[-open_[0][1]], t1 - t0))
    return out


def analyse_trace(path: str, window: devtrace.Window) -> Analysis:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    meta = xspace.read(path, devtrace.DEVICE_PLANE)
    lines = _Lines(_host(data))
    lo, hi = window.start, window.end

    enqueues = {e.stats.get("run_id"): e for line in lines.lines
                for e in line if e.name == ENQUEUE}
    launched = {}

    def launcher(run_id):
        if run_id not in launched:
            e = enqueues.get(run_id)
            launched[run_id] = None if e is None else lines.launcher(e)
        return launched[run_id]

    scope_ns, source_ns, unattr = (collections.Counter(), collections.Counter(),
                                   collections.Counter())
    devices = _device(data)
    cache, programs = {}, {}
    for plane, ops, modules in devices:
        mstarts = [m[0] for m in modules]
        for op, ns in _partition(ops, lo, hi):
            j = bisect.bisect_right(mstarts, op.start) - 1
            module = modules[j] if j >= 0 and modules[j][1] > op.start else None
            pname = module[2] if module else ""
            key = (plane, pname, op.name)
            if key not in cache:
                if (plane, pname) not in programs:
                    pid = _program_id(pname)
                    programs[plane, pname] = _Program(
                        meta.programs.get(pname),
                        {_instruction_name(o): st
                         for o, found in meta.ops.get(plane, {}).items()
                         for st in found if st.get("program_id") == pid})
                prog = programs[plane, pname]
                name = _instruction_name(op.name)
                scope = prog.scope(name)
                if scope is None and module and not prog.scoped:
                    scope = launcher(module[3])
                cache[key] = (scope or UNATTRIBUTED,
                              prog.stats.get(name, {}).get("source", ""))
            scope, source = cache[key]
            scope_ns[scope] += ns
            source_ns[(scope, source)] += ns
            if scope == UNATTRIBUTED:
                unattr[op.label] += ns
    n = max(len(devices), 1)
    scope_ns = {k: v / n for k, v in scope_ns.items()}

    marks = [e for line in lines.lines for e in line if e.name.startswith(PREFIX)]
    spans = [e for e in marks if e.end > lo and e.start < hi]
    # a dispatch of the window stacks before its search and scatters after
    dispatches = [d for d in marks if d.name == DISPATCH and any(
        d.start <= s.start and s.end <= d.end for s in window.searches)]
    by_line = collections.defaultdict(list)
    for s in spans:
        by_line[s.line].append(s)

    self_ns = collections.Counter()
    for line, group in by_line.items():
        for s in group:
            kids = [(c.start, c.end) for c in group if c is not s
                    and s.start <= c.start and c.end <= s.end
                    and (c.start, -c.end) > (s.start, -s.end)]
            self_ns[s.name] += (min(s.end, hi) - max(s.start, lo)
                                - devtrace.union_ns(kids, max(s.start, lo),
                                                    min(s.end, hi)))

    dthreads = {d.line for d in spans if d.name == DISPATCH}
    gaps = []
    for _, ops, _ in devices:
        for g in devtrace.gaps_ns([(o.start, o.end) for o in ops], lo, hi):
            mid = (g[0] + g[1]) // 2
            inner = [s for t in dthreads for s in [lines.innermost_span(t, mid)] if s]
            label = max(inner, key=lambda s: s.start).name if inner else NO_SPAN
            gaps.append((label, (g[1] - g[0]) * 1e-9))
    gaps.sort(key=lambda x: -x[1])

    gc = collections.Counter(int(s.stats.get("generation", -1)) for s in spans
                             if s.name == GC and lo <= s.start < hi)
    return Analysis(
        scope_ns=scope_ns, busy_ns=sum(scope_ns.values()),
        unattributed_ops=[(k, v / n) for k, v in unattr.most_common()],
        source_ns={k: v / n for k, v in source_ns.items()},
        dispatches=dispatches, spans=spans,
        self_ns=dict(self_ns), idle_gaps=gaps, gc=dict(gc))


def report(a: Analysis, window: devtrace.Window, top: int = TOP) -> list[str]:
    """The split, as the lines `analyse` logs."""
    busy = window.busy_ns or 1.0
    out = [f"scopes: device time by scope, {len(window.searches)} searches, "
           f"busy {window.busy_ns * 1e-9:.6f} s; split sums to "
           f"{a.busy_ns * 1e-9:.6f} s ({100 * (a.busy_ns / busy - 1):+.4f}%)"]
    for k, v in sorted(a.scope_ns.items(), key=lambda kv: -kv[1]):
        out.append(f"scopes:   {k:<16} {v * 1e-9:12.6f} s {100 * v / busy:8.3f}%")
    out.append("scopes: device time by scope and source line (top "
               f"{3 * top})")
    for (k, src), v in sorted(a.source_ns.items(), key=lambda kv: -kv[1])[:3 * top]:
        out.append(f"scopes:   {k:<16} {src or '-':<60} {v * 1e-9:12.6f} s")
    un = a.scope_ns.get(UNATTRIBUTED, 0.0)
    out.append(f"scopes: unattributed {100 * un / busy:.3f}% of busy time; "
               f"largest ops:")
    for k, v in a.unattributed_ops[:top]:
        out.append(f"scopes:   {v * 1e-9:12.6f} s {k}")
    out.append("scopes: host span self time in the window: " + ", ".join(
        f"{k} {v * 1e-9:.6f} s" for k, v in sorted(a.self_ns.items(),
                                                    key=lambda kv: -kv[1])))
    out.append("scopes: idle_gaps_by_span " + json.dumps(
        [[k, v] for k, v in a.idle_gaps[:top]]))
    out.append("scopes: gc collections by generation in the window "
               + json.dumps({str(k): v for k, v in sorted(a.gc.items())}))
    return out


def find_trace(window: devtrace.Window) -> str | None:
    """The newest trace under TRACE_ROOT whose `bench.search` spans are the
    window's."""
    want = [(s.start, s.end) for s in window.searches]
    found = sorted(glob.glob(os.path.join(TRACE_ROOT, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime, reverse=True)
    for path in found:
        got = devtrace.load(path).searches()
        if want and [(s.start, s.end) for s in got
                     if s.start >= window.start and s.end <= window.end] == want:
            return path
    return None


_ANALYSED: dict = {}


def analyse(window) -> Analysis | None:
    """The analysis of a reduced window, from the trace the benchmark wrote
    for it; logged once on standard error.  None when there is no window,
    no trace of it, or no `genie.*` mark in it (a program without them)."""
    if window is None or not window.searches:
        return None
    key = (window.start, window.end)
    if key not in _ANALYSED:
        path = find_trace(window)
        a = None if path is None else analyse_trace(path, window)
        _ANALYSED[key] = a if a is not None and a.has_program_marks else None
        if a is not None:
            for line in report(a, window):
                print(line, file=sys.stderr, flush=True)
    return _ANALYSED[key]


def trace_window(path: str) -> devtrace.Window | None:
    """The analysis window of a trace file: its `bench.search` spans, or in
    the trace of a live front end, which has none, its `genie.dispatch`
    spans."""
    from jax.profiler import ProfileData

    trace = devtrace.load(path)
    if trace.window() is None:
        trace = dataclasses.replace(trace, spans=[
            devtrace.Span(devtrace.SEARCH_SPAN, e.start, e.end, e.stats)
            for line in _host(ProfileData.from_file(path)) for e in line
            if e.name == DISPATCH])
    return devtrace.reduce(trace)


def main(argv=None) -> int:
    """Print the split of a trace: an `.xplane.pb` (or the same gzipped), or
    the newest under a directory."""
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python3 bench/scopes.py <trace file, .gz or directory>",
              file=sys.stderr)
        return 2
    path = argv[0]
    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                recursive=True), key=os.path.getmtime)[-1]
    with tempfile.TemporaryDirectory() as tmp:
        if path.endswith(".gz"):
            with gzip.open(path) as f, open(os.path.join(tmp, "t.xplane.pb"),
                                            "wb") as g:
                shutil.copyfileobj(f, g)
            path = g.name
        window = trace_window(path)
        if window is None:
            print("no analysis window in the trace", file=sys.stderr)
            return 1
        for line in report(analyse_trace(path, window), window):
            print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
