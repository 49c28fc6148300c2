"""Reduction of a profiler trace to what the per-layer metrics read.

The JAX profiler writes `<dir>/plugins/profile/<run>/<host>.xplane.pb`;
`jax.profiler.ProfileData` reads it.  Device planes are named
`/device:TPU:<n>`; their op line (`XLA Ops`) holds one event per operation
the device ran.  Host planes hold the benchmark's own spans
(`jax.profiler.TraceAnnotation`): `bench.search` around each call into the
tenant backend's `search`, with its sequence number as a stat.

The analysis window runs from the start of the first `bench.search` span in
the trace to the end of the last one, so that it holds whole dispatches
only.  Device time is the union of the op intervals clipped to that window,
averaged over the device planes that ran anything.

A Pallas kernel runs as one custom call named after the kernel (`match_count`
for `_match_count_kernel`), so its device time is that of the ops whose HLO
instruction name is the kernel's, up to XLA's `.<n>` suffix.  On the TPU an
op's event name is its HLO text, `%match_count.1 = s32[16,281344]{...}
custom-call(...)`; the instruction is its first word.  The pads,
transposes and slices of the kernel's `jax.jit` wrapper carry the wrapper's
name only in their source path (`jit(search)/jit(match_count)/transpose`),
never in their own name, and are not counted as the kernel.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

SEARCH_SPAN = "bench.search"
SUBMIT_SPAN = "bench.submit"
OP_LINES = ("XLA Ops",)
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
LABEL_CHARS = 120   # of an op's name in the breakdown


@dataclasses.dataclass(frozen=True)
class Op:
    name: str         # the HLO instruction, e.g. `match_count.1`, `fusion.12`
    start: int        # ns
    end: int          # ns
    source: str = ""  # the JAX source path of the op (`tf_op`), if any

    @property
    def instruction(self) -> str:
        """The instruction's name without XLA's `.<n>` or `.clone` suffixes."""
        return self.name.split()[0].lstrip("%").split(".")[0] if self.name else ""

    @property
    def label(self) -> str:
        """What the breakdown shows: the instruction (on the TPU, the head of
        its HLO text) and its source path where the trace gives one."""
        head = self.name[:LABEL_CHARS]
        return f"{head} | {self.source}" if self.source else head


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: int
    end: int
    stats: dict


@dataclasses.dataclass
class Trace:
    """Device ops and host spans of one traced window (times in ns)."""

    ops: list[list[Op]]          # per device plane that ran anything
    spans: list[Span]            # host spans, all threads

    def searches(self) -> list[Span]:
        return sorted((s for s in self.spans if s.name == SEARCH_SPAN),
                      key=lambda s: s.start)

    def window(self) -> tuple[int, int] | None:
        """[first search start, last search end], or None without one."""
        s = self.searches()
        if not s:
            return None
        return s[0].start, max(x.end for x in s)


def union_ns(intervals, lo: int, hi: int) -> int:
    """Length of the union of [start, end) intervals, clipped to [lo, hi)."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """The [start, end) stretches of [lo, hi) that no interval covers."""
    out, at = [], lo
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < hi:
        out.append((at, hi))
    return out


def _op(ev) -> Op:
    source = dict(ev.stats).get("tf_op")
    return Op(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
              source if isinstance(source, str) else "")


def load(path: str) -> Trace:
    """Read an `.xplane.pb` file, or the newest one under a trace directory."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    data = ProfileData.from_file(path)
    ops, spans = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            plane_ops = [_op(ev) for line in plane.lines
                         if line.name in OP_LINES for ev in line.events]
            if plane_ops:
                ops.append(plane_ops)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in (SEARCH_SPAN, SUBMIT_SPAN):
                        spans.append(Span(ev.name, int(ev.start_ns),
                                          int(ev.start_ns + ev.duration_ns),
                                          dict(ev.stats)))
    return Trace(ops=ops, spans=spans)


@dataclasses.dataclass
class Window:
    """What the per-layer readers see of one traced window."""

    start: int                   # ns
    end: int                     # ns
    busy_ns: float               # union of op intervals, mean over devices
    op_ns: dict                  # op label -> summed ns, mean over devices
    instruction_ns: dict         # instruction name -> summed ns, likewise
    searches: list[Span]         # the dispatches inside the window
    idle_gaps: list[tuple[str, float]]   # (what the host did, seconds)

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9

    def kernel_ns(self, kernel: str) -> float:
        """Summed device time of the kernel's own custom calls: the ops whose
        instruction is named `kernel` (nothing else its wrapper runs)."""
        return self.instruction_ns.get(kernel, 0.0)


def _gap_label(gap: tuple[int, int], spans: list[Span]) -> str:
    mid = (gap[0] + gap[1]) / 2
    for name in (SEARCH_SPAN, SUBMIT_SPAN):
        if any(s.start <= mid < s.end for s in spans if s.name == name):
            return f"inside {name}"
    return "between searches"


def reduce(trace: Trace) -> Window | None:
    """The analysis window of a trace, or None when it holds no dispatch or
    no device op."""
    win = trace.window()
    if win is None or not trace.ops:
        return None
    lo, hi = win
    busy, op_ns, instruction_ns, gaps = 0.0, {}, {}, []
    for plane_ops in trace.ops:
        spans = [(o.start, o.end) for o in plane_ops]
        busy += union_ns(spans, lo, hi)
        for o in plane_ops:
            d = min(o.end, hi) - max(o.start, lo)
            if d > 0:
                op_ns[o.label] = op_ns.get(o.label, 0.0) + d
                instruction_ns[o.instruction] = instruction_ns.get(o.instruction, 0.0) + d
        gaps.extend(gaps_ns(spans, lo, hi))
    n = len(trace.ops)
    op_ns = {k: v / n for k, v in op_ns.items()}
    instruction_ns = {k: v / n for k, v in instruction_ns.items()}
    idle = sorted(((_gap_label(g, trace.spans), (g[1] - g[0]) * 1e-9)
                   for g in gaps), key=lambda x: -x[1])
    searches = [s for s in trace.searches() if s.start >= lo and s.end <= hi]
    return Window(start=lo, end=hi, busy_ns=busy / n, op_ns=op_ns,
                  instruction_ns=instruction_ns, searches=searches,
                  idle_gaps=idle)


def breakdown(window: Window, top: int = 10) -> dict:
    """The device ops that took most time and the longest idle gaps."""
    ops = sorted(window.op_ns.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v * 1e-9] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in window.idle_gaps[:top]]}
