#!/usr/bin/env python3
"""The device's idle gaps in a traced window, named by the host span open
in them.

    python bench/tools/gaps.py .bench_trace/<cell> [--min-ms 1] [--top 20]

Reads the profile that ``bench/run.py --trace 1`` writes (or one
``.xplane.pb``). Each idle gap inside the ``bench.window`` span is named
by the innermost span open on the host at its midpoint, of either kind:
the benchmark's (``bench.*``) or the program's (``snapstore.*``). Prints,
per name, the gaps longer than ``--min-ms``: their number, total and
longest time; then the ``--top`` longest gaps. Then all idle time split
at span boundaries, each piece named by the innermost span open during
it and, inside a span that holds others, whether it lies before the
first of them or after the last it has reached: where in a dispatch the
host was while the device waited.
"""

from __future__ import annotations

import argparse
import bisect
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import program_trace  # noqa: E402
from bench import trace as trace_lib  # noqa: E402

NONE = "(no span)"


class Spans:
    """The window's host spans (the window itself left out), for finding
    the innermost one open at a time."""

    def __init__(self, spans):
        self.spans = sorted((s for s in spans
                             if s.name != program_trace.WINDOW),
                            key=lambda s: s.start)
        self.starts = [s.start for s in self.spans]
        self.longest = max((s.dur for s in self.spans), default=0)

    def open_at(self, t: int):
        """The span open at ``t`` that started last, or ``None``."""
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.spans[i].start >= t - self.longest:
            s = self.spans[i]
            if s.end > t:
                return s
            i -= 1
        return None

    def inside(self, outer):
        """The spans that lie inside ``outer`` on its thread."""
        lo = bisect.bisect_left(self.starts, outer.start)
        hi = bisect.bisect_left(self.starts, outer.end)
        return [s for s in self.spans[lo:hi] if s is not outer
                and s.thread == outer.thread and s.end <= outer.end]

    def name_at(self, t: int) -> str:
        s = self.open_at(t)
        return s.name if s else NONE

    def place_at(self, t: int) -> str:
        """The innermost span open at ``t``, and where ``t`` lies among
        the spans inside it."""
        s = self.open_at(t)
        if s is None:
            return NONE
        inner = self.inside(s)
        done = [c for c in inner if c.end <= t]
        if done:
            return f"{s.name}, after {max(done, key=lambda c: c.end).name}"
        if inner:
            return f"{s.name}, before {inner[0].name}"
        return s.name

    def cuts(self, lo: int, hi: int) -> list[int]:
        """Span starts and ends strictly inside ``(lo, hi)``."""
        i = max(0, bisect.bisect_left(self.starts, lo - self.longest))
        j = bisect.bisect_left(self.starts, hi)
        out = set()
        for s in self.spans[i:j]:
            out.update(x for x in (s.start, s.end) if lo < x < hi)
        return sorted(out)


def named_gaps(pt: program_trace.ProgramTrace) -> list[tuple[str, int, int]]:
    """Every idle gap in the window as ``(span at its midpoint, start,
    ns)``, on each device."""
    win = pt.window()
    if win is None:
        raise ValueError("the trace holds no bench.window span")
    index = Spans(pt.spans)
    return [(index.name_at((s + t) // 2), s, t - s)
            for ops in pt.ops.values()
            for s, t in trace_lib.gaps(ops, win.start, win.end)]


def split_gaps(pt: program_trace.ProgramTrace) -> dict[str, int]:
    """All idle ns in the window, cut at span boundaries, by
    ``Spans.place_at`` of each piece."""
    win = pt.window()
    index = Spans(pt.spans)
    out: dict[str, int] = {}
    for ops in pt.ops.values():
        for s, t in trace_lib.gaps(ops, win.start, win.end):
            edges = [s, *index.cuts(s, t), t]
            for a, b in zip(edges, edges[1:]):
                k = index.place_at((a + b) // 2)
                out[k] = out.get(k, 0) + b - a
    return out


def summary(gaps, min_ns: int) -> dict[str, tuple[int, int, int]]:
    """Per span name: (gaps over ``min_ns``, their total ns, longest ns)."""
    out: dict[str, tuple[int, int, int]] = {}
    for name, _, ns in gaps:
        if ns > min_ns:
            n, total, top = out.get(name, (0, 0, 0))
            out[name] = (n + 1, total + ns, max(top, ns))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--min-ms", type=float, default=1.0)
    ap.add_argument("--top", type=int, default=20)
    args = ap.parse_args(argv)
    pt = program_trace.load(args.trace)
    gaps = named_gaps(pt)
    win = pt.window()
    n_dev = max(1, len(pt.ops))
    idle = sum(ns for _, _, ns in gaps) / n_dev
    reads = program_trace.starts_inside(pt.program_spans("fleet.read"), win)
    print(f"window {win.dur / 1e9:.6f} s, idle {idle / 1e9:.6f} s in "
          f"{len(gaps)} gaps; {len(reads)} snapstore.fleet.read spans "
          f"opened in it")
    min_ns = int(args.min_ms * 1e6)
    print(f"gaps over {args.min_ms} ms, by the span open at the midpoint: "
          f"count, total ms, longest ms")
    for name, (n, total, top) in sorted(summary(gaps, min_ns).items(),
                                        key=lambda kv: -kv[1][1]):
        print(f"  {name}: {n}, {total / 1e6:.3f}, {top / 1e6:.3f}")
    print(f"the {args.top} longest: ms, span, ms after the window opened")
    for name, s, ns in sorted(gaps, key=lambda g: -g[2])[:args.top]:
        print(f"  {ns / 1e6:.3f}  {name}  {(s - win.start) / 1e6:.3f}")
    print("all idle time, cut at span boundaries: ms"
          + (", ms per fleet read" if reads else ""))
    for place, ns in sorted(split_gaps(pt).items(), key=lambda kv: -kv[1]):
        per = f", {ns / n_dev / 1e6 / len(reads):.4f}" if reads else ""
        print(f"  {place}: {ns / n_dev / 1e6:.3f}{per}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
