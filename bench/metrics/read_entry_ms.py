"""Host time of the program's entry per fleet read: the mean self time
of the ``snapstore.fleet.read`` spans that opened in the window, i.e. the
host's enqueue of one read program (the benchmark waits for it outside
the span)."""

from bench import program_trace


def read(run):
    pt = program_trace.for_run(run)
    win = pt.window() if pt else None
    if win is None:
        return None
    spans = pt.program_spans()
    reads = program_trace.starts_inside(pt.program_spans("fleet.read"), win)
    if not reads:
        return None
    own = [program_trace.self_ns(s, spans) for s in reads]
    return sum(own) / len(own) / 1e6
