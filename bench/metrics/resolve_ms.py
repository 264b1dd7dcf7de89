"""Device time of the fleet read's resolve per read: the ops under the
read program's ``fleet.resolve`` named scope (both stacked resolvers,
the word-plane slices, the pad to 8-layer blocks, the trust pick) in the
window, over the ``snapstore.fleet.read`` spans that opened in it."""

from bench import program_trace


def read(run):
    return program_trace.scope_ms_per_read(run, "fleet.resolve")
