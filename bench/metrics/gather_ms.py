"""Device time of the fleet read's gather per read: the ops under the
read program's ``fleet.gather`` named scope (the pool relayout and the
gather kernel, or the jnp gather) in the window, over the
``snapstore.fleet.read`` spans that opened in it."""

from bench import program_trace


def read(run):
    return program_trace.scope_ms_per_read(run, "fleet.gather")
