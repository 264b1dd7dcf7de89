"""Device time of the deepest depth class's part of the read per read:
the ops under the ``fleet.class<C>`` named scope with the largest ``C``
(``repro.core.depth_classes``) in the window, over the
``snapstore.fleet.read`` spans that opened in it. Nothing to read where
no op carries a class scope (a dense fleet's read)."""

import re

from bench import program_trace

CLASS = re.compile(r"fleet\.class(\d+)")


def read(run):
    pt = program_trace.for_run(run)
    if pt is None:
        return None
    depths = {int(m[1]) for ops in pt.ops.values() for op in ops
              for part in op.scope.split("/") if (m := CLASS.fullmatch(part))}
    if not depths:
        return None
    return program_trace.scope_ms_per_read(run, f"fleet.class{max(depths)}")
