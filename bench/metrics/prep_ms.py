"""Mean host time of one decode step's preparation
(``PagedKVCache.prepare_step_fused``, wrapped in a ``prep`` span at run
time) in the window."""


def read(run):
    spans = run.spans.get("prep")
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
