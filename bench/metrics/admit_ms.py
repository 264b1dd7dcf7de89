"""Mean host time of one admission (``Engine.add_request``: golden fork
and suffix prefill, or full prefill) in the window, from the benchmark's
``admit`` spans."""


def read(run):
    spans = run.spans.get("admit")
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
