"""Model FLOP utilization of the decode steps: the FLOPs the window's
decode steps need over their real rows (``bench/work.py``: two per weight
per row, and attention over each row's context) over the device time of
the decode step's module at the chip's bf16 peak."""

MODULE = "paged_decode_step"


def read(run):
    if run.trace is None:
        return None
    device_s = sum(s for name, s in run.trace.by_module_s.items()
                   if MODULE in name)
    flops = run.layer.get("decode_flops")
    if device_s <= 0 or not flops:
        return None
    return 100.0 * flops / run.peaks["bf16_flops_per_s"] / device_s
