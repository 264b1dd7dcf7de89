"""Share of the HBM roofline that the fleet's reads reach: the bytes the
window's dispatches need (``bench/work.py``: the L2 entries each page's
resolution must consult and the payload gathered) at the chip's peak
bandwidth, over the device's busy time. Only reads run in a fleet
window, so all busy time is read time."""


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    need = run.layer.get("needed_bytes")
    if not need:
        return None
    least_s = need / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / run.trace.busy_s
