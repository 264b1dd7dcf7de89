"""Share of the HBM roofline that the decode attention kernel reaches: the
K and V bytes of every decode row's real context (``bench/work.py``) at
the chip's peak bandwidth, over the device time of the kernel's events,
found by name."""

KERNEL = "fused_chain_attention"


def read(run):
    if run.trace is None:
        return None
    device_s = sum(s for name, s in run.trace.by_kernel_s.items()
                   if KERNEL in name)
    need = run.layer.get("decode_kv_bytes")
    if device_s <= 0 or not need:
        return None
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / device_s
