"""The fold kernels' share of their roofline: the summed roofline-
minimum time of the fold-kernel launches in the window (``flops.py``:
the larger of FLOPs over the bf16 peak and least bytes over the HBM
bandwidth, at the launch's batch) over their summed device time in the
profiler trace, in %."""


def read(rec):
    fold = rec.get("fold")
    if not fold or fold["device_s"] <= 0:
        return None
    return 100.0 * fold["roofline_min_s"] / fold["device_s"]
