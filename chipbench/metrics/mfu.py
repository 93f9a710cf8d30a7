"""The whole forward's share of the chip's bf16 peak: FLOPs of the
images served in the traced window (2 x MACs of every conv and dense
layer, ``flops.py``; padded slots do not count) over the window's
length times the peak, in %."""


def read(rec):
    dev, spans = rec.get("device_trace"), rec.get("spans")
    if not dev or not spans or not spans.get("images"):
        return None
    return (100.0 * spans["images"] * rec["flops_per_image"]
            / (dev["window_s"] * rec["peak"]["flops_per_s"]))
