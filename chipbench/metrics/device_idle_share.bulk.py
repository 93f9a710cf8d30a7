"""Share of the window in which no operation ran on the device, in %:
1 - (union of the device's op intervals) / window, from the profiler
trace.  Read in the bulk cells, where it moves images_per_s."""


def read(rec):
    dev = rec.get("device_trace")
    if not dev or dev["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])
