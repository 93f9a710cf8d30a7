"""Host time per batch outside the forward: the mean gap from the end
of one ``kernel`` span (dispatch to readback) to the start of the next,
on the engine's dispatch track, within the window."""


def read(rec):
    gaps = (rec.get("spans") or {}).get("host_gap_s")
    if not gaps or not gaps["n"]:
        return None
    return gaps["sum"] / gaps["n"] * 1e3
