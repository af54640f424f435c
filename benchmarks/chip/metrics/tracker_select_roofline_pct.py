"""Share of its memory roofline that the Pallas MFU selection reaches: the
bytes it must move (counters read and written, ids written) over the chip's
HBM bandwidth, divided by the device time of its program in the trace."""

MODULE = "jit_tracker_select"


def read(ctx):
    t, peaks, c = ctx["trace"], ctx["peaks"], ctx["counters"]
    m = (t or {}).get("modules", {}).get(MODULE)
    calls = c["selection_calls_per_save"]
    if not m or not m["s"] or not peaks or not calls or not c["window_saves"]:
        return None
    ys = ctx["yardstick"]
    per_save = sum(ys.tracker_select_bytes(x["n"], x["k"], x["seg"],
                                           x["pending"]) for x in calls)
    if m["count"] != len(calls) * c["window_saves"]:
        return None
    need_s = per_save * c["window_saves"] / peaks["hbm_bytes_per_s"]
    return 100.0 * need_s / m["s"]
