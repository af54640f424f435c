"""Device time of the train-step program per step, from the trace's module
line (the emulator's jitted ``step``)."""

MODULE = "jit_step"


def read(ctx):
    t = ctx["trace"]
    m = (t or {}).get("modules", {}).get(MODULE)
    if not m or not m["count"]:
        return None
    return 1e3 * m["s"] / m["count"]
