"""Model FLOP/s utilization of the train step: the forward and backward
operations of a sample as the configuration's reference counts them
(``train_flops_per_sample``), times the samples of the traced steps, over
the step program's own device time (``jit_step``, as ``step_ms`` reads it)
and the chip's bf16 peak.  Silent where the reference has no count."""

MODULE = "jit_step"


def read(ctx):
    t, peaks, c = ctx["trace"], ctx["peaks"], ctx["counters"]
    m = (t or {}).get("modules", {}).get(MODULE)
    if not m or not m["count"] or m["s"] <= 0 or not peaks:
        return None
    flops = ctx["yardstick"].dlrm_train_flops_per_sample(ctx["cfg"])
    if flops is None:
        return None
    rate = flops * c["batch"] * m["count"] / m["s"]
    return 100.0 * rate / peaks["bf16_flops_per_s"]
