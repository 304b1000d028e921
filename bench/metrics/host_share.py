"""Engine host: seconds of the traced slice inside ``loop.step`` spans
(admission, staging, dispatch, finishing, drain) less the
``engine.sync`` spans within them (the host waiting for the device),
over the slice, in %. None where the trace has no engine spans."""


def read(ctx):
    t = ctx.trace
    if not t or t["window_s"] <= 0 or not t.get("host_s"):
        return None
    return 100.0 * t["host_s"] / t["window_s"]
