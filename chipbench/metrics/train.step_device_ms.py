"""Device time of one call of the jitted training iteration
(XLA module ``jit_iteration``, profiler trace)."""

MODULE = "jit_iteration"


def read(ctx):
    t = ctx.get("trace")
    m = (t or {}).get("modules", {}).get(MODULE)
    if ctx.get("kind") != "train" or not m or not m["count"]:
        return None
    return 1e3 * m["time_s"] / m["count"]
