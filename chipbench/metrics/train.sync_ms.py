"""Host time per iteration spent packing the learner's weights to int8
and passing them through ``FleetSync`` (mean over the window)."""


def read(ctx):
    if ctx.get("kind") != "train" or not ctx["window"]["sync_s"]:
        return None
    s = ctx["window"]["sync_s"]
    return 1e3 * sum(s) / len(s)
