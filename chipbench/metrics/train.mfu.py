"""Share of the chip's peak that the on-policy iteration's needed work
would take, over the measured time per iteration.

The int8 actor forwards count at the int8 peak and the float32
learner's forwards and backwards at the bf16 peak (``work/<config>.py``,
``peaks.json``); their least time, over the window's wall time per
whole iteration, per chip.
"""


def read(ctx):
    if ctx.get("kind") != "train" or not ctx["window"]["iters"]:
        return None
    w, p = ctx["work"], ctx["peaks"]
    least_s = w["int8_ops"] / p["int8_ops"] + w["fp_flops"] / p["bf16_flops"]
    iter_s = ctx["window"]["elapsed_s"] / ctx["window"]["iters"]
    return 100.0 * least_s / iter_s / ctx["chips"]
