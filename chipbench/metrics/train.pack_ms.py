"""Host time of one weight pack (the learner's weights quantized to
int8 for the fleet): the mean duration of the program's ``train.pack``
span in the profiler trace."""
import scopes


def read(ctx):
    tr = scopes.current()
    spans = tr.host_spans("train.pack") if tr is not None else []
    spans = [(a, b) for name, a, b in spans if name == "train.pack"]
    if ctx.get("kind") != "train" or not spans:
        return None
    return sum(b - a for a, b in spans) / len(spans) / 1e6
