"""Device time of the learner per call of the jitted training
iteration: the ops under the program's named scope ``learner`` (GAE
with the bootstrap value forward, the minibatch shuffle, the loss's
forward and backward, AdamW), from the profiler trace."""
import scopes


def read(ctx):
    return scopes.per_call_ms(ctx, "learner")
