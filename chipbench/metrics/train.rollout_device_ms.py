"""Device time of the rollout per call of the jitted training
iteration: the ops under the program's named scope ``rollout`` (the
actor fleet's int8 forwards, sampling, env steps, relayout and episode
returns), from the profiler trace."""
import scopes


def read(ctx):
    return scopes.per_call_ms(ctx, "rollout")
