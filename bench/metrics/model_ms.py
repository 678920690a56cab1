"""model_ms (ms): device time per step of the ops outside every ``kfac/``
scope: the model's forward and backward pass and the parameter update."""
import devtrace


def value(ctx):
    if not ctx["steps"]:
        return None
    t = devtrace.scope_ns(ctx["parsed"], lambda s: not devtrace.is_kfac(s))
    return t / 1e6 / ctx["steps"]
