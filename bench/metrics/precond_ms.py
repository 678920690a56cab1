"""precond_ms (ms): device time per step under ``kfac/precond/``."""
import devtrace


def value(ctx):
    t = devtrace.scope_ns(ctx["parsed"], devtrace.is_precond)
    if not ctx["steps"] or t <= 0:
        return None
    return t / 1e6 / ctx["steps"]
