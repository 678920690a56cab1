"""factor_ms (ms): device time per step, over all steps of the traced
window, under ``kfac/factor/`` (stats absorb, Brand light update, heavy
refreshes)."""
import devtrace


def value(ctx):
    t = devtrace.scope_ns(ctx["parsed"], devtrace.is_factor)
    if not ctx["steps"] or t <= 0:
        return None
    return t / 1e6 / ctx["steps"]
