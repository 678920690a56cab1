"""factor_busy_step_ms (ms): device time under ``kfac/factor/`` per step
that runs factor work (stats, Brand light or a heavy refresh): the
steps that make the step-time tail."""
import devtrace


def value(ctx):
    per = [t for t in devtrace.per_step_scope_ns(ctx["parsed"],
                                                 devtrace.is_factor)
           if t > 0]
    if not per:
        return None
    return sum(per) / len(per) / 1e6
