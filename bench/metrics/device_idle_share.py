"""device_idle_share (%): 1 − (union of the device-op intervals ÷ the
traced window), from the profiler trace."""
import devtrace


def value(ctx):
    parsed = ctx["parsed"]
    if not parsed["ops"]:
        return None
    lo, hi = devtrace.window(parsed)
    return 100.0 * (1.0 - devtrace.busy_ns(parsed) / (hi - lo))
