"""dispatch_idle_ms (ms): device-idle time per step that lies inside the
training loop's ``train/dispatch`` host span (the step call: argument
handling, output allocation, the launch).  Idle is measured as for
``device_idle_share``: the traced window less the union of the op
intervals."""
import loopspans


def value(ctx):
    return loopspans.dispatch_idle_ms(ctx["parsed"], ctx["steps"])
