"""loop_idle_ms (ms): device-idle time per step in the traced window and
outside every ``train/dispatch`` span: the wake-up from the loss sync,
the step callback, the schedule and the batch hand-over.  With
``dispatch_idle_ms`` it adds up to the window's idle time per step."""
import loopspans


def value(ctx):
    return loopspans.loop_idle_ms(ctx["parsed"], ctx["steps"])
