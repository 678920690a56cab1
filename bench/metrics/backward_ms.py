"""backward_ms (ms): device time per step of the model's backward pass,
the ops under ``transpose(jvp(model))``, outside every ``kfac/`` scope."""
import loopspans


def value(ctx):
    return loopspans.per_step_ms(ctx["parsed"], ctx["steps"],
                                 loopspans.is_backward)
