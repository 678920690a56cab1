"""forward_ms (ms): device time per step of the model's forward pass, the
ops under the program's ``model`` scope as JAX differentiates it
(``jvp(model)``), outside every ``kfac/`` scope."""
import loopspans


def value(ctx):
    return loopspans.per_step_ms(ctx["parsed"], ctx["steps"],
                                 loopspans.is_forward)
