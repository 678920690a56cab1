"""update_ms (ms): device time per step under the program's ``update``
scope and outside every ``kfac/`` scope: the optimizer's own update
(the fallback AdamW, momentum, clipping, weight decay) and its
application to the parameters."""
import loopspans


def value(ctx):
    return loopspans.per_step_ms(ctx["parsed"], ctx["steps"],
                                 loopspans.is_update)
