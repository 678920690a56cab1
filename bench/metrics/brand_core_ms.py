"""brand_core_ms (ms): device time, per step that runs a Brand light
update, of its (r+n)-wide core: assembling the core and its symmetric
eigen-decomposition (the ops under ``*/light_brand`` whose first
``brand_*`` path part is ``brand_core``)."""
import loopspans


def value(ctx):
    return loopspans.per_light_step_ms(ctx["parsed"], loopspans.BRAND_CORE)
