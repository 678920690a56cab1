"""brand_linear_ms (ms): device time, per step that runs a Brand light
update, of its parts linear in the factor's side d: the projection panel
(``brand_panel``), CholeskyQR2 (``brand_qr``) and the rotation of the
basis (``brand_rotate``)."""
import loopspans


def value(ctx):
    return loopspans.per_light_step_ms(ctx["parsed"],
                                       loopspans.BRAND_LINEAR)
