"""precond_roofline (%): the least time the chip needs for one step's
preconditioning at its published peaks, over the device time per step
under ``kfac/precond/``.

The work follows the algorithm, whatever kernel implements it: for each
tapped layer W (d_in × d_out) the gradient is multiplied from each side
by the inverse of its damped factor.  A factor of side d holds an
eigenbasis of width w = r + n_stat where the Brand update applies
(the paper's rule d > r + n_stat), else w = min(r, d); applying it to
the p × d gradient costs two products, 2·p·d·w FLOPs each, or one dense
product, 2·p·d², where that is less.  Bytes: the float32 gradient read
and the step written, and each side's basis read once.
"""
import devtrace
import peaks


def width(d: int, r: int, n_stat: int) -> int:
    return r + n_stat if d > r + n_stat else min(r, d)


def tap_work(d_in: int, d_out: int, r: int, n_stat: int):
    """(FLOPs, bytes) of preconditioning one tap's gradient."""
    flops, nbytes = 0.0, 8.0 * d_in * d_out
    for d, p in ((d_in, d_out), (d_out, d_in)):
        w = width(d, r, n_stat)
        flops += 2.0 * p * d * min(2 * w, d)
        nbytes += 4.0 * d * min(w, d)
    return flops, nbytes


def least_time_s(taps, r: int, n_stat: int, peak: dict) -> float:
    flops = nbytes = 0.0
    for d_in, d_out in taps:
        f, b = tap_work(d_in, d_out, r, n_stat)
        flops, nbytes = flops + f, nbytes + b
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


def value(ctx):
    built = ctx["built"]
    if not ctx["steps"]:
        return None
    t = devtrace.scope_ns(ctx["parsed"], devtrace.is_precond) / 1e9
    if t <= 0:
        return None
    opt = ctx["cell"].job["optimizer"]
    taps = [(tp.d_in, tp.d_out) for tp in built.taps.values()
            for _ in range(max(1, _count(tp.stack)))]
    least = least_time_s(taps, opt["r"], opt["n_stat"],
                         peaks.peaks_for(ctx["device_kind"]))
    return 100.0 * least * ctx["steps"] / t


def _count(stack) -> int:
    n = 1
    for s in stack:
        n *= int(s)
    return n
