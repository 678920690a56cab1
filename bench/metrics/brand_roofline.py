"""brand_roofline (%): the least time the chip needs for one Brand light
update of every factor it applies to, at its published peaks, over the
device time under ``*/light_brand`` per step that runs it.

The work follows the algorithm (arXiv:2210.08494, Alg. 3-4), whatever
kernel implements it: a factor of side d takes the Brand update where
d > r + n_stat (the paper's rule).  Its state U (d × r after truncation)
absorbs the n = n_stat stats columns X: the panel C = UᵀX and
X⊥ = X − U C (2·d·r·n FLOPs each), CholeskyQR2 of X⊥ (two rounds of a
Gram product and a triangular apply, 2·d·n² each), the symmetric
eigen-decomposition of the (r+n)-wide core with its eigenvectors
(9·(r+n)³ FLOPs, the count of the symmetric QR algorithm in Golub and
Van Loan, Matrix Computations, 4th ed., section 8.3), and the rotation
of [U Q] by the core's eigenvectors (2·d·(r+n)² FLOPs).  Bytes: U and X
read, the new basis written, in float32.
"""
import devtrace
import peaks


def factor_work(d: int, r: int, n: int):
    """(FLOPs, bytes) of one Brand light update of a side-d factor, or
    (0, 0) where the update does not apply."""
    if d <= r + n:
        return 0.0, 0.0
    flops = (4.0 * d * r * n + 8.0 * d * n * n + 9.0 * (r + n) ** 3
             + 2.0 * d * (r + n) ** 2)
    nbytes = 4.0 * d * (r + n + (r + n))
    return flops, nbytes


def least_time_s(taps, r: int, n_stat: int, peak: dict) -> float:
    flops = nbytes = 0.0
    for d_in, d_out in taps:
        for d in (d_in, d_out):
            f, b = factor_work(d, r, n_stat)
            flops, nbytes = flops + f, nbytes + b
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


def value(ctx):
    built = ctx["built"]
    per = [t for t in devtrace.per_step_scope_ns(ctx["parsed"],
                                                 devtrace.is_light_brand)
           if t > 0]
    if not per:
        return None
    opt = ctx["cell"].job["optimizer"]
    taps = []
    for tp in built.taps.values():
        n = 1
        for s in tp.stack:
            n *= int(s)
        taps += [(tp.d_in, tp.d_out)] * n
    least = least_time_s(taps, opt["r"], opt["n_stat"],
                         peaks.peaks_for(ctx["device_kind"]))
    return 100.0 * least / (sum(per) / len(per) / 1e9)
