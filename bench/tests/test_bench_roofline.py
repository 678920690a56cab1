"""The roofline work functions on one tap, against a hand count."""
import manifest

PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_precond_work_one_tap():
    m = manifest.metric_module("precond_roofline")
    # FC0: d_in 16384 (Brand, w = 230 + 256), d_out 2048 (Brand, w = 486)
    flops, nbytes = m.tap_work(16384, 2048, 230, 256)
    assert flops == 2 * 2048 * 16384 * 972 + 2 * 16384 * 2048 * 972
    assert nbytes == 8 * 16384 * 2048 + 4 * 16384 * 486 + 4 * 2048 * 486
    # a narrow side (d = 10 <= r): one dense product, w = d
    flops, _ = m.tap_work(2048, 10, 230, 256)
    assert flops == 2 * 10 * 2048 * 972 + 2 * 2048 * 10 * 10
    t = m.least_time_s([(16384, 2048)], 230, 256, PEAK)
    assert t == max(m.tap_work(16384, 2048, 230, 256)[0] / 197e12,
                    m.tap_work(16384, 2048, 230, 256)[1] / 819e9)


def test_brand_work_one_factor():
    m = manifest.metric_module("brand_roofline")
    d, r, n = 16384, 230, 256
    flops, nbytes = m.factor_work(d, r, n)
    # panel and X⊥ 2·d·r·n each, CholeskyQR2 two rounds of 2·d·n² each,
    # the core's eigh 9·(r+n)³, the rotation 2·d·(r+n)²
    assert flops == (2 * 2 * d * r * n + 2 * 2 * 2 * d * n * n
                     + 9 * 486 ** 3 + 2 * d * 486 ** 2)
    assert nbytes == 4 * d * 2 * (r + n)
    assert m.factor_work(486, r, n) == (0.0, 0.0)    # d <= r + n_stat
    assert m.least_time_s([(10, 486)], r, n, PEAK) == 0.0
