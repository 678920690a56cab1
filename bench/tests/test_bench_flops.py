"""The configuration's model FLOP count against a hand count."""
import manifest


def test_vgg_forward_flops_per_image():
    cell = manifest.load_cell("vgg16bn-mod.bkfac")
    adapter = manifest.config_module("vgg16bn-mod")
    _, _, taps = adapter.build_model(cell.sizes, 256)
    per_step = adapter.model_flops(cell.sizes, taps, {"batch": 1})
    # hand count, 2·positions·(9·c_in)·c_out per conv, 2·d_in·d_out per FC:
    # stage 0 (32x32): 27·64 + 576·64; stage 1 (16x32): 576·128 + 1152·128;
    # stage 2 (8x32): 1152·256 + 2304·256; stage 3 (4x32): 2304·512 +
    # 4608·512; stage 4 (2x32): 2·4608·512; FC 16384·2048 + 2048·10
    hand = 2 * (1024 * (27 * 64 + 576 * 64)
                + 512 * (576 * 128 + 1152 * 128)
                + 256 * (1152 * 256 + 2304 * 256)
                + 128 * (2304 * 512 + 4608 * 512)
                + 64 * (2 * 4608 * 512)
                + 16384 * 2048 + 2048 * 10)
    assert abs(per_step / 3 - hand) < 1
    assert abs(hand / 1e9 - 2.34) < 0.01
    batch = adapter.model_flops(cell.sizes, taps, {"batch": 128})
    assert abs(batch - 128 * per_step) < 1e3
