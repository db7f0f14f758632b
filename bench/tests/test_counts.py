import pytest

import counts
from peaks import peaks


def hand_count_macs_224():
    """ResNet-50 v1.5 at 224 px, layer by layer: (out side, k, cin, cout)."""
    layers = [(112, 7, 3, 64)]                                  # stem
    # stage 0 at 56 px: 3 blocks of 1x1 64, 3x3 64, 1x1 256, + projection
    layers += [(56, 1, 64, 64), (56, 3, 64, 64), (56, 1, 64, 256),
               (56, 1, 64, 256)]
    layers += 2 * [(56, 1, 256, 64), (56, 3, 64, 64), (56, 1, 64, 256)]
    # stage 1: first block reduces 56 -> 28 in its 3x3 and projection
    layers += [(56, 1, 256, 128), (28, 3, 128, 128), (28, 1, 128, 512),
               (28, 1, 256, 512)]
    layers += 3 * [(28, 1, 512, 128), (28, 3, 128, 128), (28, 1, 128, 512)]
    layers += [(28, 1, 512, 256), (14, 3, 256, 256), (14, 1, 256, 1024),
               (14, 1, 512, 1024)]
    layers += 5 * [(14, 1, 1024, 256), (14, 3, 256, 256),
                   (14, 1, 256, 1024)]
    layers += [(14, 1, 1024, 512), (7, 3, 512, 512), (7, 1, 512, 2048),
               (7, 1, 1024, 2048)]
    layers += 2 * [(7, 1, 2048, 512), (7, 3, 512, 512), (7, 1, 512, 2048)]
    return sum(s * s * k * k * ci * co for s, k, ci, co in layers) \
        + 2048 * 1000                                           # head


def test_flop_per_image_matches_hand_count():
    flop = counts.flop_per_image(224)
    assert abs(flop - 2 * hand_count_macs_224()) <= 0.01 * flop
    # the published figure for ResNet-50 v1.5: about 4.1 G multiply-adds
    assert abs(flop / 2 - 4.1e9) <= 0.01 * 4.1e9


def test_weights_are_the_published_size():
    assert counts.param_count() == 25_556_032     # 51,112,064 B in bf16


def test_roofline_is_memory_bound_at_batch_1_and_compute_bound_at_16():
    p = peaks("TPU v5 lite")
    b1 = counts.roofline_s(1, p)
    assert b1 == pytest.approx(counts.bytes_per_exec(1) / p["hbm_bytes_s"])
    b16 = counts.roofline_s(16, p)
    assert b16 == pytest.approx(16 * counts.flop_per_image()
                                / p["bf16_flop_s"])


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks("cpu")
