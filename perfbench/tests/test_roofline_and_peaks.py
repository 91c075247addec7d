"""Operations and bytes against hand counts at one shape; the peaks."""
import pytest

from perfbench.harness import device, roofline


def test_flash_counts_at_one_shape():
    # B 1, H 2, T 4, hd 8: 10 causal pairs; forward 2 products of
    # 2 * 8 ops a pair a head = 2 * 16 * 10 * 2 = 640
    ops, nbytes = roofline.flash_forward(1, 2, 4, 8)
    assert ops == 640
    assert nbytes == 4 * (1 * 2 * 4 * 8) * 2       # q k v o, bf16
    ops_b, bytes_b = roofline.flash_backward(1, 2, 4, 8)
    assert ops_b == 5 * 16 * 10 * 2 == 1600
    assert bytes_b == 8 * 64 * 2


def test_paged_counts_at_one_shape():
    # 1000 cached positions, 16 heads of 128: q.K and p.V = 2 * 2 * 128
    # ops a head a position; K and V read once in bf16
    ops, nbytes = roofline.paged_decode(1000, 16, 16, 128)
    assert ops == 4 * 128 * 16 * 1000
    assert nbytes == 2 * 16 * 128 * 2 * 1000


def test_share_of_the_roofline_says_which_limit_binds():
    peak = device.peaks("TPU v5 lite")
    ops, nbytes = roofline.paged_decode(10**6, 16, 16, 128)
    least, limit = roofline.least_seconds(ops, nbytes, peak)
    assert limit == "memory" and least == pytest.approx(nbytes / 819e9)
    assert roofline.share_pct(ops, nbytes, 2 * least, peak) == \
        pytest.approx(50.0)
    assert roofline.share_pct(ops, nbytes, 0.0, peak) is None
    ops, nbytes = roofline.flash_forward(8, 12, 2048, 128)
    assert roofline.least_seconds(ops, nbytes, peak)[1] == "compute"


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        device.peaks("TPU v9 imaginary")
    with pytest.raises(device.NoChipError):
        device.require_chips(1)  # these tests run on the CPU
