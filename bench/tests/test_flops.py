"""FLOP and byte counts against counts made by hand."""
from flops import fed_compress, mclr


def test_mclr_forward():
    # x W: 784 x 10 multiply-adds, two operations each; the bias: 10 adds
    assert mclr.forward_per_sample({"n_features": 784, "n_classes": 10}) \
        == 2 * 7840 + 10 == 15690


def test_fed_compress_bytes():
    # 100 rows of 7,850 f32 in, int8 out, one 128-lane f32 scale row each
    assert fed_compress.cost(100, 7850) == (
        0, 100 * 7850 * 4 + 100 * 7850 + 100 * 512)
