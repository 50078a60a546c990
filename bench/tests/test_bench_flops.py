"""The benchmark's operation and byte counts, pinned by hand."""
import pytest

from bench.flops import cnn

RESNET50 = {"kind": "resnet", "stage_sizes": [3, 4, 6, 3], "width": 64,
            "n_classes": 1000, "img": 224, "in_ch": 3}
COSMOFLOW = {"kind": "cosmoflow", "img": 256, "in_ch": 4, "width": 16,
             "n_conv": 5, "dense": [128, 64], "n_targets": 4}
V5E = {"flops": 197e12, "bw": 819e9}


def test_resnet50_forward_is_the_published_4_1_gmac():
    # ResNet-50 v1.5 (stride on the 3x3) at 224^2: 4.09e9 multiply-adds
    assert cnn.forward_macs(RESNET50) == pytest.approx(4.09e9, rel=2e-3)


def test_cosmoflow_sums_to_a_hand_count_at_edge_16():
    m = {"kind": "cosmoflow", "img": 16, "in_ch": 4, "width": 2, "n_conv": 2,
         "dense": [8, 4], "n_targets": 3}
    conv0 = 16 ** 3 * 27 * 4 * 2          # 16^3 outputs, 4 -> 2 channels
    conv1 = 8 ** 3 * 27 * 2 * 4           # after the pool: 8^3, 2 -> 4
    dense = (4 * 4 ** 3) * 8 + 8 * 4 + 4 * 3
    assert cnn.forward_macs(m) == conv0 + conv1 + dense
    # forward, weight gradient and input gradient, less the stem's input
    # gradient, two operations a multiply-add
    assert cnn.train_flops_per_sample(m) == 2 * (3 * (conv0 + conv1 + dense)
                                                 - conv0)


def test_conv_bytes_count_each_operand_once():
    c = cnn.Conv("c", (8, 8), (4, 4), (3, 3), 2, 5)
    x, w, y = 3 * 64 * 2, 9 * 2 * 5, 3 * 16 * 5
    for p in cnn.PASSES:
        assert c.bytes(3, p) == 4 * (x + w + y)
        assert c.flops(3, p) == 2 * 3 * 16 * 9 * 2 * 5


@pytest.mark.parametrize("m", [RESNET50, COSMOFLOW], ids=["resnet50", "cosmoflow"])
@pytest.mark.parametrize("batch", [1, 16, 128])
def test_mfu_at_the_roofline_is_at_most_100(m, batch):
    """A step that takes the roofline time of all its passes reads at most
    100% of the peak, by construction."""
    t = cnn.step_roofline_s(m, batch, V5E["flops"], V5E["bw"])
    mfu = 100 * cnn.train_flops_per_sample(m) * batch / (t * V5E["flops"])
    assert 0 < mfu <= 100
    assert cnn.conv_roofline_s(m, batch, V5E["flops"], V5E["bw"]) < t
