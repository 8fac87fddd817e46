"""Operation and byte counts against worked numbers at published widths,
and the table of peaks."""
import pytest

from bench import common
from bench.counts import dense_lm, paged_attn, topk_mask

QWEN = common.load("configs", "qwen3-4b-35L")
PHI = common.load("configs", "phi4-mini-3.8b")


def test_layer_parameters():
    # 2560*48*128 + 32*128*2560 + 3*2560*9728
    assert dense_lm.layer_params(QWEN) == 100_925_440
    # 3072*40*128 + 24*128*3072 + 3*3072*8192
    assert dense_lm.layer_params(PHI) == 100_663_296


def test_elastic_zo_lane_flops_qwen3_4b_35L():
    # 35 * (2*100,925,440 + 4*512.5*32*128) + 2*2560*151936
    assert dense_lm.forward_flops_per_token(QWEN, 1024) == 8_136_581_120
    # two passes of (forward + 2 x (one layer + head) forward)
    assert dense_lm.elastic_zo_flops_per_token(QWEN, 1024, 1, 1) \
        == 20_225_802_240


def test_serve_flops_phi4_mini():
    # 32 * (2*100,663,296 + 4*600*24*128) + 2*3072*200064
    assert dense_lm.decode_flops(PHI, 600) == 7_907_573_760
    # 32 * 384 * (2*100,663,296 + 4*192.5*24*128) + 2*3072*200064
    assert dense_lm.prefill_flops(PHI, 384) == 2_504_196_882_432


def test_paged_attention_needed_work():
    # 32 layers * K and V * 600 keys * 8 heads * 128 * 2 bytes
    assert paged_attn.token_bytes(PHI, 600) == 78_643_200
    assert paged_attn.token_flops(PHI, 600) == 32 * 4 * 600 * 24 * 128
    pk = common.peaks("TPU v5 lite")
    assert paged_attn.least_seconds(PHI, 600, pk) == 78_643_200 / 819e9


def test_topk_needed_bytes():
    assert topk_mask.call_bytes(16, 200192) == 25_624_576


def test_peaks_table():
    pk = common.peaks("TPU v5 lite")
    assert (pk["bf16_flops"], pk["int8_ops"], pk["hbm_bytes_per_s"]) == \
        (197e12, 393e12, 819e9)
    with pytest.raises(KeyError):
        common.peaks("TPU v9 imaginary")
