"""Required-FLOPs functions against counts worked by hand."""

import json
import os

import pytest

from benchmarks.flops import lm, vgg
from benchmarks.lib import cells, peaks

GPT2 = json.load(open(os.path.join(cells.BENCH_DIR, "configs", "gpt2-small.json")))
VGG = json.load(open(os.path.join(cells.BENCH_DIR, "configs", "vgg16-cifar10.json")))

# 12 x (4 x 768^2 + 2 x 768 x 3072) + 50257 x 768
P_MATMUL = 84_934_656 + 38_597_376


def test_gpt2_matmul_params():
    assert lm.lm_matmul_params(GPT2) == P_MATMUL == 123_532_032


@pytest.mark.parametrize("seq_len,batch,attention_per_token,total", [
    (1024, 32, 56_623_104, 797_815_296 * 32_768),     # 6 x 12 x 1024 x 768
    (4096, 8, 226_492_416, 967_684_608 * 32_768),
    (8192, 4, 452_984_832, 1_194_177_024 * 32_768),
])
def test_gpt2_required_flops(seq_len, batch, attention_per_token, total):
    assert lm.lm_attention_flops_per_token(GPT2, seq_len) == attention_per_token
    # half of bench.py's 12 L T d: the masked half is not required
    assert attention_per_token * 2 == 12 * 12 * seq_len * 768
    assert lm.lm_required_flops_per_step(GPT2, seq_len, batch) == total
    flash = lm.lm_flash_required_per_step(GPT2, seq_len, batch)
    assert flash["flops"] == attention_per_token * seq_len * batch
    assert flash["bytes"] == 24 * 12 * batch * seq_len * 768


def test_flash_roofline_bound_says_which_binds():
    v5e = peaks.peaks_for("TPU v5 lite")
    for seq_len, batch, flops_bind_by in ((1024, 32, 1.064), (4096, 8, 4.257)):
        need = lm.lm_flash_required_per_step(GPT2, seq_len, batch)
        ratio = (need["flops"] / v5e["flops_per_s_bf16"]) / (need["bytes"] / v5e["hbm_bytes_per_s"])
        assert ratio == pytest.approx(flops_bind_by, rel=1e-3)


CONVS_32 = [3_538_944, 75_497_472, 37_748_736, 75_497_472, 37_748_736, 75_497_472, 75_497_472,
            37_748_736, 75_497_472, 75_497_472, 18_874_368, 18_874_368, 18_874_368]


def test_vgg16_forward_itemised():
    folded = vgg.vgg16_forward_flops_per_image(VGG, 32, folded_fc1=True)
    nominal = vgg.vgg16_forward_flops_per_image(VGG, 32, folded_fc1=False)
    assert folded["convs"] == nominal["convs"] == CONVS_32 and sum(CONVS_32) == 626_393_088
    assert folded["fcs"] == [2 * 512 * 4096, 2 * 4096 * 4096, 2 * 4096 * 10]
    assert nominal["fcs"][0] == 2 * 25_088 * 4096
    # at 224 the last map is 7x7: nothing folds
    assert vgg.vgg16_forward_flops_per_image(VGG, 224)["fcs"][0] == 2 * 25_088 * 4096


def test_vgg16_required_per_step():
    # 3 x forward, less the first convolution's input gradient
    assert vgg.vgg16_required_flops_per_step(VGG, 32, 4096) == (3 * 664_223_744 - 3_538_944) * 4096
    assert vgg.vgg16_required_flops_per_step(VGG, 32, 4096, folded_fc1=False) == (3 * 865_550_336 - 3_538_944) * 4096
    assert vgg.vgg16_required_flops_per_step(VGG, 32, 4096) == pytest.approx(8.147e12, rel=1e-3)


def test_peaks_unknown_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["flops_per_s_bf16"] == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")
