"""The FLOP and byte functions against a count made by hand for both
configurations."""

import json
import os

import pytest

from benchmarks.lib import flops, peaks
from benchmarks.references import transformer_lm as reference

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(os.path.dirname(HERE), "configs")


def arch(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return reference.arch_of(json.load(f))


def test_gpt2_small_by_hand():
    a = arch("gpt2_small")
    # per layer: qkv 768*2304 + out 768*768 + ffn 2*768*3072 = 7,077,888
    # tied head 768*50257 = 38,597,376, once, as a matrix product
    assert flops.matmul_params(a) == 12 * 7_077_888 + 38_597_376
    # attention, causal: 6 products * 2 * L * hidden * layers / 2
    assert flops.attention_flops_per_token(a, 1024, True) == \
        6 * 2 * 1024 * 768 * 12 / 2
    assert flops.train_flops_per_token(a, 1024, 1.0, True) == \
        6 * 123_532_032 + 56_623_104


def test_bert_base_by_hand():
    a = arch("bert_base")
    head = 768 * 30522 + 768 * 768
    assert flops.matmul_params(a, 0.15) == pytest.approx(
        12 * 7_077_888 + 0.15 * head)
    assert flops.attention_flops_per_token(a, 512, False) == \
        6 * 2 * 512 * 768 * 12
    assert flops.train_flops_per_token(a, 512, 0.15, False) == pytest.approx(
        6 * (84_934_656 + 0.15 * 24_030_720) + 56_623_104)


def test_flash_cost_and_bound():
    # one layer, batch 8, 12 heads of 64, L=1024, causal, bf16:
    # 6 products * 2 * 8*12 * 1024^2 * 64 / 2 ; 12 tensors of 8*1024*768*2 B
    c = flops.flash_attention_cost(8, 1024, 12, 64, causal=True)
    assert c["flops"] == 6 * 2 * 96 * 1024 * 1024 * 64 / 2
    assert c["bytes"] == 12 * 8 * 1024 * 768 * 2
    t, bound = flops.roofline_seconds(c, peaks.peaks_of("TPU v5 lite"))
    assert bound == "flops"
    assert t == pytest.approx(c["flops"] / 197e12)
    # a long-sequence call is further on the compute side, a short one is
    # bound by bytes
    assert flops.roofline_seconds(
        flops.flash_attention_cost(8, 128, 12, 64, True),
        peaks.peaks_of("TPU v5 lite"))[1] == "bytes"


def test_unknown_chip_is_an_error():
    with pytest.raises(ValueError):
        peaks.peaks_of("TPU v9")
