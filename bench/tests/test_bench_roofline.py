"""The Llama family's FLOP and byte counts against hand-worked values at
two configurations' published widths, and the table of peaks."""
import json
from pathlib import Path

import pytest

from bench import roofline as RF
from bench.families import llama as FAM

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
# granite-3.0-1b-a400m's published widths, as the program runs them
GRANITE = {"n_layers": 24, "d_model": 1024, "n_heads": 16, "n_kv_heads": 8,
           "d_head": 64, "d_ff": 512, "vocab_size": 49155,
           "moe": {"n_experts": 32, "top_k": 8}}


def _cfg(name):
    if name == "granite-moe-1b-a400m":
        return GRANITE
    return json.loads((CONFIGS / f"{name}.json").read_text())["program"]


def test_active_weights_per_layer():
    # 960*15*64*2 + 960*5*64*2 + 3*960*2560
    assert FAM.layer_matmul_params(_cfg("smollm-360m")) == 9_830_400
    # 1024*16*64*2 + 1024*8*64*2 + 8 experts * 3*1024*512 + router 1024*32
    assert FAM.layer_matmul_params(_cfg("granite-moe-1b-a400m")) == 15_761_408


def test_decode_token_flops():
    # 2*32*9830400 + 4*32*15*64*1000 + 2*960*49152
    assert FAM.token_flops(_cfg("smollm-360m"), 1000, head=True) == \
        846_397_440
    # without the head, granite: 2*24*15761408 + 4*24*16*64*10
    assert FAM.token_flops(_cfg("granite-moe-1b-a400m"), 10, head=False) == \
        756_547_584 + 983_040


def test_chunk_flops_are_causal():
    # 2 tokens from position 0 attend 1 and 2 positions
    assert FAM.chunk_flops(_cfg("smollm-360m"), 0, 2) == \
        2 * 32 * 9_830_400 * 2 + 4 * 32 * 15 * 64 * 3


def test_decode_attention_work():
    f, b = FAM.decode_attention_work(_cfg("smollm-360m"), [100])
    assert f == 32 * 4 * 15 * 64 * 100
    # K and V of 100 positions in bf16, float32 query and partials out
    assert b == 32 * (2 * 5 * 64 * 100 * 2 + 4 * (2 * 15 * 64 + 2 * 15))
    assert FAM.decode_attention_work(_cfg("smollm-360m"), []) == (0.0, 0.0)


def test_roofline_share_and_bound():
    chip = RF.peaks("TPU v5 lite")
    share, bound = RF.roofline_share(197e12, 1.0, 2.0, chip)
    assert share == pytest.approx(50.0) and bound == "compute"
    share, bound = RF.roofline_share(1.0, 819e9, 4.0, chip)
    assert share == pytest.approx(25.0) and bound == "memory"


def test_unknown_device_has_no_peak():
    with pytest.raises(KeyError):
        RF.peaks("cpu")
