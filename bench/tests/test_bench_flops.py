"""The benchmark's operation and byte counts against hand counts for one
decode step of each configuration."""
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import pytest  # noqa: E402

from bench.lib.flops import ModelShape, roofline_s  # noqa: E402


def shape(name):
    return ModelShape.from_config(json.loads(
        (ROOT / "bench" / "configs" / f"{name}.json").read_text()))


def test_mistral_step_by_hand():
    s = shape("mistral-7b-8L-nbl3")
    # 5 attention layers: q,o 4096x4096, k,v 4096x1024; 8 MLPs 3 x
    # 4096x14336; 3 NBL maps 4096x4096; head 4096x32000; 2 flops a MAC
    attn = 2 * (4096 * 4096 * 2 + 4096 * 1024 * 2)
    per_tok = (5 * attn + 8 * 2 * 3 * 4096 * 14336 + 3 * 2 * 4096 * 4096
               + 2 * 4096 * 32000)
    assert per_tok == 3_600_809_984
    # rows of 100 and 1000 live keys: QK and PV, 32 heads of 128, 5 layers
    want = 2 * per_tok + 5 * 4 * 32 * 128 * (100 + 1000)
    assert s.decode_flops([100, 1000]) == want
    flops, byts = s.paged_attn_cost([100, 1000], page_size=64)
    assert flops == 5 * 4 * 32 * 128 * 1100
    page = 2 * 64 * 8 * 128 * 2              # K and V, bf16
    qo = 2 * 32 * 128 * 2
    assert byts == 5 * ((2 + 16) * page + 2 * qo)


def test_minicpm_step_by_hand():
    s = shape("minicpm-2b-nbl15")
    attn = 2 * 4 * 2304 * 2304               # q,k,v,o all 2304x2304
    per_tok = (25 * attn + 40 * 2 * 3 * 2304 * 5760 + 15 * 2 * 2304 * 2304
               + 2 * 2304 * 122753)
    assert s.linear_flops() + s.head_flops() == per_tok
    lens = [512, 700, 1024, 1, 64, 65, 1500, 2000]
    assert s.decode_flops(lens) == (8 * per_tok
                                    + 25 * 4 * 36 * 64 * sum(lens))
    flops, byts = s.paged_attn_cost(lens, page_size=64)
    pages = sum(-(-n // 64) for n in lens)   # 8+11+16+1+1+2+24+32
    assert pages == 95
    assert byts == 25 * (pages * 2 * 64 * 36 * 64 * 2 + 8 * 2 * 36 * 64 * 2)
    assert flops == 25 * 4 * 36 * 64 * sum(lens)


def test_prefill_is_causal_and_counts_one_head_row():
    s = shape("mistral-7b-8L-nbl3")
    n = 3                                    # keys 1 + 2 + 3
    assert s.prefill_flops(n) == (3 * s.linear_flops() + s.head_flops()
                                  + 5 * 4 * 32 * 128 * 6)


def test_window_caps_keys():
    s = shape("mistral-7b-8L-nbl3")          # window 4096
    assert s.keys(5000) == 4096
    assert s.decode_flops([5000]) == s.decode_flops([4096])


@pytest.mark.parametrize("f,b,bound", [(1e12, 1e6, "compute"),
                                       (1e6, 1e12, "memory")])
def test_roofline_picks_larger_bound(f, b, bound):
    t, which = roofline_s(f, b, 197e12, 819e9)
    assert which == bound
    assert t == pytest.approx(max(f / 197e12, b / 819e9))
