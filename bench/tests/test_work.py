"""Operation and byte counts of bench/work.py against values worked out by
hand for both configurations, and the rule that the decode-attention count
follows live lengths only."""
import json
import os
from types import SimpleNamespace

import pytest

from bench import work

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cfg(name):
    return json.load(open(os.path.join(ROOT, "bench", "configs",
                                       name + ".json")))


def test_olmo_counts():
    c = cfg("olmo-1b")
    # attention 4 x 2048 x 2048, SwiGLU 3 x 2048 x 8192
    assert work.layer_params(c) == 16_777_216 + 50_331_648
    assert 16 * work.layer_params(c) + work.head_params(c) == 1_176_764_416
    # 2 x 1,073,741,824 x 512 + 131,072 x 512 x 513 / 2 + 2 x 103,022,592
    assert work.prefill_flops(c, 512) == 1_116_931_096_576
    assert work.decode_token_flops(c, 700) == 2_353_528_832 + 131_072 * 700
    w = work.decode_attn_work(c, 700)
    assert w["flops"] == 131_072 * 700
    # 16 layers x (K and V: 2 x 700 x 16 x 128 x 2 B + q and out 8192 B)
    assert w["bytes"] == 131_072 * 701


def test_nemotron_stage8_counts():
    c = cfg("nemotron-4-15b-stage8")
    assert work.layer_params(c) == 88_080_384 + 301_989_888
    assert work.head_params(c) == 1_572_864_000
    assert work.prefill_flops(c, 2048) == 13_197_486_587_904
    assert work.decode_token_flops(c, 2100) == \
        9_386_852_352 + 196_608 * 2100
    w = work.decode_attn_work(c, 2100)
    assert w["flops"] == 196_608 * 2100
    # 8 layers x (2 x 2100 x 8 x 128 x 2 B + 2 x 48 x 128 x 2 B)
    assert w["bytes"] == 32_768 * 2100 + 196_608


def test_decode_totals_follow_positions():
    c = cfg("olmo-1b")
    peak = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    tot = work.decode_totals(c, [(512, 1), (512, 2)], peak)
    assert tot["flops"] == float(work.decode_token_flops(c, 513)
                                 + work.decode_token_flops(c, 514))
    assert tot["attn_bytes"] == 131_072 * (514 + 515)


@pytest.mark.parametrize("name", ["olmo-1b", "nemotron-4-15b-stage8"])
def test_attention_count_ignores_cache_length(name):
    """decode_attn_roofline's count is the same whatever rows the kernel
    reads: two mixes with different cache lengths give the same least
    time, and a kernel that reads only live rows (less kernel time) reads
    a higher share."""
    from importlib.util import module_from_spec, spec_from_file_location
    sp = spec_from_file_location("r", os.path.join(
        ROOT, "bench", "metrics", "decode_attn_roofline.py"))
    reader = module_from_spec(sp)
    sp.loader.exec_module(reader)
    peak = json.load(open(os.path.join(ROOT, "bench", "peaks.json")))[
        "chips"]["TPU v5 lite"]
    toks = [(512, j) for j in range(1, 200)]

    def ctx(max_out, kernel_s):
        red = SimpleNamespace(op_s=lambda n: kernel_s)
        return SimpleNamespace(spec=cfg(name), peak=peak, red=red,
                               mix={"prompt_len": 512,
                                    "output": {"max": max_out}},
                               decode_tokens=lambda: toks)
    full = reader.read(ctx(512, 0.02))
    assert reader.read(ctx(2048, 0.02)) == full
    assert reader.read(ctx(512, 0.01)) == pytest.approx(2 * full)
