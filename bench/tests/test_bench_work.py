"""Needed work of one fused step, against counts made by hand from the
published sizes."""
import json
import os

import pytest

from bench import spec
from bench.families import wgkv_decoder as fam

# parameters per layer, by hand:
#   attention q, k, v, o: d*hq*hd + 2*d*hkv*hd + hq*hd*d
#   write gate (per KV head 2*hd -> 64 -> 1, biases): hkv*(2*hd*64+64+64+1)
#   norms: 2*d (+ 2*hd q/k norms for qwen3); MLP: 3*d*ff
HAND = {
    "qwen3-0.6b": dict(
        layer=2 * 1024 * 2048 + 2 * 1024 * 1024 + 8 * (256 * 64 + 129)
        + 2 * 1024 + 2 * 128 + 3 * 1024 * 3072,
        layers=28, d=1024, vocab=151936, hkv=8, grp=2),
    "phi4-mini-3.8b": dict(
        layer=2 * 3072 * 3072 + 2 * 3072 * 1024 + 8 * (256 * 64 + 129)
        + 2 * 3072 + 3 * 3072 * 8192,
        layers=32, d=3072, vocab=200064, hkv=8, grp=3),
}


@pytest.fixture(scope="module")
def bm():
    return spec.load_benchmark()


def config(bm, name):
    """A configuration by name; ``phi4-mini-3.8b`` (grouped queries 3:1,
    no q/k norms) is kept as test data for a second shape: no cell serves
    it until the program rotates part of each head as it does."""
    if name == "phi4-mini-3.8b":
        path = os.path.join(os.path.dirname(__file__), "data", f"{name}.json")
        with open(path) as f:
            return json.load(f)
    return spec.config(bm, name)


@pytest.mark.parametrize("name", sorted(HAND))
def test_param_counts(bm, name):
    h, c = HAND[name], config(bm, name)
    pc = fam.param_counts(c)
    assert pc["body"] == h["layers"] * h["layer"] + h["d"]
    assert pc["embed"] == h["vocab"] * h["d"]
    total = pc["body"] + pc["embed"]
    assert {"qwen3-0.6b": 0.6e9, "phi4-mini-3.8b": 3.84e9}[name] \
        == pytest.approx(total, rel=0.01)


@pytest.mark.parametrize("name", sorted(HAND))
def test_decode_step_work(bm, name):
    """Eight decode rows, each holding 10,000 KV head-tokens."""
    h, c = HAND[name], config(bm, name)
    body = h["layers"] * h["layer"] + h["d"]
    embed = h["vocab"] * h["d"]
    rows = [dict(tokens=1, kv_before=10_000, kv_after=10_008, logits=1)] * 8
    w = fam.step_work(c, rows)
    held = 10_008                  # the one position sees all held after
    attn = 8 * 4 * 128 * h["grp"] * held
    assert w["flops"] == pytest.approx(8 * 2 * body + attn + 8 * 2 * embed)
    kv_bytes = 8 * 10_000 * 2 * 128 * 2
    new_kv = 8 * h["layers"] * h["hkv"] * 2 * 128 * 2
    assert w["bytes"] == pytest.approx(
        2 * body + 2 * embed + 8 * h["d"] * 2 + kv_bytes + new_kv)


def test_prefill_chunk_without_logits(bm):
    """A mid-prompt chunk uses no logits: no LM head read or multiplied;
    attention grows evenly from the KV held before to after."""
    c = spec.config(bm, "qwen3-0.6b")
    h = HAND["qwen3-0.6b"]
    body = h["layers"] * h["layer"] + h["d"]
    rows = [dict(tokens=64, kv_before=0, kv_after=64 * 28 * 8, logits=0)]
    w = fam.step_work(c, rows)
    held = 64 * 0 + (64 * 28 * 8) * 65 / 2
    assert w["flops"] == pytest.approx(2 * body * 64
                                       + 4 * 128 * 2 * held)
    assert w["bytes"] == pytest.approx(
        2 * body + 64 * 1024 * 2 + 64 * 28 * 8 * 2 * 128 * 2)


def test_padding_rows_count_nothing(bm):
    c = spec.config(bm, "qwen3-0.6b")
    assert fam.step_work(c, []) == {
        "flops": 0.0, "bytes": fam.param_counts(c)["body"] * 2}


def test_global_budget_is_the_programs():
    """The reference's global cache size is the program's own
    ``WGKVConfig.global_budget`` of the engine capacity."""
    c = spec.config(spec.load_benchmark(), "qwen3-0.6b")
    pcfg = fam.program_config(c)
    assert fam.dims(c)["capacity"] == \
        pcfg.wgkv.global_budget(c["engine"]["capacity"]) == 512
