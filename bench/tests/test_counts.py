"""Bytes and operations from shapes, against hand numbers."""
import json
from pathlib import Path

import pytest

from bench import counts
from bench.weights import Shapes

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def shapes(name):
    return Shapes.of(json.loads((CONFIGS / f"{name}.json").read_text())
                     ["model"])


@pytest.mark.parametrize("name,words,kv,tables", [
    # 48 layers x 110,112,768 B (275.25M weights at 0.4 B, K=13824 padded
    # to 13830); 28 x 18,751,488 B (46.8M weights, K=1536 padded to 1540)
    ("qwen2.5-14b", 5_285_412_864, 196_608, 2 * 152064 * 5120),
    ("qwen2-1.5b", 525_041_664, 28_672, 151936 * 1536),
])
def test_hand_numbers(name, words, kv, tables):
    s = shapes(name)
    assert counts.qp_words_bytes(s) == words
    assert counts.kv_bytes_per_token(s) == kv
    assert counts.table_bytes(s) == tables


def test_matmul_weights_and_flops():
    s = shapes("qwen2.5-14b")
    per_layer = 2 * 5120 * 5120 + 2 * 5120 * 1024 + 3 * 5120 * 13824
    assert counts.matmul_weights(s) == 48 * per_layer + 152064 * 5120
    assert counts.decode_flops(s, 3, 100) == \
        3 * 2 * counts.matmul_weights(s) + 4 * 48 * 40 * 128 * 100


def test_qmatvec_call_and_roofline():
    ops, nbytes = counts.qmatvec_call(16, 1536, 8960)
    assert ops == 2 * 16 * 1536 * 8960
    assert nbytes == 154 * 8960 * 4 + (16 * 1536 + 16 * 8960) * 2
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    s = shapes("qwen2-1.5b")
    t = counts.qmatvec_roofline_s(s, 16, peaks)
    # at 16 rows every packed matmul is bound by its bytes
    assert t == pytest.approx(
        sum(counts.qmatvec_call(16, k, n)[1] for _, k, n, _ in s.matrices())
        / 819e9)
