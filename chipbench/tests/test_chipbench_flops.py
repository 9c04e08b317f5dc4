"""The FLOP and byte counts against hand counts, and against the counts the
benchmark has reported for its configurations so far."""
import pytest

from chipbench import flops, harness
from chipbench.reference.qwen3 import Shape

# 1 layer, d 8, 2 heads x 4, 1 KV head, d_ff 16, vocab 10
S = Shape(layers=1, d=8, heads=2, kv_heads=1, head_dim=4, d_ff=16, vocab=10,
          eps=1e-6, theta=1e6)


def test_dense_per_token():
    # q 8x8, k 8x4, v 8x4, o 8x8, three 8x16 MLP matrices; 2 FLOPs per MAC
    macs = 64 + 32 + 32 + 64 + 3 * 128
    assert S.dense_per_token() == 2 * macs


def test_attention_is_causal():
    # 3 tokens from position 0 see 1, 2, 3 keys; 2 heads x 4 dims, q.k and
    # p.v at 2 FLOPs a MAC each
    assert S.attention(0, 3) == (1 + 2 + 3) * 2 * 4 * 4
    # 2 tokens after 5 cached: 6 and 7 keys
    assert S.attention(5, 2) == (6 + 7) * 2 * 4 * 4


def test_requests():
    per = S.dense_per_token()
    assert flops.score(S, 3) == 3 * per + S.attention(0, 3) + 2 * 8 * 2
    # 4 prompt tokens, 3 generated: 6 tokens fed, 3 full-vocabulary rows
    assert flops.complete(S, 4, 3) == (6 * per + S.attention(0, 6)
                                       + 3 * 2 * 8 * 10)
    # prompt of 4 once, labels of 2 and 1 tokens after it
    assert flops.classify(S, 4, [2, 1]) == (
        4 * per + S.attention(0, 4) + 2 * per
        + S.attention(4, 2) + per + S.attention(4, 1)
        + 3 * 2 * 8 * 10)


def test_decode_attention():
    f, b = flops.decode_attention(S, 100)
    assert f == 4 * 2 * 4 * 100
    # K and V: 100 positions x 1 KV head x 4 dims x 2 bytes each
    assert b == 2 * 100 * 4 * 2


# Each configuration's counts as chipbench/flops.py gave them at commit
# dd7eba8, when the counts of a dense layer lived there: they feed mfu,
# mfu.dashboard and flash_decode_roofline, and moving the counts into the
# architecture module moves none of them by a bit.
PARENT = {
    "qwen3-8b.filter": {
        "score": {1: 6946078720.0, 200: 1395081232384.0,
                  1600: 11490951184384.0, 2047: 14836159234048.0},
        "complete": {(200, 32): 1652203847680.0, (800, 1): 5652348403712.0,
                     (50, 0): 347664384000.0},
        "classify": {(48, (1, 2, 3)): 382974099456.0,
                     (160, (2, 2, 1, 3)): 1181025992704.0},
        "decode_attention": {1: (294912.0, 73728.0),
                             300: (88473600.0, 22118400.0),
                             2048: (603979776.0, 150994944.0)}},
    "qwen3-32b.dashboard": {
        "score": {1: 7801688064.0, 200: 1565550202880.0,
                  1600: 12818002759680.0, 2047: 16518964334592.0},
        "complete": {(200, 32): 1858935455744.0, (800, 1): 6326671114240.0,
                     (50, 0): 390404505600.0},
        "classify": {(48, (1, 2, 3)): 430997241856.0,
                     (160, (2, 2, 1, 3)): 1326798077952.0},
        "decode_attention": {1: (262144.0, 32768.0),
                             300: (78643200.0, 9830400.0),
                             2048: (536870912.0, 67108864.0)}},
}


@pytest.mark.parametrize("workload", sorted(PARENT))
def test_counts_unchanged(workload):
    cell = harness.load_cell(workload)
    s = cell.arch.Shape.of(cell.conf)
    want = PARENT[workload]
    for n, v in want["score"].items():
        assert flops.score(s, n) == v
    for (p, g), v in want["complete"].items():
        assert flops.complete(s, p, g) == v
    for (p, labels), v in want["classify"].items():
        assert flops.classify(s, p, list(labels)) == v
    for n, v in want["decode_attention"].items():
        assert flops.decode_attention(s, n) == v
