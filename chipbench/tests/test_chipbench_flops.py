"""The FLOP and byte counts against hand counts."""
from chipbench import flops
from chipbench.reference.qwen3 import Shape

# 1 layer, d 8, 2 heads x 4, 1 KV head, d_ff 16, vocab 10
S = Shape(layers=1, d=8, heads=2, kv_heads=1, head_dim=4, d_ff=16, vocab=10,
          eps=1e-6, theta=1e6)


def test_dense_per_token():
    # q 8x8, k 8x4, v 8x4, o 8x8, three 8x16 MLP matrices; 2 FLOPs per MAC
    macs = 64 + 32 + 32 + 64 + 3 * 128
    assert flops.dense_per_token(S) == 2 * macs


def test_attention_is_causal():
    # 3 tokens from position 0 see 1, 2, 3 keys; 2 heads x 4 dims, q.k and
    # p.v at 2 FLOPs a MAC each
    assert flops.attention(S, 0, 3) == (1 + 2 + 3) * 2 * 4 * 4
    # 2 tokens after 5 cached: 6 and 7 keys
    assert flops.attention(S, 5, 2) == (6 + 7) * 2 * 4 * 4


def test_requests():
    per = flops.dense_per_token(S)
    assert flops.score(S, 3) == 3 * per + flops.attention(S, 0, 3) + 2 * 8 * 2
    # 4 prompt tokens, 3 generated: 6 tokens fed, 3 full-vocabulary rows
    assert flops.complete(S, 4, 3) == (6 * per + flops.attention(S, 0, 6)
                                       + 3 * 2 * 8 * 10)
    # prompt of 4 once, labels of 2 and 1 tokens after it
    assert flops.classify(S, 4, [2, 1]) == (
        4 * per + flops.attention(S, 0, 4) + 2 * per
        + flops.attention(S, 4, 2) + per + flops.attention(S, 4, 1)
        + 3 * 2 * 8 * 10)


def test_decode_attention():
    f, b = flops.decode_attention(S, 100)
    assert f == 4 * 2 * 4 * 100
    # K and V: 100 positions x 1 KV head x 4 dims x 2 bytes each
    assert b == 2 * 100 * 4 * 2
