"""The benchmark's FLOP count against a hand count, and the peaks."""
import pytest

from bench import common as C
from bench import flops


def _cfg(name):
    return C.load_json(C.BENCH / "configs" / f"{name}.json")


def test_stablelm_6_layers_hand_count():
    c = _cfg("stablelm-1.6b-6l")
    attn = 4 * 2048 * 2048            # q, k, v, o (MHA, 32 x 64)
    mlp = 3 * 2048 * 5632             # gate, up, down
    head = 2048 * 100352
    assert flops.matmul_weights(c) == 6 * (attn + mlp) + head
    attention = 6 * 2 * 32 * 64 * (2048 + 1)
    assert flops.train_flops_per_token(c, 2048) == \
        3 * (2 * (6 * (attn + mlp) + head) + attention) == 3_233_882_112


def test_gpt_paper_20b_2_layers_hand_count():
    c = _cfg("gpt-paper-20b-2l")
    attn = 4 * 8192 * 8192
    mlp = 2 * 8192 * 32768            # plain GELU MLP
    head = 8192 * 51200
    attention = 2 * 2 * 64 * 128 * (2048 + 1)
    assert flops.train_flops_per_token(c, 2048) == \
        3 * (2 * (2 * (attn + mlp) + head) + attention) == 12_381_683_712


def test_the_embedding_lookup_is_not_counted():
    c = _cfg("stablelm-1.6b-6l")
    tied = dict(c, tie_word_embeddings=True)
    # a tied head is the embedding table, counted once, as the head
    assert flops.matmul_weights(tied) == flops.matmul_weights(c)


def test_peaks():
    assert flops.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peak("TPU v9 imaginary")
