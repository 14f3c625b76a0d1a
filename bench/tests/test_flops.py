"""The FLOP count against a hand count of both configurations."""
import harness
import flops


def _conf(name):
    b = harness.load_benchmark()
    entry = {c["name"]: c for c in b["configs"]}[name]
    return harness.load_config(entry)


def test_qwen3_by_hand():
    conf = _conf("qwen3-0.6b")
    # per layer: q 1024x16x128, k and v 1024x8x128, o 16x128x1024,
    # mlp 3 x 1024x3072; head 1024 x 151936 (the embedding gather and
    # the 128 padded vocabulary columns do not count)
    layer = 1024 * 2048 + 2 * 1024 * 1024 + 2048 * 1024 + 3 * 1024 * 3072
    n = 28 * layer + 1024 * 151936
    assert flops.matmul_params(conf) == n == 595_984_384
    # 2 sequences of 2048: 6 N T plus causal attention, 3 x 4 H hd pairs
    attn = 3 * 28 * 4 * 16 * 128 * (2048 * 2049 // 2) * 2
    assert flops.train_step_flops(conf, 1, 2, 2048) == \
        6.0 * n * 4096 + attn
    assert abs(flops.train_step_flops(conf, 1, 2, 2048) / 1e12
               - 17.54) < 0.01


def test_stablelm_by_hand():
    conf = _conf("stablelm-3b-6l")
    layer = 4 * 2560 * 2560 + 3 * 2560 * 6912
    n = 6 * layer + 2560 * 50304
    assert flops.matmul_params(conf) == n == 604_569_600
    attn = 3 * 6 * 4 * 32 * 80 * (2048 * 2049 // 2)
    assert flops.train_step_flops(conf, 4, 1, 2048) == \
        4 * (6.0 * n * 2048 + attn)


def test_decode_and_prefill_agree():
    conf = _conf("qwen3-0.6b")
    # a prompt's prefill is the sum of its tokens' one-at-a-time costs
    p = 37
    assert abs(flops.prefill_flops(conf, p)
               - sum(flops.decode_flops(conf, c) for c in range(1, p + 1))
               ) < 1e-6 * flops.prefill_flops(conf, p)
