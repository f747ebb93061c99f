"""bench/flops.py against counts made by hand at one small shape."""
import pytest

from bench_tiny import ROOT  # noqa: F401  (puts the repo on sys.path)

from bench import flops

CLAQ = {"target_bits": 2.5, "p_lo": 2, "p_hi": 4, "or_extra_bits": 0.1,
        "or_o1": 0.28, "or_o2": 0.72, "or_top_frac": 0.1}
CONF = {"num_hidden_layers": 2, "hidden_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
        "intermediate_size": 256, "vocab_size": 512, "claq": CLAQ}


def test_kernel_calls_by_hand():
    calls = flops.kernel_calls(CONF, m=8)
    # 7 matrices x 2 bit-width groups x 2 layers
    assert len(calls) == 28
    # attn.q: rows 128, cols 128; 32 columns at 4 bits, 96 at 2 bits;
    # OR: 0.1*128*128/32 = 51.2 outliers, 13 top columns keep 1 each
    # (round(0.28*51.2/13)), 115 others keep round(0.72*51.2/115) = 0
    lo, hi = calls[0], calls[1]
    assert lo[0] == 2 * 8 * 128 * 96 and hi[0] == 2 * 8 * 128 * 32
    outl = 13 * 1 + 115 * 0
    assert lo[1] == pytest.approx(128 * 96 * 2 / 8 + 4 * 96 * 4
                                  + outl * 96 / 128 * 8 + 8 * 96 * 2
                                  + 8 * 128 * 4)
    assert hi[1] == pytest.approx(128 * 32 * 4 / 8 + 16 * 32 * 4
                                  + outl * 32 / 128 * 8 + 8 * 32 * 2
                                  + 8 * 128 * 4)


def test_least_time_takes_the_larger_bound_per_call():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    t, t_ops, t_bytes = flops.least_time_s([(1000.0, 1.0), (1.0, 50.0)],
                                           peaks)
    assert (t_ops, t_bytes) == (10.0, 5.0) and t == 15.0


def test_model_ops_by_hand():
    d, f, V, L, H, hd = 128, 256, 512, 2, 4, 32
    per_layer = 128 * d + 2 * 64 * d + d * 128 + 3 * d * f
    assert flops.matmul_params(CONF) == L * per_layer + d * V
    assert flops.decode_token_ops(CONF, 10) == (
        2 * (L * per_layer + d * V) + 4 * L * H * hd * 10)
