"""bench/counts.py against counts made by hand for one small layer."""

import pytest

from bench import counts

# one layer: d 8, 2 q heads and 1 kv head of size 4, d_ff 16, vocab 32
ARCH = dict(num_layers=1, d_model=8, num_heads=2, num_kv_heads=1, head_dim=4,
            d_ff=16, vocab_size=32)
# per token: wq 8x8 + wk 8x4 + wv 8x4 + wo 8x8 + 3 x 8x16 = 576 weights
LAYER = 64 + 32 + 32 + 64 + 384


def test_layer_weights():
    assert counts.layer_matmul_params(ARCH) == LAYER


def test_prefill_flops_by_hand():
    # 3 valid tokens: 2*576*3 matmul, causal scores+values 4*2*4*(1+2+3),
    # one output-head row 2*8*32
    assert counts.prefill_flops(ARCH, [3]) == 3456 + 192 + 512
    # padding never counts: two requests are the sum of each alone
    assert counts.prefill_flops(ARCH, [3, 5]) == (
        counts.prefill_flops(ARCH, [3]) + counts.prefill_flops(ARCH, [5]))


def test_decode_flops_by_hand():
    # one token through the layer and the head, attending over 5 positions
    assert counts.decode_flops(ARCH, [5]) == 2 * 576 + 512 + 4 * 2 * 4 * 5


def test_decode_bytes_by_hand():
    # bf16: layer weights + 2 norms, final norm, head (not the embedding)
    weights = 2 * (576 + 16 + 8 + 8 * 32)
    assert counts.weight_bytes(ARCH) == weights
    # 5 K/V rows of 1 head x 4 (K and V, bf16) and one embedding row
    assert counts.decode_bytes(ARCH, [5]) == weights + 5 * 16 + 16
    assert counts.decode_bytes(ARCH, []) == weights


@pytest.mark.parametrize("flops,nbytes,bound", [(1e12, 1e6, "compute"),
                                                (1e6, 1e12, "memory")])
def test_least_seconds_takes_the_larger_bound(flops, nbytes, bound):
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    t, which = counts.least_seconds(flops, nbytes, 2, peak)
    assert which == bound
    assert t == max(flops / 2e12, nbytes / 2e9)
