"""counts.py against FLOPs and bytes worked by hand at a small shape."""
import pytest

from rag_bench import counts

DENSE = counts.Model(layers=2, d=8, heads=2, kv_heads=1, head_dim=4, ff=16,
                     vocab=32)
MOE = counts.Model(layers=1, d=8, heads=2, kv_heads=1, head_dim=4, ff=4,
                   vocab=32, experts=4, top_k=2)


def test_dense_parts():
    # q, o: 8x8 each; k, v: 8x4 each -> 128 + 64
    assert DENSE.attn_params == 192
    assert DENSE.ffn_params_used() == 3 * 8 * 16
    assert DENSE.token_flops() == 2 * 2 * (192 + 384)
    assert DENSE.attn_flops(5) == 4 * 2 * 2 * 4 * 5
    assert DENSE.head_flops == 2 * 8 * 32
    assert DENSE.layer_weight_bytes(7) == (192 + 16 + 384) * 2
    # K and V, 2 layers, 1 head of 4, bf16
    assert DENSE.kv_bytes_per_position == 2 * 2 * 1 * 4 * 2


@pytest.mark.parametrize("offset, n, last, flops, byts", [
    # 3 tokens at 0..2 attend 1+2+3 positions; the last row reads the head
    (0, 3, True, 3 * 2304 + 64 * 6 + 512, 2 * 1184 + 3 * 32 + 512),
    # 2 tokens at 4..5 attend 5+6 positions; K/V of 6 positions
    (4, 2, False, 2 * 2304 + 64 * 11, 2 * 1184 + 6 * 32),
])
def test_prefill_chunk(offset, n, last, flops, byts):
    assert counts.prefill_chunk(DENSE, offset, n, last) == (flops, byts)


def test_decode_step_dense():
    # two rows at live lengths 5 and 3: each runs the layers and the head
    flops = 2 * (2304 + 512) + 64 * 8
    byts = 2 * 1184 + 512 + 8 * 32 + 2 * 32
    assert counts.decode_step(DENSE, [5, 3]) == (flops, byts)
    assert counts.decode_step(DENSE, []) == (0, 0)


def test_moe_counts_only_routed_experts():
    # router 8x4, then 2 of 4 experts of 3 * 8 * 4 each
    assert MOE.ffn_params_used() == 32 + 2 * 96
    assert MOE.distinct_experts(1) == pytest.approx(2.0)
    assert MOE.distinct_experts(2) == pytest.approx(3.0)
    assert MOE.layer_weight_bytes(1) == (192 + 16 + 32 + 2 * 96) * 2
    flops = 2 * (192 + 224) + 512 + 4 * 2 * 4 * 1
    kv = 1 * 2 * 1 * 4 * 2
    assert counts.decode_step(MOE, [1]) == (flops,
                                            (192 + 16 + 32 + 192) * 2 + 512
                                            + kv + kv)


def test_kernels():
    # 2 queries x 3 centroids, then 5 + 6 rows; 8 distinct rows read
    assert counts.route_and_scan(4, 3, 2, [5, 6], 8) == (2 * 4 * 17,
                                                         4 * 4 * 13)
    # 3 (query, doc) pairs of 3, 4, 5 windows; 9 distinct windows
    assert counts.scr_select(4, 2, [3, 4, 5], 9) == (2 * 4 * 12, 4 * 4 * 11)


def test_roofline_time_takes_the_larger_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.roofline_time(500, 20, peaks) == 5.0
    assert counts.roofline_time(100, 80, peaks) == 8.0


def test_model_from_config():
    conf = {"model": {"num_hidden_layers": 24, "hidden_size": 1024,
                      "num_attention_heads": 16, "num_key_value_heads": 8,
                      "head_dim": 64, "intermediate_size": 512,
                      "vocab_size": 49155, "num_local_experts": 32,
                      "num_experts_per_tok": 8}}
    m = counts.Model.from_config(conf)
    assert (m.experts, m.top_k, m.ff, m.qkv_bias) == (32, 8, 512, False)
