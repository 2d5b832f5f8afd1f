"""The needed-work counts against hand counts at two shapes."""
import counts

TINY = {"d_model": 4, "n_heads": 2, "n_kv_heads": 1, "head_dim": 2,
        "d_ff": 8, "vocab_size": 10, "n_layers": 3, "activation": "relu2"}
GATED = dict(TINY, activation="swiglu", n_layers=1)


def test_layer_params():
    # q 4*2*2 + k,v 2*4*1*2 + o 2*2*4 + mlp 2*4*8
    assert counts.layer_matmul_params(TINY) == 16 + 16 + 16 + 64
    # the gated MLP has three matrices
    assert counts.layer_matmul_params(GATED) == 16 + 16 + 16 + 96


def test_prefill_and_decode_flops():
    # prompt of 3: 3 layers x (2 x 3 tokens x 112 + 6 pairs x 4 x 2 x 2)
    # plus one row of logits 2 x 4 x 10
    assert counts.prefill_flops(TINY, 3) == 3 * (672 + 96) + 80
    # decode at position 5 attends to 6 positions
    assert counts.decode_flops(TINY, 5) == 3 * (224 + 6 * 16) + 80
    # gated, one layer: prompt of 1
    assert counts.prefill_flops(GATED, 1) == (2 * 144 + 1 * 16) + 80


def test_request_is_prefill_plus_decode_steps():
    for m in (TINY, GATED):
        for plen, n in ((3, 1), (3, 4), (7, 2)):
            want = counts.prefill_flops(m, plen) + sum(
                counts.decode_flops(m, plen + j) for j in range(n - 1))
            assert counts.request_flops(m, plen, n) == want


def test_topk_bytes():
    # read 8 x 256000 f32, write 8 x 50 (f32 value + i32 index)
    assert counts.topk_bytes(8, 256000, 50) == 8192000 + 3200
    assert counts.topk_bytes(1, 32256, 64) == 129024 + 512
