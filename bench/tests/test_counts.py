"""The Llama family's required FLOP and byte counts against hand counts at
one shape."""
import cells

CONF = dict(hidden_size=8, intermediate_size=16, num_attention_heads=4,
            num_key_value_heads=2, head_dim=2, num_hidden_layers=3,
            vocab_size=10)
counts = cells.family(CONF)


def test_layer_params():
    # q 8x8, k 8x4, v 8x4, o 8x8, gate/up 8x16, down 16x8
    assert counts.layer_matmul_params(CONF) == 64 + 32 + 32 + 64 + 3 * 128


def test_attention_and_head():
    assert counts.attn_flops(CONF, 5) == 4 * 4 * 2 * 5
    assert counts.head_flops(CONF) == 2 * 8 * 10


def test_sender_prefill():
    # 3 tokens: causal keys 1 + 2 + 3 = 6 per layer
    per_layer = 2 * 576 * 3 + 32 * 6
    assert counts.sender_prefill_flops(CONF, 3) == 3 * per_layer


def test_receiver_prefill():
    # 2 query tokens, 5 prefix keys at 1 selected layer (1), one head row
    want = 3 * (2 * 576 * 2 + 32 * 3) + 1 * 32 * (2 * 5) + 160
    assert counts.receiver_prefill_flops(CONF, 2, 5, (1,)) == want


def test_decode_row():
    want = 3 * (2 * 576 + 32 * 4) + 2 * 32 * 7 + 160
    assert counts.decode_row_flops(CONF, 4, 7, (0, 2)) == want


def test_decode_attention_call():
    flops, nbytes = counts.decode_attn_call(CONF, 1, [3, 5])
    assert flops == 4 * 4 * 2 * 8
    # keys and values: 2 x 2 heads x 2 dims x 8 keys; q and out: 2 x 4 x 2
    # per row, 2 rows; bf16
    assert nbytes == 2 * (2 * 2 * 2 * 8 + 2 * 4 * 2 * 2)
