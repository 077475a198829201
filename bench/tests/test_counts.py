from harness import spec

counts = spec.Bench().module("models/dense_decoder/counts.py")

M = {"L": 2, "d": 8, "H": 4, "KV": 2, "hd": 2, "ff": 16, "V": 32}


def test_decode_flops_by_hand():
    # per layer: q 8x8 + k 8x4 + v 8x4 + o 8x8 + gate/up/down 3 x 8x16
    # = 64 + 32 + 32 + 64 + 384 = 576; per token: 2 layers x 576 + head
    # 32x8 = 1408 weights -> 2816 FLOPs
    # attention: 4 * L * H * hd * ctx = 4*2*4*2*ctx = 64 * ctx
    assert counts.layer_matmul_params(8, 4, 2, 2, 16) == 576
    assert counts.decode_flops(M, [10, 3]) == 2 * 2816 + 64 * 13


def test_unit_flops_by_hand():
    # B=1, S=4, r=1, targets q and down: lora params (8+8) + (16+8) = 40
    t = ["q", "down"]
    assert counts.lora_params(8, 4, 2, 2, 16, 1, t) == 40
    attn = 2 * 1 * 4 * 2 * 16               # causal half of 4 S^2 H hd
    assert counts.unit_flops(M, "fwd", 1, 4, 1, t) == 2 * 4 * (576 + 40) + attn
    assert counts.unit_flops(M, "head", 1, 4, 1, t) == 4 * 3 * 32 * 8
    assert counts.unit_flops(M, "bwd", 1, 4, 1, t) == \
        2 * 4 * 576 + 4 * 4 * 40 + 2 * attn
    for k in ("embed", "embed_bwd", "opt"):
        assert counts.unit_flops(M, k, 1, 4, 1, t) == 0


def test_unit_kinds_follow_the_iteration():
    kinds = [counts.unit_kind(i, 2, 2) for i in range(2 * 7 + 1)]
    mb = ["embed", "fwd", "fwd", "head", "bwd", "bwd", "embed_bwd"]
    assert kinds == mb + mb + ["opt"]


def test_decode_attention_bytes_by_hand():
    # contexts 65 and 1 on 64-token pages: 2 + 1 pages of 64 tokens,
    # K and V, KV=2, hd=2, 2 bytes -> 3*64*2*2*2*2; q and out for 4 slots
    assert counts.decode_attention_bytes(M, [65, 1], 4, 64) == \
        3 * 64 * 2 * 2 * 2 * 2 + 2 * 4 * 4 * 2 * 2
    assert counts.decode_attention_flops(M, [65, 1]) == 4 * 4 * 2 * 66
