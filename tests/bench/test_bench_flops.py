"""Required operations, counted by hand for both configurations."""
import _bench_path  # noqa: F401
from bench import common, flops


def test_starcoder2_stage_by_hand():
    c = common.config("starcoder2-15b-s10")
    D, F, L, V = 6144, 24576, 10, 49152
    per_tok = 2 * D * (2 * 48 * 128 + 2 * 4 * 128) + 2 * 2 * D * F
    assert flops.token_matmul_flops(c) == per_tok
    # a 3-token prompt attends over 1 + 2 + 3 keys
    assert flops.prefill_flops(c, 3) == \
        L * (3 * per_tok + 4 * 48 * 128 * 6) + 2 * D * V
    assert flops.decode_flops(c, 100) == \
        L * (per_tok + 4 * 48 * 128 * 100) + 2 * D * V
    # about 8.3 GFLOP a token before attention
    assert 8.2e9 < L * per_tok + 2 * D * V < 8.4e9


def test_deepseek_moe_stage_counts_routed_experts_only():
    c = common.config("deepseek-moe-16b-s7")
    D, F, L, V = 2048, 1408, 7, 102400
    attn = 2 * D * (2 * 16 * 128 + 2 * 16 * 128)
    experts = (6 + 2) * 3 * 2 * D * F          # top-6 routed + 2 shared
    router = 2 * D * 64
    assert flops.token_matmul_flops(c) == attn + experts + router
    assert flops.decode_flops(c, 1) == \
        L * (attn + experts + router + 4 * 16 * 128) + 2 * D * V
    # all 64 experts would be 66 / 8 times the expert work counted
    assert experts * 66 // 8 == 66 * 3 * 2 * D * F
