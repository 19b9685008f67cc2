"""The FLOP and byte functions against values worked by hand."""

from benchmark import flops

GPTJ = {"d_model": 4096, "n_head": 16, "n_layer": 8, "d_ff": 0, "vocab_size": 50400}
NEO = {"d_model": 2048, "n_head": 16, "n_layer": 24, "d_ff": 0, "vocab_size": 50257,
       "attention_layers": ["global", "local"] * 12, "window_size": 256}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_one_gptj_layer_by_hand():
    # q, k, v, out: 4 * 4096^2 = 67,108,864; MLP: 2 * 4096 * 16384 = 134,217,728
    assert flops.layer_matmul_params(GPTJ) == 201_326_592
    # batch 8 x 1024 tokens: 2 * 8192 * 201,326,592
    dense = 3_298_534_883_328
    # causal pairs 1024 * 1025 / 2 = 524,800; QK^T and PV: 2 * 2 * 8 * 16 * 256 * 524,800
    assert flops.kept_pairs(1024) == 524_800
    attn = 68_786_585_600
    assert flops.attention_flops(8, 1024, 16, 256) == attn
    one = dict(GPTJ, n_layer=1)
    # trainable: forward (dense + attn) + activation grads (dense + 2 attn) + weight grads (dense)
    assert flops.trunk_train_flops(one, 8, 1024, unfrozen=1) == 3 * dense + 3 * attn == 10_101_964_406_784
    # frozen: no weight gradients
    two = dict(GPTJ, n_layer=2)
    assert flops.trunk_train_flops(two, 8, 1024, unfrozen=1) == (2 * dense + 3 * attn) + (3 * dense + 3 * attn)


def test_windowed_layers_count_their_window():
    # a query sees itself and the 255 keys before it: 256*257/2 + (512-256)*256
    assert flops.kept_pairs(512, 256) == 32_896 + 65_536
    assert flops.kept_pairs(256, 256) == flops.kept_pairs(256)
    assert flops.layer_windows(NEO)[:4] == [0, 256, 0, 256]


def test_one_ilql_q_head_by_hand():
    # MLPHead 2048 -> 4096 -> 50257 on 8 x 255 action positions, forward:
    # 2 * 2040 * (2048*4096 + 4096*50257) = 4080 * 214,241,280
    fwd = 874_104_422_400
    assert flops.mlp_head_flops(8 * 255, 2048, 50257) == fwd
    total = flops.ilql_train_step_flops(NEO, 8, 256, unfrozen=8, two_qs=True)
    trunk = flops.trunk_train_flops(NEO, 8, 256, 8)
    lm_head = 3 * 2 * 2040 * 2048 * 50257
    target = 2 * 2 * 2040 * 2048 * 4096
    value = 3 * flops.mlp_head_flops(8 * 256, 2048, 1)
    assert total == trunk + lm_head + 2 * 3 * fwd + target + value
    # the four vocab-wide heads (two trained Q heads and the LM head) are a large share of the step
    assert 0.25 < (2 * 3 * fwd + lm_head) / total < 0.6


def test_ppo_step_counts_the_head_on_response_positions_only():
    a = flops.ppo_train_step_flops(GPTJ, 8, 768, 256, 2)
    b = flops.ppo_train_step_flops(GPTJ, 8, 128, 896, 2)
    head = 3 * 2 * 8 * 4096 * 50400
    value = 3 * 2 * 8 * (4096 * 8192 + 8192)
    assert b - a == (896 - 256) * (head + value)


def test_kernel_calls_and_roofline_floor():
    ops, moved = flops.flash_call("fwd", 8, 1024, 16, 256)
    assert ops == 68_786_585_600 and moved == 4 * 8 * 1024 * 16 * 256 * 2
    floor, bound = flops.least_seconds(ops, moved, PEAKS)
    assert bound == "compute" and abs(floor - ops / 197e12) < 1e-12
    ops, moved = flops.logprob_head_call("fwd", 2048, 4096, 50400)
    assert ops == 2 * 2048 * 4096 * 50400
    assert moved == (2048 * 4096 + 4096 * 50400) * 2 + 3 * 2048 * 4
    assert flops.least_seconds(ops, moved, PEAKS)[1] == "compute"
    # a one-row head call streams the whole weight for almost no arithmetic
    assert flops.least_seconds(*flops.logprob_head_call("fwd", 1, 4096, 50400), PEAKS)[1] == "memory"
