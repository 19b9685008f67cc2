"""The grouped-key, windowed, sparse-expert block family (models/lm.py
`Attention` with `n_kv_head`, `qk_norm`, `rotary_layers`, `window_cache`;
ops/kv_read.py; ops/flash_attention.py; models/moe.py) against the plain
reference `benchmark/references/gqa_window_moe_decoder.py`: seeded random
weights, tiny sizes, float32, CPU. The kinds are the K-EXAONE
configuration's rehearsal kinds (ISSUE 30).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import gqa_window_moe_decoder as reference
from trlx_tpu.models import moe
from trlx_tpu.models.lm import (Attention, LMConfig, TransformerLM, cache_bytes, cache_bytes_per_token, init_cache,
                                init_paged_cache, make_attn_bias, ring_bias, rope_tables, write_ring)
from trlx_tpu.ops.flash_attention import FlashBlocks, flash_attention
from trlx_tpu.ops.kv_read import attend, kv_keys_read

# Dense + window, window, window, full span, window; 8 query heads over 2 K/V
# heads of 16 (so n_head * head_width = 128 is not d_model = 64); a window of
# 8; 16 experts of which [4, 8) are held, 2 a token.
ARCH = dict(
    vocab_size=96, n_layer=5, n_head=8, n_kv_head=2, head_width=16, d_model=64, d_ff=128, max_position=256,
    eos_token_id=0, pos_type="rotary", rotary_layers="local", rope_theta=1000000, extra={"neox_rotary": True},
    norm="rmsnorm", mlp="gated", attention="mha", activation="silu", ln_eps=1e-5, parallel_residual=False,
    tie_word_embeddings=False, fused_qkv=False, qkv_bias=False, out_bias=False, qk_norm=True,
    attention_layers=["local", "local", "local", "global", "local"], window_size=8, window_cache="ring",
    ffn_layers=["dense", "experts", "experts", "experts", "experts"], n_experts=16, experts_per_token=2,
    expert_d_ff=32, n_shared_experts=1, routed_scaling_factor=2.5, experts_held=[4, 4], embed_init_std=1.0,
)
F32 = dict(dtype="float32", param_dtype="float32", attn_impl="xla")
B, T = 2, 24


def _model(arch=ARCH, seed=0, **over):
    cfg = LMConfig.from_dict({**arch, **F32, **over})
    model = TransformerLM(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, T), 2, cfg.vocab_size)
    mask = jnp.ones((B, T), jnp.int32).at[1, :5].set(0)  # row 1 is left-padded
    params = model.init(jax.random.PRNGKey(seed), ids, mask)["params"]
    return cfg, model, params, ids * mask, mask


@pytest.mark.parametrize("attn_impl", ["xla", "flash"], ids=["einsum", "flash kernels, interpreted"])
def test_logits_match_the_reference_padded_and_unpadded_rows(attn_impl):
    cfg, model, params, ids, mask = _model(attn_impl=attn_impl)
    got = model.apply({"params": params}, ids, mask)["logits"]
    want = reference.forward(params, ARCH, ids, mask, T)
    assert float(jnp.abs(want).max()) > 0.5
    np.testing.assert_allclose(got[0], want[0], atol=3e-5, rtol=1e-4)  # no padding
    np.testing.assert_allclose(got[1, 5:], want[1, 5:], atol=3e-5, rtol=1e-4)  # its real positions


@pytest.mark.parametrize("attn_impl", ["xla", "flash"], ids=["einsum", "flash kernels, interpreted"])
def test_gradients_of_a_scalar_loss_match_the_reference(attn_impl):
    """Every parameter's gradient, the grouped K and V projections' (summed
    over a group's query heads) and the two head norms' among them."""
    cfg, model, params, ids, mask = _model(attn_impl=attn_impl)
    weight = jax.random.normal(jax.random.PRNGKey(7), (B, T, cfg.vocab_size)) * mask[:, :, None]
    got = jax.grad(lambda p: jnp.sum(model.apply({"params": p}, ids, mask)["logits"] * weight))(params)
    want = jax.grad(lambda p: jnp.sum(reference.forward(p, ARCH, ids, mask, T) * weight))(params)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        if "e_score_correction_bias" in name:
            continue  # a buffer: the program stops its gradient
        scale = float(jnp.abs(w).max())
        assert scale > 0, name
        np.testing.assert_allclose(g, w, atol=2e-4 * scale + 1e-6, rtol=2e-3, err_msg=name)


def _decode(cfg, model, params, ids, mask, prompt):
    """Prefill `prompt` tokens, then teacher-forced decode of the rest
    through the cache, one scalar traced write offset a step: [B, T - prompt + 1, V]."""
    total = ids.shape[1]
    cache_mask = jnp.concatenate([mask[:, :prompt], jnp.zeros((B, total - prompt), jnp.int32)], axis=1)
    out = model.apply({"params": params}, ids[:, :prompt], mask[:, :prompt], cache=init_cache(cfg, B, total),
                      cache_index=0, cache_mask=cache_mask)
    step = jax.jit(lambda cache, index, cache_mask, token: model.apply(
        {"params": params}, token, jnp.ones((B, 1), jnp.int32), cache=cache, cache_index=index, cache_mask=cache_mask))
    cache, rows = out["cache"], [out["logits"][:, -1]]
    for i in range(prompt, total):
        cache_mask = cache_mask.at[:, i].set(1)
        out = step(cache, jnp.int32(i), cache_mask, ids[:, i:i + 1])
        cache = out["cache"]
        rows.append(out["logits"][:, 0])
    return jnp.stack(rows, axis=1), cache


@pytest.mark.parametrize("prompt", [4, 12], ids=["prompt inside the window", "prompt longer than the window"])
@pytest.mark.parametrize("window_cache, remat", [("ring", False), ("ring", True), ("span", False)],
                         ids=["ring", "ring under remat", "span"])
def test_prefill_then_decode_past_the_window_matches_the_full_forward(window_cache, remat, prompt):
    """16 or 24 decode steps on a window of 8: the ring wraps and every slot is
    overwritten; a prompt of 12 leaves the prefill's last 8 positions, rolled
    to their slots. The full-span cache with the window in the bias gives the
    same logits as the ring, and both the reference's full forward."""
    total = 28
    cfg, model, params, _, _ = _model(window_cache=window_cache, remat=remat)  # remat: a block sees its offset traced
    ids = jax.random.randint(jax.random.PRNGKey(3), (B, total), 2, cfg.vocab_size)
    mask = jnp.ones((B, total), jnp.int32).at[1, :3].set(0)
    decoded, cache = _decode(cfg, model, params, ids * mask, mask, prompt)
    want = reference.forward(params, ARCH, ids * mask, mask, total - prompt + 1)
    np.testing.assert_allclose(decoded, want, atol=3e-5, rtol=1e-4)
    lengths = [int(layer[0].shape[1]) for layer in cache]
    assert lengths == ([8, 8, 8, total, 8] if window_cache == "ring" else [total] * 5)
    assert all(layer[0].shape[2:] == (2, 16) for layer in cache)  # K and V at the 2 K/V heads


def test_int8_cache_composes_with_grouped_keys_and_the_ring():
    """kv_cache_quant: int8 K and V at the K/V heads, scales [b, slots, kv
    heads], ring layers too; the decode logits stay near the reference."""
    total = 28
    cfg, model, params, _, _ = _model(kv_cache_quant=True)
    ids = jax.random.randint(jax.random.PRNGKey(3), (B, total), 2, cfg.vocab_size)
    mask = jnp.ones((B, total), jnp.int32)
    decoded, cache = _decode(cfg, model, params, ids, mask, 12)
    want = reference.forward(params, ARCH, ids, mask, total - 12 + 1)
    assert [tuple(leaf.shape) for leaf in cache[0]] == [(B, 8, 2, 16)] * 2 + [(B, 8, 2)] * 2
    assert cache[3][0].shape == (B, total, 2, 16) and cache[3][0].dtype == jnp.int8
    rel = float(jnp.sqrt(jnp.mean((decoded - want) ** 2) / jnp.mean(want**2)))
    assert 0 < rel < 0.05, rel


def test_a_decode_step_holds_no_key_or_value_at_the_query_heads():
    """No `jnp.repeat` of K or V: in a decode step's jaxpr the only arrays
    with all 8 query heads are one token long (q, the output); nothing of the
    cache's length carries 8 heads or 2 x 4 grouped heads of 16."""
    cfg, model, params, ids, mask = _model()
    cache = init_cache(cfg, B, T)
    jaxpr = jax.make_jaxpr(lambda cache, index, token: model.apply(
        {"params": params}, token, jnp.ones((B, 1), jnp.int32), cache=cache, cache_index=index, cache_mask=mask))(
            cache, jnp.int32(9), ids[:, :1])

    def shapes(j):
        for eqn in j.eqns:
            for v in eqn.outvars:
                yield tuple(getattr(v.aval, "shape", ()))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from shapes(sub)

    long = [s for s in shapes(jaxpr.jaxpr) if len(s) >= 4 and s[-1] == 16 and any(n in (8, T) for n in s[1:2])]
    assert long and all(s[2:] == (2, 16) for s in long), sorted(set(long))


@pytest.mark.parametrize("window", [0, 16])
@pytest.mark.parametrize("blocks", [(128, 256, 128), (64, 128, 64)], ids=["resident", "major pieces"])
def test_flash_with_a_group_of_8_matches_the_einsum(window, blocks):
    """Forward, dq and the grouped dk/dv (summed over the 8 query heads of a
    group inside the kernel), interpreted, against `attend` over the same
    grouped operands; window on and off; the sequence resident and in pieces."""
    b, t, h, h_kv, d = 2, 256, 16, 2, 32
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (b, t, h, d))
    k, v = (jax.random.normal(key, (b, t, h_kv, d)) for key in keys[1:3])
    mask = jnp.ones((b, t)).at[1, :40].set(0)
    weight = jax.random.normal(keys[3], (b, t, h, d)) * mask[:, :, None, None]
    flash = lambda q, k, v: flash_attention(q, k, v, mask, scale=0.2, causal=True, window=window,
                                            blocks=FlashBlocks(*blocks), interpret=True)
    plain = lambda q, k, v: attend(q, k, v, make_attn_bias(mask, t, 0, window=window), 0.2, jnp.float32)
    np.testing.assert_allclose(flash(q, k, v) * mask[:, :, None, None], plain(q, k, v) * mask[:, :, None, None], atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * weight), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * weight), argnums=(0, 1, 2))(q, k, v)
    assert got[1].shape == got[2].shape == (b, t, h_kv, d)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=5e-5)


LARGE_CALL_PATHS = {  # 48 tokens, 2 a token, a share of 4 of 16 experts: some 24 held slots of 96
    "small call": None,
    "large call, the buffer from the shapes": "shapes",
    "large call, a buffer that holds the slots": 64,
    "large call, in token chunks": "chunks",
    "large call, every held expert over every token": 8,
}


@pytest.mark.parametrize("path", LARGE_CALL_PATHS)
def test_the_expert_shares_add_up_to_the_uncut_layer(monkeypatch, path):
    """The parts all four shares of 4 experts give, the shared expert counted
    once, add up to the layer that holds all 16, on every path of
    `held_experts_ffn` (the uncut layer takes the same path)."""
    if LARGE_CALL_PATHS[path] is not None:
        monkeypatch.setattr(moe, "SMALL_CALL_SLOTS", 0)
    if isinstance(LARGE_CALL_PATHS[path], int):
        monkeypatch.setattr(moe, "slot_capacity", lambda n, k, held, n_experts: LARGE_CALL_PATHS[path])
    if LARGE_CALL_PATHS[path] == "chunks":
        monkeypatch.setattr(moe, "NARROW_PASS_TOKENS", 12), monkeypatch.setattr(moe, "WIDE_PASS_TOKENS", 12)  # 48 tokens: four passes
    whole_cfg = LMConfig.from_dict({**ARCH, **F32, "experts_held": []})
    x = jax.random.normal(jax.random.PRNGKey(5), (B, T, whole_cfg.d_model))
    layer = moe.ExpertLayer(whole_cfg)
    whole = layer.init(jax.random.PRNGKey(6), x)["params"]
    want, counts = layer.apply({"params": whole}, x)
    assert int(counts.sum()) == B * T * 2
    shared = moe.MLP(whole_cfg, width=whole_cfg.expert_d_ff).apply({"params": whole["shared"]}, x)
    total = shared
    for first in range(0, 16, 4):
        cfg = whole_cfg.replace(experts_held=(first, 4))
        part = {**whole, **{name: whole[name][first:first + 4] for name in ("experts_gate", "experts_up", "experts_down")}}
        total = total + moe.ExpertLayer(cfg).apply({"params": part}, x)[0] - shared
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("n_experts, first_rows", [(128, 4096), (384, 1536)], ids=["K-EXAONE: 8 of 128", "Kimi: 8 of 384"])
def test_the_first_buffer_at_the_cells_train_shape(n_experts, first_rows):
    """A train step, a scoring chunk and the prefill are calls of 4,096 tokens,
    8 a token, 8 held: the slot buffer is twice the even share in whole tiles
    of 512 rows, of the 8,192 it was before PR 31; the counter reads 1.0 at the
    held share the chip read (ledger, PR 30: 0.0622 and 0.0202) and at one and
    a half times it, and says which layers passed the first buffer."""
    assert moe.slot_capacity(4096, 8, 8, n_experts) == first_rows < moe.SLOTS_PER_TOKEN * 4096
    assert first_rows % moe.ROW_TILE == 0 and first_rows >= 2 * 4096 * 8 * 8 / n_experts > first_rows - moe.ROW_TILE
    even = 4096 * 8 * 8 // n_experts
    counts = jnp.full((4, 8), even // 8, jnp.int32)
    assert float(moe.first_buffer_share(counts, 4096, 8, n_experts)) == 1.0
    assert float(moe.first_buffer_share(counts * 3 // 2, 4096, 8, n_experts)) == 1.0
    over = counts.at[2, 0].add(first_rows - int(counts[2].sum()) + 1)  # one slot more than the buffer's rows
    assert float(moe.first_buffer_share(over, 4096, 8, n_experts)) == 0.75
    # a scoring pass over a rollout chunk: 8 chunks of 4,096 tokens, each with a first buffer of its own
    assert float(moe.first_buffer_share(counts * 8, 32768, 8, n_experts)) == 1.0


@pytest.mark.parametrize("bad, message", [
    ({"n_kv_head": 3}, "does not divide"),
    ({"attention": "mla", "q_lora_rank": 8, "kv_lora_rank": 8, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
      "v_head_dim": 8, "attention_layers": [], "window_cache": "span", "rotary_layers": "all", "qk_norm": False,
      "head_width": 0}, "grouped keys"),
    ({"fused_qkv": True}, "grouped keys"),
    ({"n_kv_head": 0, "fused_qkv": True}, "qk_norm and head_width"),
    ({"sp_size": 2}, "sp ring"),
    ({"n_kv_head": 0, "sp_size": 2}, "window_cache 'ring'"),
    ({"n_soft_tokens": 4}, "soft prompts"),
    ({"attention_layers": [], "rotary_layers": "all"}, "window_cache 'ring' needs"),
    ({"attention_layers": [], "window_cache": "span"}, "rotary_layers 'local' needs"),
    ({"pos_type": "learned", "window_cache": "span"}, "rotary_layers 'local' needs"),
    ({"rotary_layers": "global"}, "unknown rotary_layers"),
    ({"window_cache": "paged"}, "unknown window_cache"),
    ({"draw_dtype": "bfloat16"}, "unknown draw_dtype"),
    ({"sliding_window": 8}, "unknown architecture key"),
])
def test_lmconfig_refuses_what_is_not_built(bad, message):
    with pytest.raises(ValueError, match=message):
        LMConfig.from_dict({**ARCH, **bad})


def test_a_ring_cache_is_refused_where_the_static_generate_path_does_not_apply():
    """The paged pool and the rollout engine (with it spec decode) at
    construction; a per-row write offset and a verify window at trace time."""
    from trlx_tpu.engine.rollout_engine import RolloutEngine
    from trlx_tpu.ops.sampling import GenerateConfig

    cfg, model, params, ids, mask = _model()
    with pytest.raises(NotImplementedError, match="window_cache"):
        init_paged_cache(cfg, 4, 8)
    with pytest.raises(NotImplementedError, match="rollout engine"):
        RolloutEngine(model, GenerateConfig(max_new_tokens=4), n_slots=2, prompt_width=4)
    cache = init_cache(cfg, B, T)
    for index, tokens in ((jnp.zeros((B,), jnp.int32), 1), (jnp.int32(4), 2)):
        with pytest.raises(NotImplementedError, match="ring cache"):
            model.apply({"params": params}, ids[:, :tokens], jnp.ones((B, tokens), jnp.int32), cache=cache,
                        cache_index=index, cache_mask=mask)


def test_ring_pieces_and_counters_by_hand():
    # a block of 11 into a ring of 4 leaves positions 7..10, each at position mod 4
    block = jnp.arange(11, dtype=jnp.float32)[None, :, None]
    assert write_ring(jnp.zeros((1, 4, 1)), block, 0)[0, :, 0].tolist() == [8.0, 9.0, 10.0, 7.0]
    assert write_ring(jnp.zeros((1, 4, 1)), block[:, :3], 0)[0, :, 0].tolist() == [0.0, 1.0, 2.0, 0.0]
    assert write_ring(jnp.zeros((1, 4, 1)), block[:, 9:10], jnp.int32(9))[0, :, 0].tolist() == [0.0, 9.0, 0.0, 0.0]
    # the step writing position 5 of a row whose positions 0 and 1 are padding: slots hold 4, 5, 2, 3
    mask = jnp.array([[0, 0, 1, 1, 1, 1, 0, 0]])
    assert (ring_bias(mask, jnp.int32(5), 4)[0, 0, 0] == 0).tolist() == [True, True, True, True]
    assert (ring_bias(mask, jnp.int32(3), 4)[0, 0, 0] == 0).tolist() == [False, False, True, True]  # 0, 1 are padding
    assert (ring_bias(jnp.ones((1, 8), jnp.int32), jnp.int32(1), 4)[0, 0, 0] == 0).tolist() == [True, True, False, False]
    # cache bytes: four rings of 8 and one span of T, K and V, 2 heads of 16, float32
    cfg = LMConfig.from_dict({**ARCH, **F32})
    assert cache_bytes(cfg, B, T) == B * (4 * 8 + T) * 2 * 2 * 16 * 4
    assert cache_bytes_per_token(cfg) == 5 * 2 * 2 * 16 * 4
    # keys read: a ring layer its 8 slots a step; the span layer by the ranged read's buckets
    read, full = kv_keys_read(1024, 128, 896, [8, 8, 8, 0, 8], [8, 8, 8, 0, 8])
    span_read, _ = kv_keys_read(1024, 128, 896, [0])
    assert full == 5 * 1024 * 896 and read == 4 * 8 * 896 + span_read
    assert kv_keys_read(1024, 128, 896, [8, 0]) == kv_keys_read(1024, 128, 896, [8, 0], [0, 0])  # no ring: as before


@pytest.mark.parametrize("draw_dtype", ["", "float32"], ids=["drawn in bfloat16", "drawn in float32"])
def test_weights_drawn_in_float32_carry_no_common_part(draw_dtype):
    """A bfloat16 draw has the same negative mean on every entry (0.012 to
    0.018 deviations: a rank-one part along the all-ones direction, which a
    wide sum amplifies and a router turns into one expert for everyone);
    `draw_dtype: float32` draws without it and still stores bfloat16."""
    from trlx_tpu.models.heads import LMWithValueHead

    cfg = LMConfig.from_dict({**ARCH, "d_model": 256, "d_ff": 1024, "dtype": "bfloat16", "param_dtype": "bfloat16",
                              "draw_dtype": draw_dtype})
    ids = jnp.zeros((1, 2), jnp.int32)
    params = LMWithValueHead(cfg).init(jax.random.PRNGKey(0), ids, jnp.ones_like(ids))["params"]
    drawn = {jax.tree_util.keystr(path): leaf.astype(jnp.float32) for path, leaf in jax.tree_util.tree_leaves_with_path(params)
             if leaf.ndim >= 2 and leaf.size >= 2**13}
    for part in ("q_proj", "down_proj", "experts_gate", "lm_head", "wte", "v_head"):  # trunk, experts, head, table, value head
        assert any(part in name for name in drawn), (part, sorted(drawn))
    assert all(leaf.dtype == jnp.bfloat16 for leaf in jax.tree_util.tree_leaves(params["transformer"]["h_0"]["attn"]))
    pooled = jnp.concatenate([(leaf / leaf.std()).reshape(-1) for leaf in drawn.values()])  # some 1.5 M entries
    mean = float(pooled.mean())
    assert (abs(mean) < 0.003) if draw_dtype else (mean < -0.008), mean


def test_attention_matches_the_published_exaone4_attention():
    """The two `assumed` equations against published code: the program's
    grouped attention with qk-norm and rotary by layer kind gives what
    `Exaone4Attention` (transformers, torch on CPU, random weights) gives, on
    a sliding layer and on a full one."""
    torch = pytest.importorskip("torch")
    modeling = pytest.importorskip("transformers.models.exaone4.modeling_exaone4")
    from transformers.models.exaone4.configuration_exaone4 import Exaone4Config

    d, h, h_kv, hd, window, t = 64, 8, 2, 16, 8, 20
    hf_cfg = Exaone4Config(
        hidden_size=d, num_attention_heads=h, num_key_value_heads=h_kv, head_dim=hd, num_hidden_layers=2,
        sliding_window=window, sliding_window_pattern="LG", layer_types=["sliding_attention", "full_attention"],
        rope_theta=1000000.0, rms_norm_eps=1e-5, max_position_embeddings=64, vocab_size=32, intermediate_size=32)
    hf_cfg._attn_implementation = "eager"
    cfg = LMConfig.from_dict({**ARCH, **F32, "n_layer": 2, "attention_layers": ["local", "global"],
                              "ffn_layers": ["dense", "dense"], "window_cache": "span"})
    torch.manual_seed(0)
    x = torch.randn(2, t, d)
    positions = torch.arange(t)[None].expand(2, t)
    cos_sin = modeling.Exaone4RotaryEmbedding(hf_cfg)(x, positions)
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    for layer, win in ((0, window), (1, 0)):
        attn = modeling.Exaone4Attention(hf_cfg, layer).eval()
        with torch.no_grad():
            for norm in (attn.q_norm, attn.k_norm):
                norm.weight.copy_(1.0 + 0.1 * torch.randn(hd))
            keep = (j <= i) & ((i - j < win) if win else True)
            want = attn(x, cos_sin, torch.tensor(np.where(keep, 0.0, -1e9), dtype=torch.float32)[None, None])[0].numpy()
        kernel = lambda lin: {"kernel": jnp.asarray(lin.weight.detach().numpy().T)}
        params = {"q_proj": kernel(attn.q_proj), "k_proj": kernel(attn.k_proj), "v_proj": kernel(attn.v_proj),
                  "c_proj": kernel(attn.o_proj), "q_norm": {"scale": jnp.asarray(attn.q_norm.weight.detach().numpy())},
                  "k_norm": {"scale": jnp.asarray(attn.k_norm.weight.detach().numpy())}}
        bias = make_attn_bias(jnp.ones((2, t), jnp.int32), t, 0, window=win)
        got, _ = Attention(cfg).apply({"params": params}, jnp.asarray(x.numpy()), bias, rope_tables(cfg, jnp.asarray(positions.numpy())),
                                      window=win)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


def test_ppo_two_iterations_on_the_normal_path(tmp_path):
    """`trlx_tpu.train` -> orchestrator -> ops/generate.py (ring and full-span
    caches, grouped reads) -> make_experience -> learn(): the fresh-step PPO
    ratio compares the ring decode path's own log-probs with the train
    forward; the counters report what the cache holds and reads."""
    import json
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples"))
    import trlx_tpu
    from randomwalks import base_config

    config = base_config("ppo", ARCH["vocab_size"], 16)
    config.model.model_arch = dict(ARCH)
    config.model.num_layers_unfrozen = 1
    config.train.batch_size, config.train.total_steps, config.train.epochs = 8, 4, 4  # dp 8 over the test devices
    config.train.eval_interval, config.train.log_interval = 100, 1
    config.train.checkpoint_dir = str(tmp_path)
    config.method.num_rollouts = config.method.chunk_size = 8
    config.method.ppo_epochs = 2
    config.method.gen_kwargs = {"prompt_length": 4, "max_new_tokens": 20, "min_new_tokens": 20, "do_sample": True,
                                "top_k": 0, "top_p": 1.0}
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, ARCH["vocab_size"], size=int(n)).tolist() for n in rng.integers(2, 5, size=8)]
    trainer = trlx_tpu.train(reward_fn=lambda rows: [float(np.mean(r)) / 96 for r in rows], prompts=prompts,
                             eval_prompts=[[2, 3]], config=config)
    assert trainer.fused_rollout and trainer.model.cfg.window_cache == "ring" and trainer.model.cfg.kv_heads == 2
    records = [json.loads(line) for line in open(os.path.join(str(tmp_path), "metrics.jsonl"))]
    steps = {r["step"]: r for r in records if "step_time" in r}
    assert sorted(steps) == [1, 2, 3, 4]
    for first in (1, 3):  # the first step of each iteration: the policy has not moved since it sampled
        assert abs(steps[first]["mean_ratio"] - 1.0) < 1e-3, steps[first]["mean_ratio"]
    for r in steps.values():
        assert 0.0 < r["moe/held_slot_share"] < 1.0 and r["moe/max_expert_load"] >= 1.0
        assert r["moe/first_buffer_share"] == 1.0  # a tiny model's train step is a small call: no buffer to overflow
    phases = [r for r in records if "time/window_wall_s" in r]
    itemsize = trainer.model.cfg.compute_dtype.itemsize
    assert phases and all(p["rollout/cache_bytes"] == 8 * (4 * 8 + 24) * 2 * 2 * 16 * itemsize for p in phases)
    assert all(p["rollout/kv_read_share"] == (4 * 8 + 24) / (5 * 24) for p in phases)  # four rings of 8, one span of 24
