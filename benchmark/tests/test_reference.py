"""The plain reference against the program's model, tiny sizes, CPU, float32
on both sides, so what separates them is summation order only (tolerance
2e-4 on logits of unit scale; a bf16 step anywhere would miss it by 10x)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import gpt_decoder
from trlx_tpu.models.lm import LMConfig, TransformerLM

GPTJ = {"vocab_size": 512, "n_layer": 2, "n_head": 2, "d_model": 64, "max_position": 128, "pos_type": "rotary",
        "rotary_dim": 16, "parallel_residual": True, "use_parallel_ln": False, "fused_qkv": False, "qkv_bias": False,
        "out_bias": False, "scale_attn": True, "tie_word_embeddings": False, "activation": "gelu_new", "ln_eps": 1e-5,
        "extra": {"lm_head_bias": True}}
NEO = {"vocab_size": 509, "n_layer": 4, "n_head": 2, "d_model": 64, "max_position": 128, "pos_type": "learned",
       "parallel_residual": False, "fused_qkv": False, "qkv_bias": False, "out_bias": True, "scale_attn": False,
       "attention_layers": ["global", "local", "global", "local"], "window_size": 8, "tie_word_embeddings": True,
       "activation": "gelu_new", "ln_eps": 1e-5}
NEOX = dict(GPTJ, use_parallel_ln=True, fused_qkv=True, qkv_bias=True, out_bias=True, activation="gelu",
            extra={"neox_rotary": True})
GPT2 = dict(NEO, attention_layers=[], window_size=0, scale_attn=True, fused_qkv=True, qkv_bias=True)


@pytest.mark.parametrize("arch", [GPTJ, NEO, NEOX, GPT2], ids=["gptj", "gptneo-windowed", "neox", "gpt2"])
def test_reference_matches_the_program(arch):
    cfg = LMConfig.from_dict({**arch, "dtype": "float32", "param_dtype": "float32", "attn_impl": "xla"})
    model = TransformerLM(cfg)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(2, arch["vocab_size"], size=(2, 48)), jnp.int32)
    mask = np.ones((2, 48), np.int32)
    mask[1, :16] = 0  # a left-padded row: positions count from its first real token
    mask = jnp.asarray(mask)
    params = model.init(jax.random.PRNGKey(1), ids, mask)["params"]
    # biases and LayerNorm offsets start at zero: perturb every leaf so each one matters
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    params = jax.tree_util.tree_unflatten(
        tree, [x + 0.1 * jax.random.normal(k, x.shape, x.dtype) for x, k in zip(leaves, keys)])
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, ids, mask)["logits"][:, -24:]
    want = gpt_decoder.forward(params, arch, ids, mask, last=24)
    assert want.shape == (2, 24, arch["vocab_size"]) and want.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=0)
    # and the windowed layers do something: without the window the logits differ
    if arch.get("window_size"):
        wide = gpt_decoder.forward(params, dict(arch, window_size=0), ids, mask, last=24)
        assert float(jnp.max(jnp.abs(wide - want))) > 1e-2


@pytest.mark.parametrize("arch", [GPTJ, NEOX], ids=["gptj", "neox"])  # scaled attention: GPT-Neo's is ill-conditioned on fresh weights
def test_the_coarser_reruns_stand_in_their_order(arch):
    """`bfloat16` rounds what the matmuls read and nothing else (the yardstick
    of PR 22, arithmetic unchanged); `bfloat16_stream` rounds what every
    operation hands on as well; the controls feed the matmuls int8 on top of
    that, `int8_dense` all but attention's two. Each step down moves the
    logits farther from the float32 reference."""
    model = TransformerLM(LMConfig.from_dict({**arch, "dtype": "float32", "param_dtype": "float32", "attn_impl": "xla"}))
    ids = jnp.asarray(np.random.default_rng(0).integers(2, arch["vocab_size"], size=(2, 48)), jnp.int32)
    mask = jnp.ones((2, 48), jnp.int32)
    params = model.init(jax.random.PRNGKey(1), ids, mask)["params"]
    want = gpt_decoder.forward(params, arch, ids, mask, last=24)
    far = {name: float(jnp.sqrt(jnp.mean((gpt_decoder.forward(params, arch, ids, mask, 24, precision=name) - want) ** 2)))
           for name in gpt_decoder.PRECISIONS}
    assert far["highest"] == 0.0
    assert 0 < far["bfloat16"] < far["bfloat16_stream"] < min(far["int8_dense"], far["int8"]), far
    assert far["int8"] > 3 * far["bfloat16_stream"], far
    # one bf16 pass, written out: operands rounded, float32 sums, nothing else touched
    x = jnp.asarray(np.random.default_rng(1).normal(size=(8, 64)), jnp.float32)
    p = {"kernel": jnp.asarray(np.random.default_rng(2).normal(size=(64, 32)), jnp.float32)}
    r, _, s = gpt_decoder.PRECISIONS["bfloat16"]
    with jax.default_matmul_precision("highest"):
        by_hand = x.astype(jnp.bfloat16).astype(jnp.float32) @ p["kernel"].astype(jnp.bfloat16).astype(jnp.float32)
        np.testing.assert_array_equal(np.asarray(gpt_decoder._dense(x, p, r, s)), np.asarray(by_hand))
    with pytest.raises(ValueError, match="precision"):
        gpt_decoder.forward(params, arch, ids, mask, 24, precision="float8")
