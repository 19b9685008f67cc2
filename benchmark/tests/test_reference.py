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
