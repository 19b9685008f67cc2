"""Every Pallas entry point in ops/ lowers — and compiles — for a TPU, on CPU.

The static tile rule (tests/test_tiling.py) is only the first of Mosaic's
checks. Twice a kernel passed it, passed its interpret-mode parity tests and
could not run on the chip: a block mapping the lowering rejected, then a
batched matrix-vector `dot_general` Mosaic cannot express (hidden, three PRs
deep, by a probe that caught the exception and routed to einsum). Neither
needs a chip to find:

- `jax.jit(f).trace(*args).lower(lowering_platforms=("tpu",))` runs the
  jaxpr → Mosaic lowering for a TPU target in seconds on a CPU;
- `jax.experimental.topologies` describes a v5e host to the installed
  libtpu, and `.lower(...).compile()` against it runs the REST of the
  compiler — Mosaic's layout passes and the scoped-VMEM limit — with no
  device attached.

Shapes are the GPT-J-6B ones chip_smoke.py runs (flagship train batch,
logprob head). Run this before spending chip time on a kernel. The last test
holds the other end: a kernel in ops/ that no benchmark cell expects is a
kernel no program runs.
"""

import ast
import functools
import glob
import json
import os

import jax
import jax.numpy as jnp
import pytest

from trlx_tpu.ops.flash_attention import flash_attention
from trlx_tpu.ops.fused_logprob import fused_logprob

B, T, H, D = 8, 1024, 16, 256  # train batch, seq, heads x head_dim
N, DM, V = 2056, 4096, 50400  # logprob head: 8 x 257 rows, d_model, vocab
SCALE = 1.0 / 16.0


def _flash_fwd(q, k, v, m):
    return flash_attention(q, k, v, m, scale=SCALE, causal=True, interpret=False)


def _flash(q, k, v, m):
    """value_and_grad: the forward kernel and both backward kernels."""
    loss = lambda q, k, v: _flash_fwd(q, k, v, m).astype(jnp.float32).sum()
    return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)


def _flash_windowed(q, k, v, m):
    """GPT-Neo's calls: 128-wide heads, unscaled, a local layer's window
    (the loop's lower bound and the edge chunks below the mask-free run)."""
    loss = lambda q, k, v: flash_attention(
        q, k, v, m, scale=1.0, causal=True, window=256, interpret=False
    ).astype(jnp.float32).sum()
    return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)


def _flash_grouped(q, k, v, m):
    """Grouped keys (64 query heads over 8 K/V heads of 128) under a 128-key
    window: K and V by the index map, dk/dv summed over a group in scratch."""
    loss = lambda q, k, v: flash_attention(
        q, k, v, m, scale=128**-0.5, causal=True, window=128, interpret=False
    ).astype(jnp.float32).sum()
    return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)


def _flash_group_of_7(q, k, v, m, window=4096):
    """28 query heads over 4 K/V heads of 128 under a 4,096-key window at a
    length past it (SmallThinker's train step: T 6,144 in three major pieces,
    the dk/dv kernel's innermost axis 7 x 3 steps), and in its full layers
    (window 0). At the cell's train batch of 2 the rows' valid key ranges are
    a [2, 2] operand in SMEM, read by `bh // 28` and, in dk/dv, `bh // 4`."""
    loss = lambda q, k, v: flash_attention(
        q, k, v, m, scale=128**-0.5, causal=True, window=window, interpret=False
    ).astype(jnp.float32).sum()
    return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)


def _flash_prefill_group_of_7(q, k, v, m):
    """The same heads in the rollout's prefill: the forward kernel alone over
    4,096 positions, the window as wide as the block (it fills each ring exactly)."""
    return flash_attention(q, k, v, m, scale=128**-0.5, causal=True, window=4096, interpret=False)


def _flash_group_of_4(q, k, v, m):
    """8 query heads over 2 K/V heads of 128, full span (ZAYA1's "cca" layers
    hand the kernels what an "mha" layer of the shape would: T 6,144 in three
    major pieces in the train step)."""
    loss = lambda q, k, v: flash_attention(q, k, v, m, scale=128**-0.5, causal=True, interpret=False).astype(jnp.float32).sum()
    return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)


def _flash_narrow_heads(q, k, v, m):
    """Heads 64 wide (granite-4.0-h-micro's attention layers: 32 query heads
    over 8), padded with zeros to the kernels' 128 as `Attention` pads them."""
    widen = lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, 0), (0, 64)))
    loss = lambda q, k, v: flash_attention(
        widen(q), widen(k), widen(v), m, scale=1 / 64, causal=True, interpret=False
    )[..., :64].astype(jnp.float32).sum()
    return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)


def _flash_ring_chunk(q, k, v, m, offset):
    """A sequence too long to be resident (major pieces, the state carried in
    scratch) called the way the ring path calls it: a traced offset (the
    loop bounds are traced scalars), lse returned and differentiated."""
    def loss(q, k, v):
        o, lse = flash_attention(q, k, v, m, scale=SCALE, causal=True, offset=offset, return_lse=True, interpret=False)
        return o.astype(jnp.float32).sum() + lse.sum()

    return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)


def _fused(x, w, y, b):
    """value_and_grad: the forward kernel and both backward kernels."""
    loss = lambda x, w, b: sum(
        o.sum() for o in fused_logprob(x, w, y, b, tied=False, interpret=False)
    )
    return jax.value_and_grad(loss, argnums=(0, 1, 2))(x, w, b)


def _fused_tied(x, w, y):
    """The tied head at the vocabulary the hybrid state-space cell runs
    (granite-4.0-h-micro: W is the embedding table [V, D], V = 100,352)."""
    loss = lambda x, w: sum(o.sum() for o in fused_logprob(x, w, y, None, tied=True, interpret=False))
    return jax.value_and_grad(loss, argnums=(0, 1))(x, w)


def _fused_held(x, w, y, b):
    """An untied head as `routed_logprob` hands it over where the chip holds
    `[D, V]` vocabulary-major (V not a multiple of 128): the transpose, to
    the tied kernels, with the bias. ILQL's Q heads: float32 rows."""
    loss = lambda x, w, b: sum(o.sum() for o in fused_logprob(x, w, y, b, tied=True, interpret=False))
    return jax.value_and_grad(loss, argnums=(0, 1, 2))(x, w, b)


def _fused_no_bias(x, w, y):
    """An untied head without a bias (SmallThinker's, V = 37,984: 128 does not divide it)."""
    loss = lambda x, w: sum(o.sum() for o in fused_logprob(x, w, y, None, tied=False, interpret=False))
    return jax.value_and_grad(loss, argnums=(0, 1))(x, w)


def _fused_scoring(x, w, y, b):
    """The scoring program's call: the forward kernel alone over the rollout chunk."""
    return fused_logprob(x, w, y, b, tied=True, interpret=False)[0]


def _cases(s):
    """(name, fn, abstract args) with `s(shape, dtype)` building each arg."""
    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    qkv = s((B, T, H, D), bf16)
    head = (s((N, DM), bf16), s((DM, V), bf16), s((N,), i32), s((V,), bf16))
    return [
        ("flash fwd+bwd", _flash, (qkv, qkv, qkv, s((B, T), f32))),
        ("flash fwd+bwd windowed d128", _flash_windowed, (s((16, 512, H, 128), bf16),) * 3 + (s((16, 512), f32),)),
        ("flash fwd+bwd grouped keys windowed d128", _flash_grouped,
         (s((4, 1024, 64, 128), bf16), s((4, 1024, 8, 128), bf16), s((4, 1024, 8, 128), bf16), s((4, 1024), f32))),
        ("flash fwd+bwd grouped keys 32 over 8, d64 padded to 128", _flash_narrow_heads,
         (s((8, 1024, 32, 64), bf16), s((8, 1024, 8, 64), bf16), s((8, 1024, 8, 64), bf16), s((8, 1024), f32))),
        ("flash fwd+bwd grouped keys 28 over 4, window 4096, T 6144", _flash_group_of_7,
         (s((1, 6144, 28, 128), bf16), s((1, 6144, 4, 128), bf16), s((1, 6144, 4, 128), bf16), s((1, 6144), f32))),
        ("flash fwd+bwd grouped keys 28 over 4, window 4096, T 6144, the train batch of 2", _flash_group_of_7,
         (s((2, 6144, 28, 128), bf16), s((2, 6144, 4, 128), bf16), s((2, 6144, 4, 128), bf16), s((2, 6144), f32))),
        ("flash fwd+bwd grouped keys 28 over 4, a full layer, T 6144, the train batch of 2",
         functools.partial(_flash_group_of_7, window=0),
         (s((2, 6144, 28, 128), bf16), s((2, 6144, 4, 128), bf16), s((2, 6144, 4, 128), bf16), s((2, 6144), f32))),
        ("flash fwd grouped keys 28 over 4, window 4096, the prefill at T 4096", _flash_prefill_group_of_7,
         (s((16, 4096, 28, 128), bf16), s((16, 4096, 4, 128), bf16), s((16, 4096, 4, 128), bf16), s((16, 4096), f32))),
        ("flash fwd+bwd grouped keys 8 over 2, T 6144, the train batch of 2", _flash_group_of_4,
         (s((2, 6144, 8, 128), bf16), s((2, 6144, 2, 128), bf16), s((2, 6144, 2, 128), bf16), s((2, 6144), f32))),
        ("flash fwd grouped keys 8 over 2, the prefill at T 4096", _flash_fwd,
         (s((16, 4096, 8, 128), bf16), s((16, 4096, 2, 128), bf16), s((16, 4096, 2, 128), bf16), s((16, 4096), f32))),
        ("flash fwd+bwd major pieces, traced offset", _flash_ring_chunk,
         (s((1, 8192, H, D), bf16),) * 3 + (s((1, 8192), f32), s((), f32))),
        ("fused_logprob fwd+bwd", _fused, head),
        ("fused_logprob tied fwd+bwd V100352 d2048", _fused_tied,
         (s((8 * 896, 2048), bf16), s((100352, 2048), bf16), s((8 * 896,), i32))),
        # the tiles `head_tiles` gives the cells' calls (512 rows; 256 in dx at the widest models)
        ("fused_logprob fwd+bwd 7168 rows", _fused, (s((8 * 896, DM), bf16),) + head[1:2] + (s((8 * 896,), i32), head[3])),
        ("fused_logprob held [V, D] fwd+bwd 7168 rows", _fused_held,
         (s((8 * 896, DM), bf16), s((V, DM), bf16), s((8 * 896,), i32), s((V,), bf16))),
        ("fused_logprob held [V, D] fwd+bwd float32 rows (ILQL Q head)", _fused_held,
         (s((8 * 255, DM), f32), s((50257, DM), bf16), s((8 * 255,), i32), s((50257,), bf16))),
        ("fused_logprob tied fwd+bwd d7168 V20480", _fused_tied,
         (s((4 * 896, 7168), bf16), s((20480, 7168), bf16), s((4 * 896,), i32))),
        ("fused_logprob fwd+bwd no bias d2560 V37984, 2048 rows", _fused_no_bias,
         (s((2048, 2560), bf16), s((2560, 37984), bf16), s((2048,), i32))),
        ("fused_logprob held [V, D] fwd+bwd d2560 V37984, 4096 rows", _fused_tied,
         (s((2 * 2048, 2560), bf16), s((37984, 2560), bf16), s((2 * 2048,), i32))),
        ("fused_logprob tied fwd+bwd d2048 V131136, 4096 rows", _fused_tied,
         (s((2 * 2048, 2048), bf16), s((131136, 2048), bf16), s((2 * 2048,), i32))),
        ("fused_logprob scoring fwd 28672 rows", _fused_scoring,
         (s((32 * 896, DM), bf16), s((V, DM), bf16), s((32 * 896,), i32), s((V,), bf16))),
    ]


CASE_NAMES = [name for name, _, _ in _cases(jax.ShapeDtypeStruct)]


@pytest.mark.parametrize("name", CASE_NAMES)
def test_kernel_lowers_for_tpu(name):
    """jaxpr → Mosaic for a TPU target. Catches the block-mapping class of
    failure and the dot_general-Mosaic-cannot-express class."""
    (fn, args), = [(f, a) for n, f, a in _cases(jax.ShapeDtypeStruct) if n == name]
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()


@pytest.fixture(scope="module")
def v5e_sharding():
    """A one-device sharding on a described (not attached) v5e host."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu")
    except Exception as e:  # noqa: BLE001 — no libtpu to describe a TPU with
        pytest.skip(f"no TPU topology description available here: {e}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name", CASE_NAMES)
def test_kernel_compiles_for_v5e(name, v5e_sharding):
    """The whole compiler, device-free: Mosaic's layout passes and the
    scoped-VMEM limit run at compile(), which lowering alone never reaches."""
    s = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_sharding)
    (fn, args), = [(f, a) for n, f, a in _cases(s) if n == name]
    jax.jit(fn).lower(*args).compile()


def test_a_v5e_holds_an_untied_head_vocabulary_major_where_the_vocabulary_is_ragged(v5e_sharding, monkeypatch):
    """`held_vocab_major` asks the client for the default layout of the
    weight's shape: a TPU puts the axis that pads less to its (8, 128) tile
    minor, so GPT-J's and GPT-Neo's [4096, V] heads (V not a multiple of 128)
    are held as the rows of [V, 4096] and the kernels take the transpose."""
    from trlx_tpu.ops import fused_logprob as fl

    device = next(iter(v5e_sharding.device_set))
    monkeypatch.setattr(fl, "_layout_device", lambda: device)
    w = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    assert fl.held_vocab_major(w(4096, 50400)) and fl.held_vocab_major(w(4096, 50257))
    assert not fl.held_vocab_major(w(4096, 50304))  # a multiple of 128: row-major
    assert not fl.held_vocab_major(w(7168, 20480)) and not fl.held_vocab_major(w(6144, 19200))
    assert not fl.held_vocab_major(w(50257, 2048)) and not fl.held_vocab_major(w(100352, 2048))  # the tied tables


def test_both_forms_of_the_state_space_mixer_compile_for_v5e(v5e_sharding):
    """models/ssm.py at granite-4.0-h-micro's widths, bf16, no Pallas kernel in
    it: the chunked form forward and backward over [1, 512] (two chunks of
    256) and the recurrent step over 32 rows; the state stays float32."""
    import json
    import os

    from trlx_tpu.models import ssm
    from trlx_tpu.models.lm import LMConfig

    spec = json.load(open(os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs", "granite-4.0-h-micro.json")))
    cfg = LMConfig.from_dict({**spec["model_arch"], "dtype": "bfloat16", "param_dtype": "bfloat16"})
    mixer = ssm.SSMMixer(cfg)
    s = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_sharding)
    x = jnp.zeros((1, 4, cfg.d_model), jnp.bfloat16)
    params = jax.eval_shape(lambda: mixer.init(jax.random.PRNGKey(0), x, jnp.ones((1, 4), jnp.int32))["params"])
    params = jax.tree_util.tree_map(lambda a: s(a.shape, a.dtype), params)

    def train(p, x, m):
        return jax.value_and_grad(lambda p, x: mixer.apply({"params": p}, x, m)[0].astype(jnp.float32).sum(), argnums=(0, 1))(p, x)

    jax.jit(train).lower(params, s((1, 512, 2048), jnp.bfloat16), s((1, 512), jnp.int32)).compile()
    step = lambda p, x, m, c: mixer.apply({"params": p}, x, m, c)
    args = (params, s((32, 1, 2048), jnp.bfloat16), s((32, 1), jnp.int32),
            tuple(s(shape, dtype) for shape, dtype in ssm.cache_shapes(cfg, 32)))
    jax.jit(step).lower(*args).compile()
    _, (conv, state) = jax.eval_shape(step, *args)
    assert state.dtype == jnp.float32 and state.shape == (32, 64, 64, 128) and conv.shape == (32, 3, 4352)


def test_both_forms_of_the_kda_mixer_compile_for_v5e(v5e_sharding):
    """models/kda.py at Kimi-Linear's widths, bf16, no Pallas kernel in it: the
    chunked form forward and backward over [4, 1024] (four row groups of 16
    chunks of 64, each recomputed in its own backward pass) and the recurrent
    step over 32 rows; the state stays float32."""
    import json
    import os

    from trlx_tpu.models import kda
    from trlx_tpu.models.lm import LMConfig

    spec = json.load(open(os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs", "kimi-linear-48b-ep32-l13.json")))
    cfg = LMConfig.from_dict({**spec["model_arch"], "dtype": "bfloat16", "param_dtype": "bfloat16"})
    mixer = kda.KDAMixer(cfg)
    s = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_sharding)
    x = jnp.zeros((1, 4, cfg.d_model), jnp.bfloat16)
    params = jax.eval_shape(lambda: mixer.init(jax.random.PRNGKey(0), x, jnp.ones((1, 4), jnp.int32))["params"])
    params = jax.tree_util.tree_map(lambda a: s(a.shape, a.dtype), params)

    def train(p, x, m):
        return jax.value_and_grad(lambda p, x: mixer.apply({"params": p}, x, m)[0].astype(jnp.float32).sum(), argnums=(0, 1))(p, x)

    compiled = jax.jit(train).lower(params, s((4, 1024, 2304), jnp.bfloat16), s((4, 1024), jnp.int32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9  # 4.8 GB a whole train batch without the row groups
    step = lambda p, x, m, c: mixer.apply({"params": p}, x, m, c)
    args = (params, s((32, 1, 2304), jnp.bfloat16), s((32, 1), jnp.int32),
            tuple(s(shape, dtype) for shape, dtype in kda.cache_shapes(cfg, 32)))
    jax.jit(step).lower(*args).compile()
    _, (conv, state) = jax.eval_shape(step, *args)
    assert state.dtype == jnp.float32 and state.shape == (32, 32, 128, 128) and conv.shape == (32, 3, 12288)


def _zaya_layer(v5e_sharding):
    """One layer of zaya1-ep2.ppo-4096x2048 at its widths, bf16, under remat,
    on a described v5e: (cfg, model, abstract params, the abstract-array maker,
    the train pass over [2, 6144])."""
    import json
    import os

    from trlx_tpu.models.lm import LMConfig, TransformerLM

    spec = json.load(open(os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs", "zaya1-8b-ep2-l8.json")))
    cfg = LMConfig.from_dict({**spec["model_arch"], "dtype": "bfloat16", "param_dtype": "bfloat16", "remat": True,
                              "n_layer": 1, "ffn_layers": ["experts"], "attn_impl": "flash"})
    model = TransformerLM(cfg)
    s = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_sharding)
    ids = jnp.zeros((1, 4), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids, jnp.ones_like(ids))["params"])
    params = jax.tree_util.tree_map(lambda a: s(a.shape, a.dtype), params)

    def train_step(p, ids, mask):
        loss = lambda p: model.apply({"params": p}, ids, mask, compute_logits=False)["hidden"].astype(jnp.float32).sum()
        return jax.value_and_grad(loss)(p)

    return cfg, model, params, s, jax.jit(train_step).lower(params, s((2, 6144), jnp.int32), s((2, 6144), jnp.int32))


def test_a_one_pass_train_step_keeps_its_grouped_products_under_moe_experts(v5e_sharding):
    """ZAYA's train batch of 12,288 tokens goes through the expert layer in
    ONE pass, one expert a token: no `while` and no `cond` contains the nine
    grouped products, XLA names them `ragged-dot-none` with no name stack,
    and `device_scopes.scope_table` has to find them their scope from what
    they read (`moe_experts_ms_per_step` and `scope_attributed_pct` read it)."""
    from trlx_tpu.observability import device_scopes

    text = _zaya_layer(v5e_sharding)[-1].compile().as_text()
    ops = device_scopes.scope_table(text)["ops"]
    products = {name: entry for name, entry in ops.items() if name.startswith("ragged-dot")}
    assert sum(name.startswith("ragged-dot-none") for name in products) == 9  # forward, lhs- and rhs-gradient of gate, up, down
    assert all(chain.split("/")[0] == "moe_experts" for chain, _ in products.values()), products
    entry = text[text.index("\nENTRY "):]
    assert all(f"%{name} = " in entry for name in products)  # none inside a loop or a branch: the rule under test found them


def test_a_cca_layer_s_three_passes_compile_for_v5e(v5e_sharding):
    """One layer of zaya1-ep2.ppo-4096x2048 at its widths, bf16, under remat:
    the train pass (forward and backward over the train batch [2, 6144], the
    attention core through the flash kernels, the MLP router with its carried
    state, 8 held experts one a token), the prefill of 16 x 4,096 into the
    four-leaf cache, and the decode step that advances the window and the
    shifted value through the ranged read."""
    from trlx_tpu.models import cca

    cfg, model, params, s, train = _zaya_layer(v5e_sharding)
    text = train.compile().as_text()
    assert "flash_fwd" in text and "flash_bwd_dkv" in text
    cache = (tuple(s(shape, dtype) for shape, dtype in cca.cache_shapes(cfg, 16, 6144)),)
    prefill = lambda p, ids, mask, cache, cache_mask: model.apply(
        {"params": p}, ids, mask, cache=cache, cache_index=0, cache_mask=cache_mask, logits_start=4095)
    jax.jit(prefill).lower(params, s((16, 4096), jnp.int32), s((16, 4096), jnp.int32), cache, s((16, 6144), jnp.int32)).compile()
    step = lambda p, ids, cache, index, cache_mask: model.apply(
        {"params": p}, ids, jnp.ones((16, 1), jnp.int32), cache=cache, cache_index=index, cache_mask=cache_mask)
    args = (params, s((16, 1), jnp.int32), cache, s((), jnp.int32), s((16, 6144), jnp.int32))
    jax.jit(step).lower(*args).compile()
    (k, v, window, shifted), = jax.eval_shape(step, *args)["cache"]
    assert k.shape == v.shape == (16, 6144, 2, 128) and window.shape == (16, 2, 1280) and shifted.shape == (16, 1, 128)


def test_a_lightning_and_a_sparse_layer_s_three_passes_compile_for_v5e(v5e_sharding):
    """One lightning layer and the sparse layer of minicpmsala-l4.ppo-10240x2048 at
    their widths, bf16, under remat: the train pass (forward and backward over the
    train batch [1, 12288]: the chunked constant-decay pass, the choice and the
    masked attention a query chunk at a time), the prefill of 4 x 10,240 into the
    state leaf and the three-leaf cache, and the decode step that updates the
    state, completes a compressed key and gathers the chosen blocks."""
    import json
    import os

    from trlx_tpu.models.lm import LMConfig, TransformerLM, init_cache

    spec = json.load(open(os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs", "minicpm-sala-9b-l4.json")))
    cfg = LMConfig.from_dict({**spec["model_arch"], "dtype": "bfloat16", "param_dtype": "bfloat16", "remat": True,
                              "n_layer": 2, "mixer_layers": ["lightning", "attention"]})
    model = TransformerLM(cfg)
    s = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_sharding)
    ids = jnp.zeros((1, 4), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids, jnp.ones_like(ids))["params"])
    params = jax.tree_util.tree_map(lambda a: s(a.shape, a.dtype), params)

    def train_step(p, ids, mask):
        def loss(p):
            out = model.apply({"params": p}, ids, mask, compute_logits=False)
            return out["hidden"].astype(jnp.float32).sum(), out["sparse_sums"]
        return jax.value_and_grad(loss, has_aux=True)(p)

    train = jax.jit(train_step).lower(params, s((1, 12288), jnp.int32), s((1, 12288), jnp.int32)).compile()
    assert train.memory_analysis().temp_size_in_bytes < 6e9
    assert "tpu_custom_call" not in train.as_text()  # past 97 blocks the sparse layer takes no flash kernel
    cache = jax.tree_util.tree_map(lambda a: s(a.shape, a.dtype), jax.eval_shape(lambda: init_cache(cfg, 4, 12288)))
    prefill = lambda p, ids, mask, cache, cache_mask: model.apply(
        {"params": p}, ids, mask, cache=cache, cache_index=0, cache_mask=cache_mask, logits_start=10239)
    jax.jit(prefill).lower(params, s((4, 10240), jnp.int32), s((4, 10240), jnp.int32), cache, s((4, 12288), jnp.int32)).compile()
    step = lambda p, ids, cache, index, cache_mask: model.apply(
        {"params": p}, ids, jnp.ones((4, 1), jnp.int32), cache=cache, cache_index=index, cache_mask=cache_mask)
    args = (params, s((4, 1), jnp.int32), cache, s((), jnp.int32), s((4, 12288), jnp.int32))
    jax.jit(step).lower(*args).compile()
    (state,), (k, v, compressed) = jax.eval_shape(step, *args)["cache"]
    assert state.dtype == jnp.float32 and state.shape == (4, 32, 128, 128)
    assert k.shape == v.shape == (4, 12288, 2, 128) and compressed.shape == (4, 767, 2, 128)


def test_an_indexed_latent_layer_s_three_passes_compile_for_v5e(v5e_sharding):
    """One dense block of glm5-l5.ppo-6144x2048 at its widths (16 heads of 192 + 64 / 256, the
    indexer's 32 x 128, top-2048), bf16, under remat: the train pass (forward and backward over the train batch
    [1, 8192]: the index scores, the k-th largest by counts and the masked unabsorbed attention a query chunk at a
    time), the prefill of 4 x 6,144 into the three-leaf cache, and the decode step that scores the row's index keys,
    takes the top-2,048 and gathers the chosen latent entries."""
    import json
    import os

    from trlx_tpu.models.lm import LMConfig, TransformerLM, init_cache

    spec = json.load(open(os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs", "glm-5-ep32-tp4-l5.json")))
    cfg = LMConfig.from_dict({**spec["model_arch"], "dtype": "bfloat16", "param_dtype": "bfloat16", "remat": True,
                              "n_layer": 1, "ffn_layers": ["dense"]})  # the expert layer compiles in its own cells' tests
    model = TransformerLM(cfg)
    s = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_sharding)
    ids = jnp.zeros((1, 4), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids, jnp.ones_like(ids))["params"])
    params = jax.tree_util.tree_map(lambda a: s(a.shape, a.dtype), params)

    def train_step(p, ids, mask):
        def loss(p):
            out = model.apply({"params": p}, ids, mask, compute_logits=False)
            return out["hidden"].astype(jnp.float32).sum(), out["dsa_sums"]
        return jax.value_and_grad(loss, has_aux=True)(p)

    train = jax.jit(train_step).lower(params, s((1, 8192), jnp.int32), s((1, 8192), jnp.int32)).compile()
    assert train.memory_analysis().temp_size_in_bytes < 4e9
    # the softmax's row maximum stays a reduction: fused with its subtraction the compiler made ONE reduce-window
    # 16,383 wide over each [16, 512, 8192] score chunk, 23.6 ms a chunk on the chip (PERF.md section 6, PR 53)
    assert "size=1x1x16383" not in train.as_text()
    assert "flash_attention" not in train.as_text()  # past index_topk tokens an indexed layer takes no flash kernel
    cache = jax.tree_util.tree_map(lambda a: s(a.shape, a.dtype), jax.eval_shape(lambda: init_cache(cfg, 4, 8192)))
    prefill = lambda p, ids, mask, cache, cache_mask: model.apply(
        {"params": p}, ids, mask, cache=cache, cache_index=0, cache_mask=cache_mask, logits_start=6143)
    jax.jit(prefill).lower(params, s((4, 6144), jnp.int32), s((4, 6144), jnp.int32), cache, s((4, 8192), jnp.int32)).compile()
    step = lambda p, ids, cache, index, cache_mask: model.apply(
        {"params": p}, ids, jnp.ones((4, 1), jnp.int32), cache=cache, cache_index=index, cache_mask=cache_mask)
    args = (params, s((4, 1), jnp.int32), cache, s((), jnp.int32), s((4, 8192), jnp.int32))
    compiled = jax.jit(step).lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9
    for c_kv, k_rope, k_idx in jax.eval_shape(step, *args)["cache"]:
        assert (c_kv.shape, k_rope.shape, k_idx.shape) == ((4, 8192, 512), (4, 8192, 64), (4, 8192, 128))


def test_the_delta_rule_pass_does_not_hand_its_inverse_to_autodiff(v5e_sharding):
    """One row of `kda_chunked` at Kimi-Linear's train shapes ([1, 1024], 32
    heads of 128, bf16 operands, chunks of 64: what one step of the mixer's
    `lax.map` runs), forward and gradient in all five operands, as the v5e's
    compiler leaves it. With the parent's `unit_lower_inverse` (PR 39: sixteen
    substitution steps each against the rows so far stacked, three growing
    eliminations, all of it differentiated step by step) the gradient's
    program was 291 fusions and 9.48 GB by the compiler's count of bytes, the
    forward's 125 fusions. With the backward pass two products and the
    eliminations two rounds of pairs (PERF.md section 6, PR 40) they are 198
    fusions, 7.91 GB and 113: an edit that hands the inverse back to autodiff
    fails here and not in a benchmark. (The counts are the compiler's, not
    the chip's: a form that read 180 fusions and 7.35 GB here, the systems
    along the lanes, took twice the parent's time on the chip, same section.)"""
    from trlx_tpu.models import kda

    s = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_sharding)
    shape = (1, 1024, 32, 128)
    ops = (s(shape, jnp.bfloat16),) * 3 + (s(shape, jnp.float32), s(shape[:3], jnp.float32))
    forward = lambda *ops: kda.kda_chunked(*ops, kda.CHUNK, jnp.bfloat16)[0]
    gradient = lambda w, *ops: jax.grad(lambda *ops: (forward(*ops) * w).sum(), argnums=(0, 1, 2, 3, 4))(*ops)
    compiled = jax.jit(forward).lower(*ops).compile()
    assert compiled.as_text().count(" fusion(") < 125
    compiled = jax.jit(gradient).lower(s(shape, jnp.float32), *ops).compile()
    assert compiled.as_text().count(" fusion(") < 240
    assert compiled.cost_analysis()["bytes accessed"] < 8.7e9


def test_the_chunked_scan_relays_no_float32_array_64_lanes_wide(v5e_sharding):
    """`ssd_chunked` forward and gradient at granite-4.0-h-micro's train
    shapes ([8, 1024], 64 heads of 64, state 128, chunks of 256, bf16), as the
    v5e's compiler leaves it. A head 64 wide fills half a 128-lane tile: held
    P-minor, x and y were float32 arrays of 268 MB for 134, copied three to
    four times a layer a pass (PERF.md section 6, PR 38). The form holds them
    positions-minor, so NO float32 result as large as x has a last axis of 64
    (what is left 64 wide is a head's scalar a position or a chunk, H = 64
    here: at most b T H elements, and no relayout instruction among those
    either beyond a chunk's decay), and x crosses HBM in a relayout twice in,
    twice out: x and its gradient in bf16, y and its cotangent in float32."""
    import math
    import re

    from trlx_tpu.models import ssm

    b, T, H, P, N, Q = 8, 1024, 64, 64, 128, 256
    s = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_sharding)

    def scalar(x, dt, a, B, C, wy, wl):  # the mixer's own reshapes in and out
        y, last = ssm.ssd_chunked(x.reshape(b, T, H, P), dt, a, B, C, Q, jnp.bfloat16)
        return (y.reshape(b, T, H * P) * wy).sum() + (last * wl).sum()

    text = jax.jit(jax.value_and_grad(scalar, argnums=(0, 1, 2, 3, 4))).lower(
        s((b, T, H * P), jnp.bfloat16), s((b, T, H), jnp.float32), s((H,), jnp.float32), s((b, T, N), jnp.bfloat16),
        s((b, T, N), jnp.bfloat16), s((b, T, H * P), jnp.float32), s((b, H, P, N), jnp.float32)).compile().as_text()
    relayout, wide, moved = ("copy", "reshape", "transpose"), [], []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([a-z][\w\-]*)\(", line)
        if not m or m.group(2) in ("parameter", "bitcast", "tuple", "get-tuple-element"):
            continue
        for dtype, dims in re.findall(r"\b(f32|bf16)\[([\d,]+)\]", m.group(1)):
            dims = [int(d) for d in dims.split(",")]
            if dtype == "f32" and dims[-1] == 64:
                wide.append((m.group(2), math.prod(dims)))
            if m.group(2) in relayout and math.prod(dims) >= b * T * H * P:
                moved.append(dtype)
    assert wide and max(n for _, n in wide) <= b * T * H, sorted(wide, key=lambda w: -w[1])[:5]
    assert all(n <= b * (T // Q) * H for op, n in wide if op in relayout), [w for w in wide if w[0] in relayout]
    assert sorted(moved) == ["bf16", "bf16", "f32", "f32"], moved


def test_rotary_keeps_the_projection_s_output_out_of_float32(v5e_sharding):
    """A q (or k) projection followed by rotary at Ouro-2.6B's train shape
    ([8, 1024, 2048] -> [8, 1024, 16, 128], bf16, rotate-half over the whole
    head), forward and gradient, as the v5e's compiler leaves it. The sliced
    formula began with a convert, which the compiler folded into the product:
    the projection's output crossed HBM in float32 (67 MB for 34), was copied
    whole in float32 and rotated in two halves 64 lanes wide (PERF.md section
    6, PR 50). `apply_rotary` reads the projection's output in bf16 (the pair
    swap is a product with a 0/1 matrix) and does its float32 sums inside one
    fusion: NO instruction of the entry computation has a float32 result of
    the projection's size. A count, not a rate."""
    import math
    import re

    from trlx_tpu.models.lm import apply_rotary, rotary_tables

    b, T, D, H, hd = 8, 1024, 2048, 16, 128
    s = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_sharding)

    def forward(x, w, positions):
        q = (x @ w).reshape(b, T, H, hd)
        return apply_rotary(q, rotary_tables(positions, hd, hd, 1e6, True), hd, True)

    def gradient(x, w, positions, ct):
        y, vjp = jax.vjp(lambda x, w: forward(x, w, positions), x, w)
        return (y,) + vjp(ct)

    args = (s((b, T, D), jnp.bfloat16), s((D, D), jnp.bfloat16), s((b, T), jnp.int32))
    for fn, operands in ((forward, args), (gradient, args + (s((b, T, H, hd), jnp.bfloat16),))):
        text = jax.jit(fn).lower(*operands).compile().as_text()
        entry = text[text.index("\nENTRY "):]
        wide, seen = [], 0
        for line in entry.splitlines():
            m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([a-z][\w\-]*)\(", line)
            if not m or m.group(2) in ("parameter", "bitcast", "tuple", "get-tuple-element"):
                continue
            for dtype, dims in re.findall(r"\b(f32|bf16)\[([\d,]+)\]", m.group(1)):
                size = math.prod(int(d) for d in dims.split(","))
                seen += size == b * T * D
                if dtype == "f32" and size >= b * T * D:
                    wide.append((m.group(2), dims))
        assert seen, "no result of the projection's size in the entry computation: the pattern reads nothing"
        assert not wide, wide


def test_mosaic_kernels_refuse_a_multi_device_jit(v5e_sharding):
    """Why every model-layer gate requires a one-device mesh
    (flash_attention.one_device_tpu): jax will not partition a Mosaic call."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    topo = topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu")
    sh = NamedSharding(Mesh(np.array(topo.devices), ("dp",)), PartitionSpec("dp"))
    qkv = jax.ShapeDtypeStruct((B, 256, H, D), jnp.bfloat16, sharding=sh)
    mask = jax.ShapeDtypeStruct((B, 256), jnp.float32, sharding=sh)
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(_flash_fwd).lower(qkv, qkv, qkv, mask)


def test_one_device_rule(monkeypatch):
    """On a TPU backend the gates open on a one-device mesh and close on a
    larger one; off TPU they are closed."""
    from trlx_tpu.ops import flash_attention as fa
    from trlx_tpu.ops.fused_logprob import fused_logprob_eligible
    from trlx_tpu.parallel import mesh as mesh_mod

    assert not fa.one_device_tpu()  # the CPU backend of this test
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(mesh_mod, "_GLOBAL_MESH", None)
    assert fa.one_device_tpu() and fused_logprob_eligible(DM, V)
    monkeypatch.setattr(mesh_mod, "_GLOBAL_MESH", mesh_mod.make_mesh([1, 1, 1, 1], jax.devices()[:1]))
    assert fa.one_device_tpu()
    monkeypatch.setattr(mesh_mod, "_GLOBAL_MESH", mesh_mod.make_mesh([4, 1, 1, 1], jax.devices()[:4]))
    assert not fa.one_device_tpu() and not fused_logprob_eligible(DM, V)


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@functools.lru_cache(maxsize=None)
def _pallas_calls_in_ops():
    """{"<module>.<kernel function>": [(line, has a name=), ...]} of every
    `pallas_call` under trlx_tpu/ops/, read from the source: the key is the
    one the benchmark records at trace time (benchmark/harness.py
    `record_pallas_calls`) and a cell's `expect_kernels` lists."""
    calls = {}
    for path in sorted(glob.glob(os.path.join(REPO, "trlx_tpu", "ops", "*.py"))):
        module = os.path.basename(path)[:-3]
        with open(path) as f:
            tree = ast.parse(f.read())
        functions = {n.name for n in tree.body if isinstance(n, ast.FunctionDef)}
        assigned = {n.targets[0].id: n.value for n in ast.walk(tree)
                    if isinstance(n, ast.Assign) and isinstance(n.targets[0], ast.Name)}
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and getattr(node.func, "attr", None) == "pallas_call"):
                continue
            kernel = node.args[0]
            if isinstance(kernel, ast.Name) and kernel.id not in functions:
                kernel = assigned.get(kernel.id, kernel)  # `kernel = functools.partial(_fwd_kernel, ...)`
            if isinstance(kernel, ast.Call) and getattr(kernel.func, "attr", "") == "partial":
                kernel = kernel.args[0]
            # a kernel this cannot name is a case of its own, and fails below
            fn = kernel.id if isinstance(kernel, ast.Name) and kernel.id in functions else f"<line {node.lineno}>"
            named = any(kw.arg == "name" for kw in node.keywords)
            calls.setdefault(f"{module}.{fn}", []).append((node.lineno, named))
    return calls


@pytest.mark.parametrize("kernel", sorted(_pallas_calls_in_ops()))
def test_every_pallas_kernel_in_ops_is_expected_by_a_cell(kernel):
    """A kernel stays in ops/ while a benchmark cell runs it. Every
    `pallas_call` carries a `name=` (how a device trace finds it), and its
    kernel is in `expect_kernels` of at least one cell, so the chip run of
    that cell fails when the route to it closes. A kernel that loses its
    measurement goes, with its gates; it does not stay switched off."""
    expected = set()
    for path in glob.glob(os.path.join(REPO, "benchmark", "workloads", "*.json")):
        with open(path) as f:
            expected |= set(json.load(f)["expect_kernels"])
    unnamed = [line for line, named in _pallas_calls_in_ops()[kernel] if not named]
    assert not unnamed, f"{kernel}: pallas_call without name= at line(s) {unnamed}"
    assert kernel in expected, f"{kernel} is in no cell's expect_kernels: {sorted(expected)}"
