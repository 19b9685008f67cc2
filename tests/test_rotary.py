"""The rotary signal as `x * C + R(x) * S` over a head's whole width
(`models/lm.py apply_rotary`, `rotary_tables`) against the formula it
replaced, kept here as the plain reference: convert to float32, slice the
rotated channels into halves (NeoX) or even/odd neighbours (GPT-J), rotate
the pieces, put them together, round once. The two are the same arithmetic
(`a - b` is `a + (-b)`, `x * 1 + 0 * 0` is `x`), so evaluated operation by
operation on the CPU they agree bit for bit, and so do their gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.models import lm
from trlx_tpu.models.lm import LMConfig, apply_rotary, rotary_layout, rope_tables, rotary_sincos, rotary_swap, rotary_tables


def reference_rotary(x, sin, cos, rotary_dim, neox_style):
    """x [b, t, n_head, head_dim]; sin/cos [b, t, rotary_dim / 2]."""
    rot = x[..., :rotary_dim].astype(jnp.float32)
    rest = x[..., rotary_dim:]
    sin = sin[:, :, None, :]
    cos = cos[:, :, None, :]
    if neox_style:
        half = rotary_dim // 2
        x1, x2 = rot[..., :half], rot[..., half:]
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    else:
        x1 = rot[..., ::2]
        x2 = rot[..., 1::2]
        r1 = x1 * cos - x2 * sin
        r2 = x2 * cos + x1 * sin
        out = jnp.stack([r1, r2], axis=-1).reshape(rot.shape)
    return jnp.concatenate([out.astype(x.dtype), rest], axis=-1) if rotary_dim < x.shape[-1] else out.astype(x.dtype)


def left_padded_positions(rng, b, t):
    """Row r starts after its own run of pad tokens (row 0 has none)."""
    pads = np.concatenate([[0], rng.integers(0, t, b - 1)])
    mask = (np.arange(t)[None, :] >= pads[:, None]).astype(np.int32)
    return jnp.asarray(np.maximum(np.cumsum(mask, axis=-1) - 1, 0)) + 3  # + 3: a decode step's row is not at 0


def bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


@pytest.fixture(params=["product", "slices"])
def swap_by(request, monkeypatch):
    """Both statements of the pair swap at every size: the product with the 0/1
    matrix (a train batch's) and the slices (a decode step's)."""
    monkeypatch.setattr(lm, "ROTARY_MXU_MIN", 0 if request.param == "product" else 1 << 62)
    return request.param


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("q_len", [1, 17, 1024])
@pytest.mark.parametrize("head_dim,rotary_dim", [(128, 128), (256, 64), (128, 64)])
@pytest.mark.parametrize("neox_style", [True, False], ids=["neox", "interleaved"])
def test_the_full_width_form_is_the_sliced_formula_bit_for_bit(neox_style, head_dim, rotary_dim, q_len, dtype, swap_by):
    rng = np.random.default_rng(head_dim + rotary_dim + q_len)
    b, h = 3, 2
    x = jnp.asarray(rng.normal(size=(b, q_len, h, head_dim)), dtype)
    positions = left_padded_positions(rng, b, q_len)
    sin, cos = rotary_sincos(positions, rotary_dim, 1e4)
    tables = rotary_tables(positions, head_dim, rotary_dim, 1e4, neox_style)
    assert all(t.dtype == jnp.float32 and t.shape == (b, q_len, 1, head_dim) for t in tables)
    with jax.disable_jit():  # operation by operation: no compiler decides where a product meets its sum
        want = reference_rotary(x, sin, cos, rotary_dim, neox_style)
        got = apply_rotary(x, tables, rotary_dim, neox_style)
        square = lambda f: (lambda x: jnp.sum(f(x).astype(jnp.float32) ** 2))
        g_want = jax.grad(square(lambda x: reference_rotary(x, sin, cos, rotary_dim, neox_style)))(x)
        g_got = jax.grad(square(lambda x: apply_rotary(x, tables, rotary_dim, neox_style)))(x)
    assert got.dtype == want.dtype == x.dtype and g_got.dtype == x.dtype
    np.testing.assert_array_equal(bits(got), bits(want))
    # the channels past rotary_dim pass through untouched
    np.testing.assert_array_equal(bits(got[..., rotary_dim:]), bits(x[..., rotary_dim:]))
    if dtype == "float32":
        scale = float(jnp.max(jnp.abs(g_want)))
        assert float(jnp.max(jnp.abs(g_got - g_want))) <= 1e-6 * scale
    else:  # one rounding of a float32 sum on either side
        np.testing.assert_array_equal(bits(g_got), bits(g_want))


@pytest.mark.parametrize("neox_style", [True, False], ids=["neox", "interleaved"])
def test_the_compiled_form_stays_within_a_rounding_of_the_formula(neox_style, swap_by):
    """Under jit the CPU's compiler may fuse a product into its sum on either
    side: float32 agrees to a few ulp, and bf16 results differ, if at all, by
    the last bit of a value on a rounding boundary."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 33, 4, 128)), jnp.float32)
    positions = left_padded_positions(rng, 2, 33)
    sin, cos = rotary_sincos(positions, 64, 1e4)
    tables = rotary_tables(positions, 128, 64, 1e4, neox_style)
    want = jax.jit(reference_rotary, static_argnums=(3, 4))(x, sin, cos, 64, neox_style)
    got = jax.jit(apply_rotary, static_argnums=(2, 3))(x, tables, 64, neox_style)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    xb = x.astype(jnp.bfloat16)
    want = jax.jit(reference_rotary, static_argnums=(3, 4))(xb, sin, cos, 64, neox_style).astype(jnp.float32)
    got = jax.jit(apply_rotary, static_argnums=(2, 3))(xb, tables, 64, neox_style).astype(jnp.float32)
    assert float(jnp.mean(got != want)) < 1e-3
    np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=0)


@pytest.mark.parametrize("width,rotary_dim,neox_style", [(128, 128, True), (256, 64, False), (128, 64, True), (64, 64, False)])
def test_the_pair_swap_is_a_permutation_of_the_rotated_channels(width, rotary_dim, neox_style):
    swap = rotary_swap(width, rotary_dim, neox_style)
    assert swap.shape == (width, width) and set(np.unique(swap)) <= {0.0, 1.0}
    np.testing.assert_array_equal(swap.sum(axis=0), (np.arange(width) < rotary_dim).astype(np.float32))  # one term a column
    np.testing.assert_array_equal(swap, swap.T)  # a swap of pairs: its own inverse
    x = np.arange(width, dtype=np.float32)
    partner = x @ swap
    want = np.where(np.arange(width) < rotary_dim,
                    (np.arange(width) + rotary_dim // 2) % rotary_dim if neox_style else np.arange(width) ^ 1, 0)
    np.testing.assert_array_equal(partner, want)


def test_the_size_of_the_call_picks_the_statement_of_the_swap():
    """A train batch's q goes to the MXU (one `dot_general`), a decode step's
    rows stay slices (none): the rule reads the call's own shape."""
    for shape, products in (((8, 1024, 16, 128), 1), ((32, 1, 16, 128), 0), ((32, 128, 16, 128), 1), ((8, 1024, 1, 64), 0)):
        tables = tuple(jax.ShapeDtypeStruct(shape[:2] + (1, shape[3]), jnp.float32) for _ in range(2))
        jaxpr = jax.make_jaxpr(lambda x, c, s: apply_rotary(x, (c, s), 64))(jax.ShapeDtypeStruct(shape, jnp.bfloat16), *tables)
        assert str(jaxpr).count("dot_general") == products, shape


def test_the_tables_of_a_configuration_follow_its_flags():
    """`rope_tables`: None without rotary positions; a head's width and the
    NeoX flag from the configuration; latent attention's rope part whole, in
    interleaved pairs, at YaRN's frequencies and table factor."""
    positions = jnp.asarray([[0, 0, 1, 2], [5, 6, 7, 8]])
    base = dict(vocab_size=16, n_layer=1, n_head=2, d_model=64, dtype="float32")
    assert rope_tables(LMConfig(**base, pos_type="learned"), positions) is None
    cfg = LMConfig(**base, pos_type="rotary", rotary_dim=16, rope_theta=5e5, extra={"neox_rotary": True})
    assert rotary_layout(cfg) == (32, 16, True)
    for got, want in zip(rope_tables(cfg, positions), rotary_tables(positions, 32, 16, 5e5, True)):
        np.testing.assert_array_equal(got, want)
    scaling = {"type": "yarn", "factor": 40.0, "original_max_position_embeddings": 64, "beta_fast": 32, "beta_slow": 1,
               "mscale": 1.0, "mscale_all_dim": 0.5}
    mla = LMConfig.from_dict({**base, "pos_type": "rotary", "attention": "mla", "norm": "rmsnorm", "kv_lora_rank": 16,
                              "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_scaling": scaling})
    assert rotary_layout(mla) == (8, 8, False)
    from trlx_tpu.models.lm import yarn_inv_freq, yarn_mscale

    factor = yarn_mscale(40.0, 1.0) / yarn_mscale(40.0, 0.5)
    sin, cos = rotary_sincos(positions, 8, mla.rope_theta, inv_freq=yarn_inv_freq(8, mla.rope_theta, scaling))
    c, s = rope_tables(mla, positions)
    np.testing.assert_array_equal(c[:, :, 0, ::2], cos * factor)
    np.testing.assert_array_equal(c[:, :, 0, 1::2], cos * factor)
    np.testing.assert_array_equal(s[:, :, 0, ::2], -(sin * factor))
    np.testing.assert_array_equal(s[:, :, 0, 1::2], sin * factor)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 4, 3, 8)), jnp.float32)
    with jax.disable_jit():
        np.testing.assert_array_equal(bits(apply_rotary(x, (c, s), 8)),
                                      bits(reference_rotary(x, sin * factor, cos * factor, 8, False)))
