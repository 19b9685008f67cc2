"""Decode attention's ranged cache read (trlx_tpu/ops/kv_read.py).

The static generate path reads, on each decode step, only a static-size
slice of the KV cache that holds every key the bias can admit, chosen by a
`lax.switch` on the write frontier. These tests pin: the same logits and
tokens as the full-cache read, the ranges as a pure function, that the
engine's per-row and paged steps do not take it, that the decode loop stays
one `while`, the host counter `rollout/kv_read_share`, and how a read of an
int8 cache states its two contractions (`attend_quantized`: a key's scale once
a key, the int8 values converted and nothing else).
"""

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

import trlx_tpu.models.lm as lm
from trlx_tpu.models.lm import LMConfig, TransformerLM, init_cache, init_paged_cache, make_attn_bias
from trlx_tpu.ops.generate import generate
from trlx_tpu.ops import kv_read
from trlx_tpu.ops.kv_read import (
    KV_READ_BUCKET,
    KV_READ_MAX_BRANCHES,
    attend,
    attend_quantized,
    attend_range,
    kv_keys_read,
    kv_read_bucket,
    kv_read_ranges,
    kv_scale_mults_per_key,
    ranged_read,
)
from trlx_tpu.ops.sampling import GenerateConfig

# Prompt 120 + 200 new tokens: a cache of 320 (323 with soft slots) has the
# branches [0,128) [0,256) [0,320); the frontier starts in the first and
# crosses both bucket edges. Window 100 < cache: the last local branch starts
# at 128, so a range with lo > 0 is exercised too.
B, P, N, WINDOW, N_SOFT = 2, 120, 200, 100, 3


def _tiny(quant, local, soft, n_layer=2):
    cfg = LMConfig(
        vocab_size=64, n_layer=n_layer, n_head=2, d_model=32, max_position=512,
        pos_type="learned" if local else "rotary", rotary_dim=8,
        attention_layers=("global", "local") * (n_layer // 2) if local else (),
        window_size=WINDOW if local else 0,
        n_soft_tokens=N_SOFT if soft else 0, kv_cache_quant=quant, dtype="float32",
    )
    model = TransformerLM(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (B, P), 1, cfg.vocab_size)
    mask = jnp.asarray(np.arange(P)[None, :] >= np.array([[17], [0]]), jnp.int32)  # row 0 left-padded
    params = model.init(jax.random.PRNGKey(0), ids[:, :4], mask[:, :4])
    return cfg, model, params, ids * mask, mask


def _greedy(model, params, ids, mask, new_tokens=N):
    """Greedy generate; returns (tokens, [b, N, V] logits each step sampled from)."""
    gcfg = GenerateConfig(max_new_tokens=new_tokens, do_sample=False, eos_token_id=None)
    fn = lambda p, i, m: generate(
        p, i, m, jax.random.PRNGKey(2), model=model, gcfg=gcfg,
        step_stats_fn=lambda tok, s: {"logits": s["last_logits"]},
    )
    tokens, _, stats = jax.jit(fn)(params, ids, mask)
    return np.asarray(tokens), np.asarray(stats["logits"])


@pytest.mark.parametrize("soft", [False, True], ids=["nosoft", "soft"])
@pytest.mark.parametrize("local", [False, True], ids=["global", "alternating-local"])
@pytest.mark.parametrize("quant", [False, True], ids=["plain-cache", "int8-cache"])
def test_ranged_read_matches_full_read(monkeypatch, quant, local, soft):
    """(a) Same logits (float32) and the same greedy tokens as the read of
    the whole cache, while the frontier crosses two bucket edges."""
    cfg, model, params, ids, mask = _tiny(quant, local, soft)
    assert len(kv_read_ranges(P + N + cfg.n_soft_tokens, 0)) == 3
    tokens, logits = _greedy(model, params, ids, mask)
    monkeypatch.setattr(lm, "ranged_read", lambda *a, **k: None)  # today's read
    tokens_full, logits_full = _greedy(model, params, ids, mask)
    np.testing.assert_array_equal(tokens, tokens_full)
    np.testing.assert_allclose(logits, logits_full, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("window", [0, 1, 100, 256, 5000])
@pytest.mark.parametrize("cache_len", [1, 100, 128, 129, 320, 512, 1024, 1500, 2048, 4100])
def test_ranges_hold_every_admitted_key(cache_len, window):
    """(b) Pure function: at every cache_index of a branch, every key the
    bias admits lies inside the branch's [lo, hi)."""
    bucket = kv_read_bucket(cache_len)
    ranges = kv_read_ranges(cache_len, window)
    assert bucket % KV_READ_BUCKET == 0 and 1 <= len(ranges) <= KV_READ_MAX_BRANCHES
    assert len(ranges) == -(-cache_len // bucket)
    if cache_len <= KV_READ_BUCKET:
        assert ranges == ((0, cache_len),)  # one branch: the read of today
    index = np.arange(cache_len)
    lo, hi = np.array(ranges)[np.minimum(index // bucket, len(ranges) - 1)].T
    first_admitted = np.maximum(index - window + 1, 0) if window > 0 else np.zeros_like(index)
    assert (lo <= first_admitted).all() and (index < hi).all() and (hi <= cache_len).all()
    assert (lo % bucket == 0).all()
    # and against the bias itself, at the branches' first and last frontiers
    for c in {0, cache_len - 1, *(r[1] - 1 for r in ranges), *(k * bucket for k in range(len(ranges)))}:
        row = np.asarray(make_attn_bias(jnp.ones((1, cache_len), jnp.int32), 1, jnp.int32(c), window=window))[0, 0, 0]
        admitted = np.flatnonzero(row == 0.0)
        k = min(c // bucket, len(ranges) - 1)
        assert ranges[k][0] <= admitted.min() and admitted.max() < ranges[k][1], (c, ranges[k])


def _eqns(jaxpr):
    """Every equation, through every sub-jaxpr."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _count(jaxpr, name):
    """Equations of primitive `name`, through every sub-jaxpr."""
    return sum(eqn.primitive.name == name for eqn in _eqns(jaxpr))


@pytest.mark.parametrize("path", ["vector-index", "spec-verify", "paged", "paged-scalar-index", "prefill"])
@pytest.mark.parametrize("quant", [False, True], ids=["plain-cache", "int8-cache"])
def test_engine_and_paged_steps_keep_the_full_read(monkeypatch, quant, path):
    """(c) A per-row write offset (slot decode, spec verify), a block table
    and a multi-token prefill lower to the jaxpr of the full read: no
    conditional, and the same text as with the ranged read switched off."""
    cfg, model, params, _, _ = _tiny(quant, local=True, soft=False)
    T = 320
    vec = jnp.array([5, 290], jnp.int32)
    q_len = {"spec-verify": 4, "prefill": 4}.get(path, 1)
    index = {"paged-scalar-index": jnp.int32(7), "prefill": 0}.get(path, vec)
    if path.startswith("paged"):
        cache, tables, kv = init_paged_cache(cfg, 20, 32), jnp.arange(20, dtype=jnp.int32).reshape(B, 10), 320
        index = index if path == "paged-scalar-index" else vec % 300
    else:
        cache, tables, kv = init_cache(cfg, B, T), None, T
    assert ranged_read(kv, q_len, index, WINDOW) is None or path == "paged-scalar-index"

    def step(p):
        return model.apply(
            p, input_ids=jnp.ones((B, q_len), jnp.int32), attention_mask=jnp.ones((B, q_len), jnp.int32),
            cache=cache, cache_index=index, cache_mask=jnp.ones((B, kv), jnp.int32), block_tables=tables,
        )["logits"]

    text = str(jax.make_jaxpr(step)(params))
    assert _count(jax.make_jaxpr(step)(params).jaxpr, "cond") == 0
    monkeypatch.setattr(lm, "ranged_read", lambda *a, **k: None)
    assert text == str(jax.make_jaxpr(lambda p: step(p))(params))


def test_generate_is_one_while_with_one_switch_a_layer(monkeypatch):
    """(d) The decode loop stays ONE `while` (decode_ms_per_step reads the
    largest `while` of the program): the switch wraps the read, per layer,
    inside the body; nothing is cut into segments."""
    cfg, model, params, ids, mask = _tiny(quant=True, local=True, soft=False, n_layer=4)
    gcfg = GenerateConfig(max_new_tokens=N, do_sample=True, eos_token_id=None)
    fn = lambda p, i, m: generate(p, i, m, jax.random.PRNGKey(2), model=model, gcfg=gcfg)
    jaxpr = jax.make_jaxpr(fn)(params, ids, mask).jaxpr
    whiles = [e for e in jaxpr.eqns if e.primitive.name == "while"]
    assert len(whiles) == 1 and _count(jaxpr, "while") == 1
    assert _count(jaxpr, "cond") == _count(whiles[0].params["body_jaxpr"].jaxpr, "cond") == cfg.n_layer
    # no branch returns a cache: every conditional yields the [b, 1, h, d] read
    for eqn in whiles[0].params["body_jaxpr"].jaxpr.eqns:
        if eqn.primitive.name == "cond":
            assert [v.aval.shape for v in eqn.outvars] == [(B, 1, cfg.n_head, cfg.head_dim)]
    # the lowered program has as many loops as with the full read (the other
    # two are the CPU lowering of the sampler's random bits, outside the scope)
    loops = jax.jit(fn).lower(params, ids, mask).as_text().count("stablehlo.while")
    monkeypatch.setattr(lm, "ranged_read", lambda *a, **k: None)
    assert loops == jax.jit(lambda p, i, m: fn(p, i, m)).lower(params, ids, mask).as_text().count("stablehlo.while")


@pytest.mark.parametrize("soft", [0, N_SOFT], ids=["nosoft", "soft"])
def test_kv_read_share_by_hand(soft):
    """(e) The counter's two sums for the shapes of (a), against the share
    worked out by hand from the three branches."""
    cache_len, first = P + N + soft, P + soft
    # steps whose write slot lies in [120,128) [128,256) [256,320) (soft: +3 on both sides)
    in_branch = [128 - first, 128, N - 128 - (128 - first)]
    global_keys = in_branch[0] * 128 + in_branch[1] * 256 + in_branch[2] * cache_len
    local_keys = in_branch[0] * 128 + in_branch[1] * 256 + in_branch[2] * (cache_len - 128)
    assert kv_keys_read(cache_len, first, N, [0]) == (global_keys, cache_len * N)
    assert kv_keys_read(cache_len, first, N, [0, WINDOW]) == (global_keys + local_keys, 2 * cache_len * N)
    if not soft:
        assert global_keys / (cache_len * N) == pytest.approx(0.848)
        assert local_keys / (cache_len * N) == pytest.approx(0.72)
    # a cache of one bucket reads all of it; an early exit counts only its steps
    assert kv_keys_read(100, 40, 60, [0, 30]) == (2 * 100 * 60, 2 * 100 * 60)
    assert kv_keys_read(cache_len, first, 8 - soft, [0]) == ((8 - soft) * 128, (8 - soft) * cache_len)
    # the benchmark's rollout shapes (PERF.md, PR 24)
    # (cache 1024 has buckets of 256: 128 steps read 256 keys, then 256 steps each 512, 768, 1024)
    assert kv_keys_read(1024, 128, 896, [0] * 8)[0] / (1024 * 896 * 8) == pytest.approx(19 / 28)
    assert kv_keys_read(512, 256, 256, [0, 256] * 12)[0] / (512 * 256 * 24) == pytest.approx(0.8125)
    assert kv_keys_read(1024, 768, 256, [0] * 8)[0] / (1024 * 256 * 8) == 1.0  # the frontier starts in the last bucket


def test_a_partitioned_mesh_keeps_the_full_read():
    """The read's layout request is a custom call that GSPMD would answer by
    replicating the cache in every branch (a device-free v5e compile over
    fsdp x tp shows 128 all-gathers), so on a mesh of more than one device the
    generate program keeps the read of the whole cache."""
    from trlx_tpu.parallel import make_mesh
    from trlx_tpu.parallel.mesh import peek_mesh, set_mesh

    cfg, model, params, ids, mask = _tiny(quant=True, local=True, soft=False)
    gcfg = GenerateConfig(max_new_tokens=N, do_sample=False, eos_token_id=None)
    fn = lambda p, i, m: generate(p, i, m, jax.random.PRNGKey(2), model=model, gcfg=gcfg)
    assert _count(jax.make_jaxpr(fn)(params, ids, mask).jaxpr, "cond") == cfg.n_layer
    prior = peek_mesh()
    set_mesh(make_mesh((1, 2, 4, 1)))
    try:
        assert _count(jax.make_jaxpr(lambda p, i, m: fn(p, i, m))(params, ids, mask).jaxpr, "cond") == 0
        assert kv_keys_read(P + N, P, N, [0, WINDOW]) == (2 * (P + N) * N,) * 2  # and the counter says so
    finally:
        set_mesh(prior)


# ----- one decode step against a reference of its own -------------------
# Cache 300 (128 does not divide it): branches [0,128) [0,256) [0,300), and
# with the window of 100 the last one starts at 128. Rows are left-padded by
# different amounts, so their valid lengths are ragged at every frontier.
STEP_T, STEP_BLOCK, STEP_PAD = 300, 20, (0, 17, 3)
STEP_FRONTIERS = {"first-bucket": 70, "last-bucket": 290}


def _dense_attention_f32(q, k, v, admitted, scale):
    """Softmax attention in numpy float32 over the admitted keys only:
    q [b, h, d], k/v [b, T, h, d], admitted [b, T] bool -> [b, h, d]."""
    out = np.zeros_like(q)
    for b in range(q.shape[0]):
        keys = np.flatnonzero(admitted[b])
        s = np.einsum("hd,khd->hk", q[b], k[b, keys]) * np.float32(scale)
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        out[b] = np.einsum("hk,khd->hd", p / p.sum(axis=-1, keepdims=True), v[b, keys])
    return out


@pytest.mark.parametrize("window", [0, WINDOW], ids=["global", "local"])
@pytest.mark.parametrize("addressing", ["one-traced-index", "per-row-index", "block-table"])
@pytest.mark.parametrize("quant", [False, True], ids=["plain-cache", "int8-cache"])
def test_decode_step_read_matches_dense_float32_attention(quant, addressing, window):
    """One `Attention` decode step, write then read, by each way a step
    addresses its cache (the generate loop's one traced index, which takes
    the ranged read; the engine's per-row index; the engine's block table,
    with a dead row parked on the trash block), against a float32 softmax
    attention in numpy over the dequantized keys the bias admits. The frontier
    sits in the first and in the last bucket of the read."""
    rng = np.random.default_rng(3)
    cfg = LMConfig(vocab_size=8, n_layer=1, n_head=2, d_model=32, pos_type="learned", fused_qkv=False,
                   qkv_bias=False, out_bias=True, kv_cache_quant=quant, dtype="float32")
    Bs, h, d, T = len(STEP_PAD), cfg.n_head, cfg.head_dim, STEP_T
    attn = lm.Attention(cfg)
    x = jnp.asarray(rng.normal(size=(Bs, 1, cfg.d_model)), jnp.float32)
    f32 = lambda a: np.asarray(a, np.float32)

    # what the cache holds before the step, by virtual slot: [Bs, T, ...]
    old = [rng.normal(size=(Bs, T, h, d)).astype(np.float32) for _ in range(2)]
    if quant:
        old = [np.asarray(a) for kv in old for a in lm.quantize_kv(jnp.asarray(kv))]
        old = [old[0], old[2], old[1], old[3]]  # (k, v, k_scale, v_scale)
    paged = addressing == "block-table"
    if paged:
        bps = T // STEP_BLOCK
        tables = 1 + rng.permutation(Bs * bps).reshape(Bs, bps)  # block 0 is the trash block
        tables[2] = 0  # a freed row, parked on it
        place = lambda a: np.zeros((1 + Bs * bps, STEP_BLOCK) + a.shape[2:], a.dtype)
        cache = []
        for a in old:
            pool = place(a)
            pool[tables[:2].reshape(-1)] = a[:2].reshape((2 * bps, STEP_BLOCK) + a.shape[2:])
            cache.append(pool)
        virtual = lambda pool: f32(pool)[tables].reshape((Bs, T) + pool.shape[2:])
    else:
        tables, cache, virtual = None, old, f32
    cache = tuple(jnp.asarray(a) for a in cache)
    params = attn.init(jax.random.PRNGKey(0), x, jnp.zeros((Bs, 1, 1, 1)), None)

    @jax.jit
    def step(index, cache_mask):
        bias = make_attn_bias(cache_mask, 1, index, window=window)
        return attn.apply(params, x, bias, None, cache=cache, cache_index=index, window=window,
                          block_tables=None if tables is None else jnp.asarray(tables, jnp.int32))

    w = {name: f32(p["kernel"]) for name, p in params["params"].items()}
    q_ref, k_new, v_new = (f32(x)[:, 0] @ w[n] for n in ("q_proj", "k_proj", "v_proj"))
    for frontier in STEP_FRONTIERS.values():
        # per-row frontiers differ (slot decode); the generate loop has one for all rows
        at = np.full(Bs, frontier) if addressing == "one-traced-index" else frontier - np.array([0, 9, 40])
        slots = np.arange(T)[None, :]
        cache_mask = (slots >= np.array(STEP_PAD)[:, None]) & (slots <= at[:, None])
        live = np.ones(Bs, bool)
        if paged:
            cache_mask[2], live[2] = False, False  # the freed row holds nothing
        index = jnp.int32(frontier) if addressing == "one-traced-index" else jnp.asarray(at, jnp.int32)
        assert (ranged_read(T, 1, index, window) is not None) == (addressing == "one-traced-index")
        out, new_cache = step(index, jnp.asarray(cache_mask, jnp.int32))

        # the write: the step's key and value at each live row's frontier, nothing else moved
        got = [virtual(a) for a in new_cache]
        k_all, v_all = (got[0], got[1]) if not quant else (got[0] * got[2][..., None], got[1] * got[3][..., None])
        before = [virtual(a) for a in cache]
        for b in np.flatnonzero(live):
            tol = dict(rtol=0, atol=np.abs(k_new).max() / 127) if quant else dict(rtol=1e-6, atol=1e-6)
            np.testing.assert_allclose(k_all[b, at[b]], k_new[b].reshape(h, d), **tol)
            np.testing.assert_allclose(v_all[b, at[b]], v_new[b].reshape(h, d), **tol)
            untouched = np.arange(T) != at[b]
            for a, a0 in zip(got, before):
                np.testing.assert_array_equal(a[b, untouched], a0[b, untouched])

        # the read: softmax over the keys the mask, causality and the window admit
        admitted = cache_mask & (slots > at[:, None] - window if window else True)
        assert admitted[live].any(axis=1).all() and len(set(cache_mask[live].sum(axis=1))) > 1  # ragged lengths
        ref = _dense_attention_f32(q_ref.reshape(Bs, h, d)[live], k_all[live], v_all[live], admitted[live],
                                   1.0 / np.sqrt(d))
        ref = ref.reshape(-1, cfg.d_model) @ w["c_proj"] + f32(params["params"]["c_proj"]["bias"])
        np.testing.assert_allclose(f32(out)[live, 0], ref, rtol=2e-5, atol=2e-5)
        assert np.isfinite(f32(out)).all()


# ----- the read of an int8 cache: a key's scale once a key ----------------


def _dequantizing_attend_range(q, cache, attn_bias, lo, hi, scale, dtype):
    """`attend_range` as it stood before `attend_quantized`: an int8 cache is
    dequantized element by element, in `dtype`, ahead of both contractions.
    Kept here as the comparison (and as the form an unquantized read must
    still trace to), nowhere in the package."""
    cut = lambda a: jax.lax.slice_in_dim(a, lo, hi, axis=1)
    k, v = cut(cache[0]), cut(cache[1])
    if len(cache) == 4:
        k = k.astype(dtype) * cut(cache[2])[..., None].astype(dtype)
        v = v.astype(dtype) * cut(cache[3])[..., None].astype(dtype)
    return attend(q, k, v, jax.lax.slice_in_dim(attn_bias, lo, hi, axis=3), scale, dtype)


def _attention_f64(q, k, v, bias, scale):
    """Softmax attention in numpy float64; q [b, q, h, d], k/v [b, kv, h_kv, d],
    K/V head j serving query heads [j * g, (j + 1) * g); bias [b, 1, q, kv]."""
    g = q.shape[2] // k.shape[2]
    k, v = np.repeat(k, g, axis=2), np.repeat(v, g, axis=2)
    s = np.einsum("bqhd,bkhd->bhqk", q, k) * scale + bias
    p = np.exp(s - s.max(axis=-1, keepdims=True))
    return np.einsum("bhqk,bkhd->bqhd", p / p.sum(axis=-1, keepdims=True), v)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("span", [(0, 160), (32, 128)], ids=["whole", "lo-above-0"])
@pytest.mark.parametrize("heads", [(4, 4), (8, 2)], ids=["plain", "grouped"])
@pytest.mark.parametrize("q_len", [1, 8])
def test_quantized_read_matches_float64_attention(q_len, heads, span, dtype):
    """`attend_quantized` (through `attend_range`, so a slice too) against a
    float64 softmax attention over k_i8 x k_scale, v_i8 x v_scale. In float32
    it agrees to float32 rounding; in bf16 its error is no larger than that
    of the read that dequantized K and V in bf16 first, on the same inputs."""
    (h, h_kv), (lo, hi), b, T, d = heads, span, 3, 160, 32
    rng = np.random.default_rng(11)
    dtype = jnp.dtype(dtype)
    q = jnp.asarray(rng.normal(size=(b, q_len, h, d)), dtype)
    (k8, ks), (v8, vs) = (lm.quantize_kv(jnp.asarray(rng.normal(size=(b, T, h_kv, d)), jnp.float32)) for _ in range(2))
    # ragged validity (left padding), and causal among the q_len queries at the end of the range
    valid = np.arange(T)[None, None, None, :] >= np.array([0, 7, 40])[:, None, None, None]
    causal = np.arange(T)[None, None, None, :] <= hi - q_len + np.arange(q_len)[None, None, :, None]
    bias = np.where(valid & causal, 0.0, -1e9).astype(np.float32)
    scale = 1.0 / np.sqrt(d)

    f64 = lambda a: np.asarray(a.astype(jnp.float32), np.float64)
    want = _attention_f64(f64(q), (f64(k8) * f64(ks)[..., None])[:, lo:hi], (f64(v8) * f64(vs)[..., None])[:, lo:hi],
                          bias[..., lo:hi].astype(np.float64), scale)
    cache = (k8, v8, ks, vs)
    err = lambda read: float(np.sqrt(np.mean((f64(read(q, cache, jnp.asarray(bias), lo, hi, scale, dtype)) - want) ** 2)))
    new, old = err(attend_range), err(_dequantizing_attend_range)
    rms = float(np.sqrt(np.mean(want**2)))
    if dtype == jnp.float32:
        assert new <= 2e-6 * rms
    else:
        assert new <= old, (new, old)
        assert new <= 2.0**-8 * rms  # the output's own rounding to bf16, and the weights': nothing else


def test_grouped_quantized_read_never_repeats_keys_or_values():
    """h_kv < h: K and V reach their contractions at their own h_kv heads; no
    value of the program is as large as a K or V repeated over the group."""
    b, q_len, h, h_kv, T, d = 2, 1, 8, 2, 64, 16
    S = jax.ShapeDtypeStruct
    args = (S((b, q_len, h, d), jnp.bfloat16), S((b, T, h_kv, d), jnp.int8), S((b, T, h_kv, d), jnp.int8),
            S((b, T, h_kv), jnp.float32), S((b, T, h_kv), jnp.float32), S((b, 1, q_len, T), jnp.float32))
    jaxpr = jax.make_jaxpr(lambda *a: attend_quantized(*a, 0.25, jnp.bfloat16))(*args).jaxpr
    dots = [e for e in _eqns(jaxpr) if e.primitive.name == "dot_general"]
    assert len(dots) == 2 and all((b, T, h_kv, d) in [v.aval.shape for v in e.invars] for e in dots)
    assert max(int(np.prod(v.aval.shape)) for e in _eqns(jaxpr) for v in e.outvars) <= b * T * h_kv * d


# What may stand between an int8 cache leaf and its contraction: the write,
# a slice, the layout request, the paged pool's gather, a convert.
_CARRIES_THE_CACHE = {"dynamic_update_slice", "slice", "dynamic_slice", "layout_constraint", "gather", "reshape",
                      "convert_element_type", "scatter", "squeeze", "broadcast_in_dim"}


def _int8_cache_consumers(jaxpr, tainted):
    """Primitives that consume a value derived from an int8 cache leaf
    (`tainted`: a set of the jaxpr's variables) by anything in
    `_CARRIES_THE_CACHE`; returns (consumers, tainted outvars)."""
    consumers = set()
    tainted = set(tainted)
    for eqn in jaxpr.eqns:
        hit = [i for i, v in enumerate(eqn.invars) if isinstance(v, jax.extend.core.Var) and v in tainted]
        if not hit:
            continue
        subs = list(jax.core.jaxprs_in_params(eqn.params))
        if subs:
            # a conditional's branches take the operands after the index; a jitted call all of them
            offset = len(eqn.invars) - len(subs[0].invars)
            for sub in subs:
                inner, out = _int8_cache_consumers(sub, {sub.invars[i - offset] for i in hit if i >= offset})
                consumers |= inner
                tainted |= {eqn.outvars[j] for j, v in enumerate(sub.outvars) if v in out}
        elif eqn.primitive.name in _CARRIES_THE_CACHE:
            tainted |= set(eqn.outvars)
        else:
            consumers.add(eqn.primitive.name)
    return consumers, tainted


@pytest.mark.parametrize("addressing", ["one-traced-index", "per-row-index", "block-table", "prefill"])
def test_int8_cache_reaches_its_contractions_through_converts_only(addressing):
    """A decode step (ranged: one traced index; whole: a per-row index, a block
    table) and a multi-token read over an int8 cache: the int8 leaves reach a
    `dot_general` through the write, slices, gathers and converts only, and no
    `mul` has an operand of the extent of the cache or of a range of it."""
    cfg, model, params, _, _ = _tiny(quant=True, local=True, soft=False)
    T, q_len = 320, 4 if addressing == "prefill" else 1
    if addressing == "block-table":
        cache, tables = init_paged_cache(cfg, 20, 32), jnp.arange(20, dtype=jnp.int32).reshape(B, 10)
    else:
        cache, tables = init_cache(cfg, B, T), None
    index = {"one-traced-index": jnp.int32(290), "prefill": 0}.get(addressing, jnp.array([5, 290], jnp.int32))

    def step(p, cache, index):
        return model.apply(
            p, input_ids=jnp.ones((B, q_len), jnp.int32), attention_mask=jnp.ones((B, q_len), jnp.int32),
            cache=cache, cache_index=index, cache_mask=jnp.ones((B, T), jnp.int32), block_tables=tables)["logits"]

    jaxpr = jax.make_jaxpr(step)(params, cache, index).jaxpr
    assert _count(jaxpr, "cond") == (cfg.n_layer if addressing == "one-traced-index" else 0)
    int8_leaves = {v for v in jaxpr.invars if v.aval.dtype == jnp.int8}
    assert len(int8_leaves) == 2 * cfg.n_layer
    consumers, _ = _int8_cache_consumers(jaxpr, int8_leaves)
    assert consumers == {"dot_general"}
    h, d = cfg.kv_heads, cfg.head_dim
    extents = {(B, hi - lo, h, d) for w in (0, WINDOW) for lo, hi in kv_read_ranges(T, w)} | {tuple(cache[0][0].shape)}
    for eqn in _eqns(jaxpr):
        if eqn.primitive.name == "mul":
            assert not extents & {tuple(v.aval.shape) for v in eqn.invars}, eqn


_PLAIN = dict(vocab_size=64, n_layer=2, n_head=4, d_model=32, max_position=512, pos_type="rotary", rotary_dim=8,
              dtype="float32")
_FAMILIES = {
    # the GPT block, learned positions, alternating windows in a full-span cache
    "plain": dict(_PLAIN, pos_type="learned", attention_layers=("global", "local"), window_size=WINDOW),
    # grouped keys, qk-norm, a ring cache in the window layer (K-EXAONE's kinds)
    "grouped-ring": dict(_PLAIN, n_kv_head=2, head_width=16, fused_qkv=False, qk_norm=True, norm="rmsnorm", mlp="gated",
                         rotary_layers="local", attention_layers=("local", "global"), window_size=8, window_cache="ring"),
    # a state-space layer beside a grouped attention layer with no position signal (granite's kinds)
    "state-space": dict(_PLAIN, n_kv_head=2, head_width=16, fused_qkv=False, pos_type="none", norm="rmsnorm",
                        mlp="gated", tie_word_embeddings=True, mixer_layers=("mamba", "attention"), ssm_heads=4,
                        ssm_head_dim=16, ssm_state=16, ssm_conv=4, ssm_chunk=8),
    # latent attention (Kimi's kind): its read is `attend_latent_range`
    "latent": dict(_PLAIN, attention="mla", norm="rmsnorm", mlp="gated", tie_word_embeddings=False, q_lora_rank=24,
                   kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8),
}


@pytest.mark.parametrize("q_len", [1, 4], ids=["decode-step", "prefill"])
@pytest.mark.parametrize("family", list(_FAMILIES))
def test_unquantized_reads_trace_to_the_dequantizing_form(monkeypatch, family, q_len):
    """A cache that is not int8 (`len(cache) == 2`: the three families whose
    cells set `kv_cache_quant` off, and the GPT block without it) reads as it
    did: the jaxpr text of a ranged decode step and of a multi-token read is
    the one the old `attend_range` and a plain `attend` over the view give."""
    cfg = LMConfig.from_dict(_FAMILIES[family])
    model, T = TransformerLM(cfg), 320
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.ones((B, 4), jnp.int32), jnp.ones((B, 4), jnp.int32)))
    cache = jax.eval_shape(lambda: init_cache(cfg, B, T))

    def step(p, cache, index):
        return model.apply(
            p, input_ids=jnp.ones((B, q_len), jnp.int32), attention_mask=jnp.ones((B, q_len), jnp.int32),
            cache=cache, cache_index=index if q_len == 1 else 0, cache_mask=jnp.ones((B, T), jnp.int32))["logits"]

    index = jax.ShapeDtypeStruct((), jnp.int32)
    text = str(jax.make_jaxpr(step)(params, cache, index))
    assert ("cond[" in text) == (q_len == 1)
    monkeypatch.setattr(lm, "attend_cache", lambda q, kv, *rest: attend(q, *kv, *rest))
    monkeypatch.setattr(lm, "ranged_read", lambda *a, **k: ranged_read(*a, **{"attend_range": _dequantizing_attend_range, **k}))
    assert text == str(jax.make_jaxpr(lambda *a: step(*a))(params, cache, index))


def test_a_partitioned_mesh_keeps_the_dequantizing_read():
    """On a mesh of more than one device a read of an int8 cache traces to the
    text it had before `attend_quantized` (restated, the four-chip cell's
    generate program reserved more memory than its bound allows), and the
    counter `rollout/kv_scale_mults_per_key` says which read ran: two for a
    key read and query head served, or 2 x head_dim (512 at GPT-J's heads)."""
    from trlx_tpu.parallel import make_mesh
    from trlx_tpu.parallel.mesh import peek_mesh, set_mesh

    assert kv_scale_mults_per_key(16, 16, 256) == 2.0 and kv_scale_mults_per_key(64, 8, 128) == 16.0
    S = jax.ShapeDtypeStruct
    b, T, h, d = 2, 64, 4, 16
    for q_len in (1, 8):
        args = (S((b, q_len, h, d), jnp.bfloat16), (S((b, T, h, d), jnp.int8), S((b, T, h, d), jnp.int8),
                S((b, T, h), jnp.float32), S((b, T, h), jnp.float32)), S((b, 1, q_len, T), jnp.float32))
        bf16 = jnp.bfloat16
        new = lambda q, cache, bias: kv_read.attend_cache(q, cache, bias, 0.25, bf16)
        old = lambda q, c, bias: attend(q, c[0].astype(bf16) * c[2][..., None].astype(bf16),
                                        c[1].astype(bf16) * c[3][..., None].astype(bf16), bias, 0.25, bf16)
        assert str(jax.make_jaxpr(new)(*args)) != str(jax.make_jaxpr(old)(*args))
        prior = peek_mesh()
        set_mesh(make_mesh((1, 2, 4, 1)))
        try:
            assert str(jax.make_jaxpr(lambda *a: new(*a))(*args)) == str(jax.make_jaxpr(lambda *a: old(*a))(*args))
            assert kv_scale_mults_per_key(16, 16, 256) == 512.0
        finally:
            set_mesh(prior)
