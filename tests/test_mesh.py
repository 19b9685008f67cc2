"""Mesh + sharding semantics on 8 virtual CPU devices — a capability the
reference cannot test at all (its distributed path is exercised only by
manual `accelerate launch`, SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from trlx_tpu.models import LMConfig, LMWithValueHead
from trlx_tpu.parallel import make_mesh, match_partition_rules, lm_partition_rules, shard_pytree, batch_sharding
from trlx_tpu.parallel.mesh import resolve_mesh_shape

slow = pytest.mark.slow  # excluded from `make test-fast` (see conftest)


@slow
def test_device_count():
    assert jax.device_count() == 8


@slow
def test_resolve_mesh_shape():
    assert resolve_mesh_shape((-1, 1, 1, 1), 8) == (8, 1, 1, 1)
    assert resolve_mesh_shape((2, -1, 2, 1), 8) == (2, 2, 2, 1)
    with pytest.raises(ValueError):
        resolve_mesh_shape((3, 1, 1, 1), 8)
    with pytest.raises(ValueError):
        resolve_mesh_shape((-1, -1, 1, 1), 8)


@slow
def test_partition_rules_megatron_layout():
    cfg = LMConfig(vocab_size=32, n_layer=2, n_head=4, d_model=64, dtype="float32")
    model = LMWithValueHead(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32), jnp.ones((1, 4), jnp.int32))["params"]
    specs = match_partition_rules(lm_partition_rules(), params)
    t = specs["transformer"]
    assert t["h_0"]["attn"]["c_qkv"]["kernel"] == P("fsdp", "tp")
    assert t["h_0"]["attn"]["c_proj"]["kernel"] == P("tp", "fsdp")
    assert t["h_0"]["mlp"]["c_fc"]["kernel"] == P("fsdp", "tp")
    assert t["h_0"]["mlp"]["c_proj"]["kernel"] == P("tp", "fsdp")
    assert t["wte"]["embedding"] == P("tp", "fsdp")
    assert t["ln_f"]["scale"] == P()


@slow
def test_sharded_train_step_matches_single_device():
    """A jitted loss+grad step over a dp×fsdp×tp mesh must agree numerically
    with the unsharded computation (XLA collectives are semantically
    transparent)."""
    cfg = LMConfig(vocab_size=32, n_layer=2, n_head=4, d_model=64, dtype="float32")
    model = LMWithValueHead(cfg)
    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (8, 6), 0, 32)
    mask = jnp.ones((8, 6), jnp.int32)
    params = model.init(rng, ids, mask)["params"]

    def loss_fn(p, i, m):
        out = model.apply({"params": p}, i, m)
        return jnp.mean(out["logits"].astype(jnp.float32) ** 2) + jnp.mean(out["values"] ** 2)

    ref_loss, ref_grads = jax.value_and_grad(loss_fn)(params, ids, mask)

    mesh = make_mesh((2, 2, 2, 1))
    sharded_params, _ = shard_pytree(params, mesh)
    sharded_ids = jax.device_put(ids, batch_sharding(mesh, extra_dims=1))
    sharded_mask = jax.device_put(mask, batch_sharding(mesh, extra_dims=1))
    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(sharded_params, sharded_ids, sharded_mask)

    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    flat_ref = jax.tree_util.tree_leaves(ref_grads)
    flat_sh = jax.tree_util.tree_leaves(jax.device_get(grads))
    for a, b in zip(flat_ref, flat_sh):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)


@slow
def test_optimizer_state_shards_like_params():
    """ZeRO equivalence: Adam moments follow the param partition specs."""
    cfg = LMConfig(vocab_size=32, n_layer=1, n_head=2, d_model=32, dtype="float32")
    model = LMWithValueHead(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32), jnp.ones((1, 4), jnp.int32))["params"]
    opt = optax.adamw(1e-4)
    opt_state = opt.init(params)
    mesh = make_mesh((1, 2, 4, 1))
    sharded, shardings = shard_pytree(opt_state, mesh)
    adam_state = sharded[0]  # ScaleByAdamState
    mu_qkv = adam_state.mu["transformer"]["h_0"]["attn"]["c_qkv"]["kernel"]
    assert mu_qkv.sharding.spec == P("fsdp", "tp")


@slow
def test_sharded_generation_matches_single_device():
    """Greedy decode with params sharded over (fsdp, tp) and the KV cache
    pinned to the mesh must emit the same tokens as unsharded decode."""
    from trlx_tpu.models import LMWithValueHead
    from trlx_tpu.ops.generate import make_generate_fn
    from trlx_tpu.ops.sampling import GenerateConfig
    from trlx_tpu.parallel.mesh import peek_mesh, set_mesh
    from trlx_tpu.parallel.sharding import batch_sharding

    cfg = LMConfig(vocab_size=32, n_layer=2, n_head=4, d_model=64, max_position=64, dtype="float32")
    model = LMWithValueHead(cfg)
    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (8, 6), 2, 32)
    mask = jnp.ones((8, 6), jnp.int32)
    params = model.init(rng, ids, mask)["params"]
    gcfg = GenerateConfig(max_new_tokens=5, do_sample=False, eos_token_id=None, pad_token_id=0)
    gen = make_generate_fn(model, gcfg)

    ref_toks, _ = gen({"params": params}, ids, mask, jax.random.PRNGKey(1))

    prior = peek_mesh()
    mesh = make_mesh((1, 2, 4, 1))
    set_mesh(mesh)
    try:
        # A generate fn is bound to the mesh it was built under: calling the
        # old one after set_mesh must fail LOUDLY (stale KV-cache placement),
        # and a freshly built one must work.
        with np.testing.assert_raises(RuntimeError):
            gen({"params": params}, ids, mask, jax.random.PRNGKey(1))
        gen_sharded = make_generate_fn(model, gcfg)
        sharded_params, _ = shard_pytree(params, mesh)
        s_ids = jax.device_put(ids, batch_sharding(mesh, extra_dims=1))
        s_mask = jax.device_put(mask, batch_sharding(mesh, extra_dims=1))
        toks, _ = gen_sharded({"params": sharded_params}, s_ids, s_mask, jax.random.PRNGKey(1))
    finally:
        set_mesh(prior)  # restore the exact prior global (possibly None)
    np.testing.assert_array_equal(np.asarray(ref_toks), np.asarray(toks))


@slow
def test_dryrun_all_four_axes_16_devices():
    """All four mesh axes >1 simultaneously ({dp:2, fsdp:2, tp:2, sp:2} on 16
    virtual devices): the full PPO + on-device-RM + fused + ILQL dry run.
    Subprocess because this pytest process is pinned to 8 virtual devices
    (conftest) and the device count is fixed at backend init."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = " ".join(
        f
        for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    )
    env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=16").strip()
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "__graft_entry__.py"), "dryrun", "16"],
        env=env,
        cwd=repo,
        capture_output=True,
        text=True,
        timeout=540,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "'dp': 2, 'fsdp': 2, 'tp': 2, 'sp': 2" in proc.stdout, proc.stdout


# ----- a tiny PPO trainer over fsdp=4 against the same trainer on one device
# GPT-J-shaped (rotary, parallel residual, separate q/k/v, untied head with a
# bias), sized so that on one TPU chip every route that stands back on a mesh
# would be taken: 256-token sequences (flash in the train step), d_model 128
# and a 512-entry vocabulary (the fused log-prob head), a cache of two
# buckets (the ranged read).
FSDP4_P, FSDP4_R, FSDP4_B = 128, 128, 4


def _fsdp4_config(mesh_shape):
    from trlx_tpu.trainer.api import default_config

    config = default_config("ppo")
    config.model.model_path = config.model.tokenizer_path = ""
    config.model.num_layers_unfrozen = 1
    config.model.dtype = "float32"
    config.model.model_arch = {
        "vocab_size": 512, "n_layer": 2, "n_head": 2, "d_model": 128, "max_position": 256, "eos_token_id": 0,
        "pos_type": "rotary", "rotary_dim": 16, "parallel_residual": True, "use_parallel_ln": False,
        "fused_qkv": False, "qkv_bias": False, "out_bias": False, "tie_word_embeddings": False,
        "activation": "gelu_new", "extra": {"lm_head_bias": True},
    }
    config.train.mesh = mesh_shape
    config.train.batch_size = FSDP4_B
    config.train.seq_length = FSDP4_P + FSDP4_R
    config.train.checkpoint_interval = 0
    config.method.gen_kwargs = {"prompt_length": FSDP4_P, "max_new_tokens": FSDP4_R, "min_new_tokens": FSDP4_R,
                                "do_sample": False}
    config.method.chunk_size = config.method.num_rollouts = FSDP4_B
    return config


def _count_primitive(jaxpr, name):
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count_primitive(sub, name)
    return n


@pytest.fixture(scope="module")
def fsdp4_and_one_device(tmp_path_factory):
    """What each trainer computes, and the primitives its programs trace to
    where the kernel gates see a TPU backend: {"fsdp4": {...}, "one": {...}}."""
    from trlx_tpu.data import PPORLBatch
    from trlx_tpu.ops import fused_logprob, tiling
    from trlx_tpu.ops.generate import make_generate_fn
    from trlx_tpu.parallel.mesh import set_mesh
    from trlx_tpu.trainer.ppo import PPOTrainer

    rng = np.random.default_rng(0)
    P_, R, B = FSDP4_P, FSDP4_R, FSDP4_B
    ids = rng.integers(2, 512, size=(B, P_)).astype(np.int32)
    mask = (np.arange(P_)[None, :] >= np.array([0, 9, 0, 31])[:, None]).astype(np.int32)  # left-padded rows
    batch = PPORLBatch(
        query_tensors=ids * mask, query_mask=mask,
        response_tensors=rng.integers(2, 512, size=(B, R)).astype(np.int32), response_mask=np.ones((B, R), np.int32),
        logprobs=-rng.random((B, R)).astype(np.float32) * 3, values=rng.normal(size=(B, R)).astype(np.float32),
        rewards=rng.normal(size=(B, R)).astype(np.float32) * 0.1,
    )
    out = {}
    for name, mesh_shape in (("fsdp4", (1, 4, 1, 1)), ("one", (1, 1, 1, 1))):
        config = _fsdp4_config(mesh_shape)
        config.train.checkpoint_dir = str(tmp_path_factory.mktemp(name))
        trainer = PPOTrainer(config, mesh_devices=jax.devices()[: int(np.prod(mesh_shape))])
        got = out[name] = {}
        fed = trainer.put_batch({"i": ids * mask, "m": mask})
        forward = jax.jit(lambda p, i, m: trainer.model.apply({"params": p}, i, m)["logits"])
        got["logits"] = np.asarray(forward(trainer.state.params, fed["i"], fed["m"]))
        got["tokens"] = np.asarray(trainer.rollout_generate(ids * mask, mask)[0])
        device_batch = trainer.put_batch(batch)
        state, stats = trainer.train_step(trainer.state, device_batch)
        got["loss"], got["grad_norm"] = float(stats["loss"]), float(stats["grad_norm"])

        # The same programs, built anew and traced (not lowered) where the
        # gates see a TPU backend; the mesh is the trainer's.
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(jax, "default_backend", lambda: "tpu")
            patch.setattr(tiling, "require_lowering", lambda *a, **k: None)  # nothing here can lower for a TPU
            generate = make_generate_fn(trainer.model, trainer.gen_cfg, trainer._gen_processor)
            programs = {
                "generate": jax.make_jaxpr(generate)(
                    trainer._decode_variables(None), fed["i"], fed["m"], jax.random.PRNGKey(0)),
                "train_step": jax.make_jaxpr(trainer.build_train_step())(state, device_batch),
            }
        fused_logprob._PROBE_CACHE.clear()
        got["primitives"] = {
            prog: {prim: _count_primitive(jaxpr.jaxpr, prim) for prim in ("pallas_call", "cond")}
            for prog, jaxpr in programs.items()
        }
        got["n_layer"] = trainer.model.cfg.n_layer
        set_mesh(None)
    return out


@pytest.mark.parametrize("what", ["policy-logits", "first-train-step", "greedy-tokens", "routes-stand-back"])
def test_tiny_ppo_on_fsdp4_matches_one_device(fsdp4_and_one_device, what):
    """Mesh [1, 4, 1, 1] on four forced CPU devices computes what one device
    computes (the cell gptj6b-l28.ppo-768x256.fsdp4 in small), and its traced
    programs hold none of the routes that cannot be partitioned
    (`parallel.mesh.partitioned`): no Pallas call, no ranged-read switch."""
    mesh, one = fsdp4_and_one_device["fsdp4"], fsdp4_and_one_device["one"]
    if what == "policy-logits":
        np.testing.assert_allclose(mesh["logits"], one["logits"], rtol=1e-4, atol=1e-4)
    elif what == "first-train-step":
        assert mesh["loss"] == pytest.approx(one["loss"], rel=1e-4)
        assert mesh["grad_norm"] == pytest.approx(one["grad_norm"], rel=1e-3)
    elif what == "greedy-tokens":
        assert mesh["tokens"].shape == (FSDP4_B, FSDP4_P + FSDP4_R)
        np.testing.assert_array_equal(mesh["tokens"], one["tokens"])
    else:
        # one device, as a control: the ranged read once a layer in the decode
        # loop, flash and the fused head in the train step
        assert one["primitives"]["generate"]["cond"] == one["n_layer"]
        assert one["primitives"]["train_step"]["pallas_call"] > 0
        assert mesh["primitives"] == {"generate": {"pallas_call": 0, "cond": 0},
                                      "train_step": {"pallas_call": 0, "cond": 0}}
