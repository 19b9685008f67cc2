"""Mesh + sharding semantics on 8 virtual CPU devices — a capability the
reference cannot test at all (its distributed path is exercised only by
manual `accelerate launch`, SURVEY.md §4)."""

import functools
import re

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
@pytest.mark.parametrize("kind", ["gpt block", "grouped keys with qk-norm"])
def test_partition_rules_megatron_layout(kind):
    grouped = kind != "gpt block"
    extra = dict(n_kv_head=2, head_width=32, fused_qkv=False, qk_norm=True) if grouped else {}
    cfg = LMConfig(vocab_size=32, n_layer=2, n_head=4, d_model=64, dtype="float32", **extra)
    model = LMWithValueHead(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32), jnp.ones((1, 4), jnp.int32))["params"]
    specs = match_partition_rules(lm_partition_rules(), params)
    t = specs["transformer"]
    if grouped:
        # K and V at their 2 heads of 32 (64 columns where q_proj has 128): column-parallel like q_proj;
        # the two head norms' scales are head_width numbers for every head: whole
        attn = params["transformer"]["h_0"]["attn"]
        assert attn["q_proj"]["kernel"].shape == (64, 128) and attn["k_proj"]["kernel"].shape == (64, 64)
        for name in ("q_proj", "k_proj", "v_proj"):
            assert t["h_0"]["attn"][name]["kernel"] == P("fsdp", "tp")
        assert t["h_0"]["attn"]["q_norm"]["scale"] == t["h_0"]["attn"]["k_norm"]["scale"] == P()
        unruled = match_partition_rules(lm_partition_rules()[:-1] + [(".*", "fallback")], {"attn": attn})  # every leaf has its rule
        assert "fallback" not in jax.tree_util.tree_leaves(unruled, is_leaf=lambda s: isinstance(s, (str, P)))
    else:
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


# ----- the ZeRO-3 schedule the program states on a partitioned mesh
# (trlx_tpu/parallel/schedule.py): a pass over many tokens gathers each
# kernel at its point of use and leaves the rows where the batch split put
# them; a decode step keeps the shards. Read off the compiled HLO of tiny
# models on forced CPU devices, whose SPMD partitioner is the chip's.
ZERO3_B, ZERO3_P, ZERO3_R = 8, 16, 16  # global batch 8: no other dimension of these models is 8
_ZERO3_COMMON = {"vocab_size": 512, "n_layer": 3, "n_head": 2, "d_model": 128, "max_position": 64, "eos_token_id": 0}
ZERO3_ARCHS = {
    "gptj": {**_ZERO3_COMMON, "pos_type": "rotary", "rotary_dim": 32, "parallel_residual": True,
             "fused_qkv": False, "qkv_bias": False, "out_bias": False, "tie_word_embeddings": False,
             "extra": {"lm_head_bias": True}},
    "gptneo": {**_ZERO3_COMMON, "pos_type": "learned", "fused_qkv": False, "qkv_bias": False, "scale_attn": False,
               "attention_layers": ["global", "local", "global"], "window_size": 12, "tie_word_embeddings": True},
    "mla-experts": {**_ZERO3_COMMON, "pos_type": "rotary", "norm": "rmsnorm", "mlp": "gated", "attention": "mla",
                    "activation": "silu", "tie_word_embeddings": False, "d_ff": 256,
                    "ffn_layers": ["dense", "experts", "experts"], "q_lora_rank": 64, "kv_lora_rank": 64,
                    "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32, "n_experts": 16,
                    "experts_per_token": 2, "expert_d_ff": 128, "n_shared_experts": 1, "experts_held": [0, 4]},
}


def _hlo_shapes(text, op):
    """Result shapes (as [dims] tuples) of every `op` instruction of an HLO
    text, the elements of a tuple result each."""
    shapes = []
    for line in text.splitlines():
        head, sep, _ = line.partition(f" {op}(")
        if not sep:
            head, sep, _ = line.partition(f" {op}-start(")
        if sep and " = " in head:
            shapes += [tuple(int(d) for d in dims.split(",") if d)
                       for dims in re.findall(r"\w+\[([\d,]*)\]", head.split(" = ", 1)[1])]
    return shapes


@functools.lru_cache(maxsize=None)
def _zero3_programs(arch_name, mesh_shape):
    """The compiled train step and generate program of a tiny PPO model over
    `mesh_shape`, from abstract parameters (nothing runs): their HLO texts,
    what `use_weight` counted while each was traced, and the kernels' shapes."""
    from functools import partial

    from jax.sharding import NamedSharding

    from trlx_tpu.data import PPORLBatch
    from trlx_tpu.models.heads import extract_branch_params, trainable_mask
    from trlx_tpu.models.hf_import import build_lm_config
    from trlx_tpu.ops.generate import generate
    from trlx_tpu.ops.sampling import GenerateConfig
    from trlx_tpu.parallel.mesh import DATA_AXES, peek_mesh, set_mesh
    from trlx_tpu.parallel.schedule import count_weight_gathers, use_spec
    from trlx_tpu.parallel.sharding import sanitize_specs, specs_to_shardings
    from trlx_tpu.trainer.api import default_config
    from trlx_tpu.trainer.base import TrainState, build_optimizer
    from trlx_tpu.trainer.ppo import make_ppo_train_step

    B, P_, R = ZERO3_B, ZERO3_P, ZERO3_R
    config = default_config("ppo")
    config.model.model_path = config.model.tokenizer_path = ""
    config.model.num_layers_unfrozen, config.model.dtype, config.model.remat = 1, "float32", True
    config.model.model_arch = dict(ZERO3_ARCHS[arch_name])
    config.train.mesh, config.train.batch_size, config.train.seq_length = mesh_shape, B, P_ + R
    prior = peek_mesh()
    mesh = make_mesh(mesh_shape, devices=jax.devices()[: int(np.prod(mesh_shape))])
    set_mesh(mesh)
    try:
        lm_cfg = build_lm_config(config).replace(onehot_embed=True)  # as the trainer does on a mesh
        model = LMWithValueHead(lm_cfg, branch_layer=lm_cfg.n_layer - 1)
        one = jnp.zeros((1, 2), jnp.int32)
        params = jax.eval_shape(lambda r: model.init(r, one, jnp.ones_like(one))["params"], jax.random.PRNGKey(0))
        trainable = trainable_mask(params, lm_cfg, 1)
        optimizer, schedule = build_optimizer(config.train, trainable)
        detach = lambda p: jax.tree_util.tree_map(lambda x, t: x if t else jax.lax.stop_gradient(x), p, trainable)
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
        f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
        state = TrainState(step=i32(), params=params, opt_state=jax.eval_shape(optimizer.init, params),
                           extras=jax.eval_shape(lambda p: extract_branch_params(p, lm_cfg, model.branch_layer), params),
                           bad_steps=i32())
        batch = PPORLBatch(query_tensors=i32(B, P_), query_mask=i32(B, P_), response_tensors=i32(B, R),
                           response_mask=i32(B, R), logprobs=f32(B, R), values=f32(B, R), rewards=f32(B, R))
        placed = lambda tree, shardings: jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), tree, shardings)
        by_rules = lambda tree: specs_to_shardings(
            mesh, sanitize_specs(mesh, tree, match_partition_rules(lm_partition_rules(), tree)))
        rows = NamedSharding(mesh, P(DATA_AXES, None))
        out = {"train_tally": {}, "generate_tally": {}}
        with count_weight_gathers(out["train_tally"]):
            step = make_ppo_train_step(model, optimizer, config, P_, schedule, detach)
            out["train"] = step.lower(
                placed(state, by_rules(state)), placed(batch, jax.tree_util.tree_map(lambda _: rows, batch))
            ).compile().as_text()
        gcfg = GenerateConfig(max_new_tokens=R, do_sample=False, eos_token_id=None, pad_token_id=0)
        with count_weight_gathers(out["generate_tally"]):
            out["generate"] = jax.jit(partial(generate, model=model, gcfg=gcfg)).lower(
                {"params": placed(params, by_rules(params))}, placed(i32(B, P_), rows), placed(i32(B, P_), rows),
                jax.ShapeDtypeStruct((2,), jnp.uint32),
            ).compile().as_text()
        # every leaf the partition rules split over fsdp: its full shape, and
        # whether it trains
        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        masks = jax.tree_util.tree_leaves(trainable)
        out["split"] = {}
        for (path, leaf), trains in zip(flat, masks):
            name = "/".join(str(k.key) for k in path)
            stored, at_use = use_spec(name, leaf.shape)
            if stored != at_use:
                out["split"][name] = (tuple(leaf.shape), bool(trains), at_use)
        return out
    finally:
        set_mesh(prior)


def _computation(text, name):
    """The lines of the HLO computation `name`."""
    lines = text.splitlines()
    start = next(i for i, l in enumerate(lines) if l.lstrip("ENTRY ").startswith(f"%{name} ") and l.rstrip().endswith("{"))
    return lines[start: next(i for i in range(start, len(lines)) if lines[i].startswith("}"))]


@pytest.mark.parametrize("what", ["rows-stay", "every-kernel-gathers", "only-trainable-gradients-reduce"])
@pytest.mark.parametrize("arch", sorted(ZERO3_ARCHS))
def test_zero3_train_step_on_fsdp4(arch, what):
    """Over fsdp 4 the train step of a GPT-J-shaped, a GPT-Neo-shaped and an
    MLA + expert model moves weights and never rows: no all-to-all, no
    collective-permute, no array whose leading dimensions are the GLOBAL
    batch and a sequence; an all-gather for every kernel the rules split over
    fsdp (and one more each in the remat'd backward); weight gradients
    reduced for the leaves that train and no others."""
    from trlx_tpu.parallel.schedule import weight_gather_share

    got = _zero3_programs(arch, (1, 4, 1, 1))
    text, split = got["train"], got["split"]
    if what == "rows-stay":
        assert not _hlo_shapes(text, "all-to-all") and not _hlo_shapes(text, "collective-permute")
        global_rows = [s for s in re.findall(r"\w+\[([\d,]+)\]", text)
                       if s.startswith(f"{ZERO3_B},") and int(s.split(",")[1]) >= ZERO3_R - 1]
        assert not global_rows, sorted(set(global_rows))
    elif what == "every-kernel-gathers":
        assert weight_gather_share(got["train_tally"]) == 1.0
        assert {path for path, _ in got["train_tally"]} == set(split)
        gathered = _hlo_shapes(text, "all-gather")
        for name, (shape, _, _) in split.items():
            assert shape in gathered or shape[::-1] in gathered, (name, shape)
        assert len(gathered) >= len(split)
    else:
        # By elements, not by instruction: the partitioner reduces an expert
        # stack an expert at a time and a latent projection in its per-head
        # shape. The CPU backend writes a reduce-scatter as all-reduce + slice.
        big = lambda shape: int(np.prod(shape)) >= 64 * 64  # a kernel, not a bias, a norm or a loss scalar
        reduced = sum(int(np.prod(s)) for s in _hlo_shapes(text, "all-reduce") if big(s))
        reduced += sum(4 * int(np.prod(s)) for s in _hlo_shapes(text, "reduce-scatter") if big(s))
        expected = sum(int(np.prod(shape)) for shape, trains, _ in split.values() if trains and big(shape))
        if ZERO3_ARCHS[arch]["tie_word_embeddings"]:
            expected += int(np.prod(split["transformer/wte/embedding"][0]))  # used twice (lookup, head): reduced twice
        assert reduced == expected


@pytest.mark.parametrize("arch", sorted(ZERO3_ARCHS))
def test_zero3_decode_step_keeps_its_shards(arch):
    """The generate program over fsdp 4: the prefill (128 tokens) gathers,
    the decode loop (8 tokens a step) gathers no kernel, so the program's
    share of gathered weight bytes is the prefill's part only."""
    from trlx_tpu.parallel.schedule import weight_gather_share

    got = _zero3_programs(arch, (1, 4, 1, 1))
    text = got["generate"]
    kept = {path for (path, gathered) in got["generate_tally"] if not gathered}
    assert kept and 0.0 < weight_gather_share(got["generate_tally"]) < 1.0
    kernel_shapes = {s for shape, _, _ in got["split"].values() for s in (shape, shape[::-1])}
    body = re.search(r" while\(.*body=%([\w.\-]+)", text).group(1)
    assert not set(_hlo_shapes("\n".join(_computation(text, body)), "all-gather")) & kernel_shapes
    assert set(_hlo_shapes(text, "all-gather")) & kernel_shapes  # the prefill's


def test_zero3_keeps_the_tp_split_at_use():
    """fsdp 2 x tp 2: only the fsdp axis is dropped at a kernel's point of
    use. Every split kernel's spec at use still names tp, and no all-gather
    of the train step produces a whole kernel."""
    got = _zero3_programs("gptj", (1, 2, 2, 1))
    on_tp = {name: at_use for name, (_, _, at_use) in got["split"].items() if "tp" in jax.tree_util.tree_leaves(tuple(at_use))}
    assert len(on_tp) == len(got["split"]) and all("fsdp" not in str(spec) for spec in on_tp.values())
    # the 512-wide kernels (c_fc, mlp c_proj, the embedding, the head) arrive
    # 256 wide: no all-gather of the train step produces one whole
    gathered = set(_hlo_shapes(got["train"], "all-gather"))
    assert {(128, 256), (256, 128)} <= gathered and not any(512 in s for s in gathered)
    assert not _hlo_shapes(got["train"], "all-to-all")


def test_the_size_rule_of_a_weight_gather():
    """`weights_travel` at GPT-J's widths over fsdp 4: break-even at 2,048
    tokens for a square projection and 3,277 for the MLP's; the cell's train
    step, prefill and scoring pass gather, its decode step does not; nothing
    travels without an fsdp axis."""
    from trlx_tpu.parallel.mesh import peek_mesh, set_mesh
    from trlx_tpu.parallel.schedule import weights_travel

    prior = peek_mesh()
    try:
        set_mesh(make_mesh((1, 4, 1, 1), devices=jax.devices()[:4]))
        assert not weights_travel(2048, (4096, 4096)) and weights_travel(2049, (4096, 4096))
        assert not weights_travel(3276, (4096, 16384)) and weights_travel(3277, (4096, 16384))
        for tokens, travels in ((8 * 1024, True), (32 * 768, True), (32 * 1024, True), (32, False), (32 * 4, False)):
            assert all(weights_travel(tokens, s) == travels for s in ((4096, 4096), (4096, 16384), (16384, 4096), (4096, 50400)))
        assert weights_travel(8192, (8, 7168, 2048)) and not weights_travel(32, (8, 7168, 2048))  # an expert stack: by one expert's widths
        set_mesh(make_mesh((4, 1, 1, 1), devices=jax.devices()[:4]))
        assert not weights_travel(8192, (4096, 4096))
        set_mesh(None)
        assert not weights_travel(8192, (4096, 4096))
    finally:
        set_mesh(prior)


def test_schedule_helpers_return_their_argument_without_a_partitioned_mesh():
    """No mesh, or a mesh of one device: `use_weight` and `hold_rows` are the
    identity and the traced program holds no constraint and no barrier; over
    fsdp 4 the same function traces to both."""
    from trlx_tpu.parallel.mesh import peek_mesh, set_mesh
    from trlx_tpu.parallel.schedule import hold_rows, use_weight

    cfg = LMConfig(vocab_size=64, n_layer=2, n_head=2, d_model=64, max_position=32, dtype="float32", remat=True)
    model = LMWithValueHead(cfg)
    ids, mask = jnp.zeros((4, 32), jnp.int32), jnp.ones((4, 32), jnp.int32)
    params = jax.eval_shape(lambda r: model.init(r, ids, mask)["params"], jax.random.PRNGKey(0))
    loss = lambda p: jnp.sum(model.apply({"params": p}, ids, mask)["logits"])
    w, x = jnp.ones((64, 256)), jnp.ones((4, 32, 64))
    prior = peek_mesh()
    try:
        for mesh in (None, make_mesh((1, 1, 1, 1), devices=jax.devices()[:1])):
            set_mesh(mesh)
            assert use_weight(w, ("h_0", "mlp", "c_fc", "kernel"), 4096) is w and hold_rows(x) is x
            jaxpr = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
            assert _count_primitive(jaxpr, "sharding_constraint") == 0
            assert _count_primitive(jaxpr, "optimization_barrier") == 0
        set_mesh(make_mesh((1, 4, 1, 1), devices=jax.devices()[:4]))
        assert _count_primitive(jax.make_jaxpr(jax.grad(loss))(params).jaxpr, "sharding_constraint") > 0
    finally:
        set_mesh(prior)
