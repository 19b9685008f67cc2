"""Device-free rehearsal: compile a cell's train step for a described v5e.

    JAX_PLATFORMS=cpu python benchmark/compile_check.py <cell> [<cell> ...]

The TPU compiler is installed where no TPU is. This builds the cell's real
train-step program (`make_ppo_train_step`, `ILQLTrainer.build_train_step`)
over abstract parameters placed on one chip of a `v5e:2x2` topology and
compiles it: what Mosaic or the memory planner would refuse on the chip, it
refuses here, at no chip time. It prints `memory_analysis()` and the Pallas
kernels the program contains. The program's kernel gates ask
`jax.default_backend()`, which is the CPU here, so this script (and only
this script) answers "tpu" for them. A compile that passes is a rehearsal,
never a result: nothing runs, and the analysis counts one program, not what
else the process keeps on the device (decode copies, the rollout cache).
"""

import math
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def abstract_train_step(cell, config, arch):
    """(jitted step, abstract state, abstract batch) of a cell, no allocation."""
    import jax
    import jax.numpy as jnp

    from trlx_tpu.data import ILQLBatch, PPORLBatch
    from trlx_tpu.models.heads import LMWithILQLHeads, LMWithValueHead, extract_branch_params, trainable_mask
    from trlx_tpu.models.hf_import import build_lm_config
    from trlx_tpu.trainer.base import TrainState, build_optimizer
    from trlx_tpu.trainer.ilql import ILQLTrainer
    from trlx_tpu.trainer.ppo import make_ppo_train_step

    lm_cfg = build_lm_config(config)
    if math.prod(config.train.mesh) > 1:
        lm_cfg = lm_cfg.replace(onehot_embed=True)  # as JaxBaseTrainer.finalize_lm_config does on a mesh
    k, n = config.model.num_layers_unfrozen, lm_cfg.n_layer
    ppo = cell["method"] == "ppo"
    if ppo:
        model = LMWithValueHead(lm_cfg, branch_layer=n - k if 0 < k < n else -1)
    else:
        model = LMWithILQLHeads(lm_cfg, two_qs=config.method.two_qs)
    ids = jnp.zeros((1, 2), jnp.int32)
    params = jax.eval_shape(lambda r: model.init(r, ids, jnp.ones_like(ids))["params"], jax.random.PRNGKey(0))
    mask = trainable_mask(params, lm_cfg, k)
    optimizer, schedule = build_optimizer(config.train, mask)

    def detach_frozen(p):
        return jax.tree_util.tree_map(lambda x, t: x if t else jax.lax.stop_gradient(x), p, mask)

    B, T = config.train.batch_size, config.train.seq_length
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    if ppo:
        P = cell["traffic_params"]["prompt_length"]["max"]
        R = T - P
        extras = jax.eval_shape(lambda p: extract_branch_params(p, lm_cfg, model.branch_layer), params)
        step = make_ppo_train_step(model, optimizer, config, P, schedule, detach_frozen)
        batch = PPORLBatch(query_tensors=i32(B, P), query_mask=i32(B, P), response_tensors=i32(B, R),
                           response_mask=i32(B, R), logprobs=f32(B, R), values=f32(B, R), rewards=f32(B, R))
    else:
        extras = {h: params[h] for h in (("q1_head", "q2_head") if config.method.two_qs else ("q1_head",))}
        shell = object.__new__(ILQLTrainer)  # build_train_step reads these five and nothing else
        shell.config, shell.model, shell.optimizer, shell.schedule, shell.opt_mask = config, model, optimizer, schedule, mask
        step = shell.build_train_step()
        batch = ILQLBatch(input_ids=i32(B, T), attention_mask=i32(B, T), rewards=f32(B, T - 1),
                          states_ixs=i32(B, T), actions_ixs=i32(B, T - 1), dones=i32(B, T))
    state = TrainState(step=i32(), params=params, opt_state=jax.eval_shape(optimizer.init, params),
                       extras=extras, bad_steps=i32())
    return step, state, batch


def main(names):
    import jax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    from benchmark import harness
    from benchmark.manifest import Manifest
    from trlx_tpu.ops import tiling
    from trlx_tpu.parallel import make_mesh, set_mesh
    from trlx_tpu.parallel.mesh import DATA_AXES
    from trlx_tpu.parallel.sharding import lm_partition_rules, match_partition_rules, sanitize_specs, specs_to_shardings

    # the kernel gates' view of the machine: a TPU backend, a one-device mesh
    jax.default_backend = lambda: "tpu"
    tiling.require_lowering = lambda *a, **kw: None  # the compile below is the stricter check
    jax.config.update("jax_enable_compilation_cache", False)

    devices = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices
    chip = SingleDeviceSharding(devices[0])
    with_shardings = lambda tree, shardings: jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), tree, shardings)
    manifest = Manifest(ROOT)
    for name in names:
        cell = manifest.cell(name)
        config, arch = harness.build_config(cell, manifest.config(cell["config"]), 0, "/nonexistent", False)
        # A cell on a mesh: the trainer's own placement (parallel/sharding.py's
        # rules for the state, the batch over the data axes) on the described
        # chips; the kernel gates see the mesh through `set_mesh`, as they do
        # in the trainer. memory_analysis() is then one chip's bytes.
        mesh = make_mesh(config.train.mesh, devices=devices[:cell["chips"]]) if cell["chips"] > 1 else None
        set_mesh(mesh)
        kernels = {}
        with harness.record_pallas_calls(kernels):
            step, state, batch = abstract_train_step(cell, config, arch)
            everywhere = lambda sharding, tree: jax.tree_util.tree_map(lambda s: sharding, tree)
            if mesh is None:
                state_at, batch_at = everywhere(chip, state), everywhere(chip, batch)
            else:
                specs = sanitize_specs(mesh, state, match_partition_rules(lm_partition_rules(), state))
                state_at = specs_to_shardings(mesh, specs)
                batch_at = everywhere(NamedSharding(mesh, PartitionSpec(DATA_AXES, None)), batch)
            compiled = step.lower(with_shardings(state, state_at), with_shardings(batch, batch_at)).compile()
        set_mesh(None)
        ma = compiled.memory_analysis()
        gb = lambda b: round(b / 1e9, 3)
        text = compiled.as_text()
        collectives = {op: len(re.findall(rf" {op}(?:-start)?\(", text))
                       for op in ("all-gather", "reduce-scatter", "all-reduce", "all-to-all", "collective-permute")}
        collectives["reduce-scatter"] += text.count(", calls=%all-reduce-scatter")  # how a v5e compile writes one
        print(f"[compile_check] {name}: train step compiles for v5e"
              f"{'' if mesh is None else ' over mesh ' + str(dict(mesh.shape))}; "
              f"arguments {gb(ma.argument_size_in_bytes)} GB, temporaries {gb(ma.temp_size_in_bytes)} GB, "
              f"outputs {gb(ma.output_size_in_bytes)} GB (aliased {gb(ma.alias_size_in_bytes)} GB), "
              f"tpu_custom_calls {text.count('tpu_custom_call')}, collectives {collectives}, "
              f"kernels {sorted(kernels)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
