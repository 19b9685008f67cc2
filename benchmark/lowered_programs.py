"""Are the traced programs of accepted configurations the same in two trees? For each configuration named (by
default the four ISSUE 53 named), at its published widths and its cell's shapes, with the TPU kernel gates on and no
device: the train pass (value_and_grad through LMWithValueHead, remat as served), the prefill into the cache and one
decode step, each as jaxpr text, hashed. A program that hashes alike in both trees compiles to the same executable,
so a cell that runs only such programs cannot move.

    python3 benchmark/lowered_programs.py <tree> <other tree> [--configs name ...]

Each tree is a checkout of this repository (`git archive <commit> | tar -x -C <dir>`); the hashing runs in a process
a tree, from that tree's own sources. Exit 0 where every program is the same, 1 where one differs (they are listed).
"""

import argparse
import hashlib
import json
import os
import re
import subprocess
import sys

# configuration: (train rows, seq_length, prompt length) of the cell that runs it; 4 rollout rows through the cache
SHAPES = {"kimi-k2.5-ep48-l5": (4, 1024, 128), "kimi-linear-48b-ep32-l13": (4, 1024, 128),
          "minicpm-sala-9b-l4": (1, 12288, 10240), "gptj-6b-l8": (8, 1024, 128),
          "smallthinker-21b-ep4": (2, 6144, 4096), "zaya1-8b-ep2-l8": (2, 6144, 4096),
          "k-exaone-236b-ep16-l5": (4, 1024, 128), "glm-5-ep32-tp4-l5": (1, 8192, 6144)}
DEFAULT = ("kimi-k2.5-ep48-l5", "kimi-linear-48b-ep32-l13", "minicpm-sala-9b-l4", "gptj-6b-l8")
SERVING_KEYS = ("dtype", "param_dtype", "remat", "kv_cache_quant")


def hashes(root, names):
    """{"<configuration> <pass>": [text length, sha256's first 16]} from the sources under `root`."""
    sys.path.insert(0, root)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp

    jax.default_backend = lambda: "tpu"  # the kernel gates read it; nothing is lowered, so no TPU is asked for
    from trlx_tpu.ops import tiling

    tiling.require_lowering = lambda *a, **kw: None
    from trlx_tpu.models.heads import LMWithValueHead
    from trlx_tpu.models.lm import LMConfig, TransformerLM, init_cache

    out = {}
    for name in names:
        b, T, P = SHAPES[name]
        spec = json.load(open(os.path.join(root, "benchmark", "configs", f"{name}.json")))
        cfg = LMConfig.from_dict({**spec["model_arch"], **{k: v for k, v in spec.get("serving", {}).items() if k in SERVING_KEYS}})
        model = LMWithValueHead(cfg, branch_layer=cfg.n_layer - 1)
        ids = jnp.zeros((1, 2), jnp.int32)
        params = jax.eval_shape(lambda r: model.init(r, ids, jnp.ones_like(ids))["params"], jax.random.PRNGKey(0))
        tokens = lambda rows, n: jax.ShapeDtypeStruct((rows, n), jnp.int32)

        def loss(p, ids, mask):
            got = model.apply({"params": p}, ids, mask, labels=ids[:, P:], logits_start=P - 1)
            return jnp.sum(got["logprobs"]) + jnp.sum(got["values"].astype(jnp.float32))

        rows, trunk, trunk_params = 4, TransformerLM(cfg), params["transformer"]
        cache = jax.eval_shape(lambda: init_cache(cfg, rows, T))
        through = lambda p, i, m, c, cm, at: trunk.apply({"params": p}, i, m, cache=c, cache_index=at, cache_mask=cm)
        texts = {
            "train": jax.make_jaxpr(jax.value_and_grad(loss))(params, tokens(b, T), tokens(b, T)),
            "prefill": jax.make_jaxpr(lambda p, i, m, c, cm: through(p, i, m, c, cm, 0))(
                trunk_params, tokens(rows, P), tokens(rows, P), cache, tokens(rows, T)),
            "decode": jax.make_jaxpr(through)(trunk_params, tokens(rows, 1), tokens(rows, 1), cache, tokens(rows, T),
                                              jax.ShapeDtypeStruct((), jnp.int32)),
        }
        for kind, text in texts.items():
            text = re.sub(r"0x[0-9a-f]+", "0x", str(text))
            out[f"{name} {kind}"] = [len(text), hashlib.sha256(text.encode()).hexdigest()[:16]]
    return out


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("trees", nargs="+", help="two checkouts to compare (one with --hash)")
    p.add_argument("--configs", nargs="+", default=list(DEFAULT), choices=sorted(SHAPES))
    p.add_argument("--hash", action="store_true", help="print one tree's hashes as JSON (what the comparison runs a tree)")
    args = p.parse_args(argv)
    if args.hash:
        print(json.dumps(hashes(os.path.abspath(args.trees[0]), args.configs)))
        return 0
    if len(args.trees) != 2:
        p.error("two trees to compare")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    sides = []
    for tree in args.trees:
        script = os.path.join(os.path.abspath(tree), "benchmark", "lowered_programs.py")
        script = script if os.path.exists(script) else os.path.abspath(__file__)  # a tree from before this file
        done = subprocess.run([sys.executable, script, "--hash", tree, "--configs", *args.configs], env=env, check=True,
                              capture_output=True, text=True)
        sides.append(json.loads(done.stdout.strip().splitlines()[-1]))
    differ = [key for key in sides[0] if sides[0][key] != sides[1].get(key)]
    for key in sides[0]:
        print(f"{'differs' if key in differ else 'same   '} {key:44s} {sides[0][key]} {sides[1].get(key)}")
    print(f"[lowered_programs] {len(sides[0]) - len(differ)} of {len(sides[0])} programs are the same in both trees")
    return 1 if differ else 0


if __name__ == "__main__":
    sys.exit(main())
