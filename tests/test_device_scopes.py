"""Device time by scope (trlx_tpu/observability/device_scopes.py): the
vocabulary against the source, the table from a compiled module's text, the
proxy every jitted program is dispatched through, and the capture's life on a
tiny PPO run: nothing without a profiler session; with one, the table of the
programs that ran under it, written at the first boundary after it closed."""

import ast
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples"))

import trlx_tpu  # noqa: E402
from randomwalks import base_config, generate_random_walks  # noqa: E402
from trlx_tpu.observability import device_scopes  # noqa: E402
from trlx_tpu.observability import spans as obs_spans  # noqa: E402

PACKAGE = os.path.dirname(os.path.abspath(trlx_tpu.__file__))


@pytest.fixture(autouse=True)
def _clean_slate():
    device_scopes.configure(None)
    yield
    obs_spans.shutdown()
    device_scopes.configure(None)


def _scope_literals(node):
    """The string constants a `jax.named_scope(...)` call's first argument can be."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, ast.IfExp):
        return _scope_literals(node.body) + _scope_literals(node.orelse)
    return [None]


def test_every_named_scope_of_the_package_is_in_the_vocabulary_and_every_name_has_a_site():
    used = {}
    for base, _, files in os.walk(PACKAGE):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(base, name)
            with open(path) as f:
                tree = ast.parse(f.read(), path)
            for node in ast.walk(tree):
                if (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute) and node.func.attr == "named_scope"
                        and isinstance(node.func.value, ast.Name) and node.func.value.id == "jax"):
                    for literal in _scope_literals(node.args[0]):
                        used.setdefault(literal, []).append(f"{os.path.relpath(path, PACKAGE)}:{node.lineno}")
    assert None not in used, f"named_scope with a name that is not a literal: {used.get(None)}"
    assert len(set(device_scopes.SCOPES)) == len(device_scopes.SCOPES)
    unknown = {k: v for k, v in used.items() if k not in device_scopes.SCOPES}
    assert not unknown, f"jax.named_scope names missing from device_scopes.SCOPES: {unknown}"
    assert not set(device_scopes.SCOPES) - set(used), f"SCOPES names no site uses: {set(device_scopes.SCOPES) - set(used)}"


def _layer(x, w):
    with jax.named_scope("ssm_scan"):
        return jnp.tanh(x @ w)


def _table_of(fn, *args):
    return device_scopes.scope_table(jax.jit(fn).lower(*args).compile().as_text())


def test_scope_table_innermost_scope_through_a_loop_and_a_switch():
    def f(x, w):
        with jax.named_scope("decode_loop"):
            def body(c):
                i, x = c
                with jax.named_scope("kv_read"):
                    x = jax.lax.switch(i % 2, [lambda x: _layer(x, w), lambda x: x * 2.0], x)
                return i + 1, x

            return jax.lax.while_loop(lambda c: c[0] < 3, body, (0, x))[1]

    x = jnp.ones((16, 16))
    table = _table_of(f, x, x)
    assert table["module"] == "jit_f"
    chains = {chain for chain, _ in table["ops"].values()}
    assert {"decode_loop", "decode_loop/kv_read", "decode_loop/kv_read/ssm_scan"} <= chains
    assert {which for _, which in table["ops"].values()} == {"fwd"}
    assert not any(name.startswith("%") for name in table["ops"])
    # the containers have entries of their own: the loop under its scope, the conditional under the read's
    by_opcode = lambda prefix: {chain for name, (chain, _) in table["ops"].items() if name.startswith(prefix)}
    assert "decode_loop" in by_opcode("while") and all(c.startswith("decode_loop") for c in by_opcode("while"))
    assert "decode_loop/kv_read" in by_opcode("cond") | by_opcode("conditional")
    # a parameter's op_name is its own name: no scope
    assert table["ops"]["x.1"][0] == ""


def test_scope_table_passes_from_the_name_stack_of_a_remat_gradient():
    def loss(w, x):
        h = jax.checkpoint(_layer)(x, w)
        h = jax.checkpoint(_layer)(h, w)
        with jax.named_scope("loss"):
            return jnp.sum(h**2)

    x = jnp.ones((16, 16))
    table = _table_of(jax.grad(loss), x, x)
    seen = {(chain, which) for chain, which in table["ops"].values()}
    # forward and backward of the scope (a transform wraps the name: `jvp(ssm_scan)`), and its recomputation
    assert {("ssm_scan", "fwd"), ("ssm_scan", "bwd"), ("ssm_scan", "recompute")} <= seen
    # `jit(loss)`, at the head of every path, is the jitted function and not the scope `loss`
    assert ("", "bwd") in seen and all(chain in ("", "ssm_scan", "loss") for chain, _ in seen)
    # a forward-only pass through `checkpoint` recomputes nothing
    forward = _table_of(lambda x, w: jax.checkpoint(_layer)(x, w), x, x)
    assert {which for _, which in forward["ops"].values()} == {"fwd"}


def test_scope_table_reads_names_with_and_without_percent_and_skips_fused_members():
    text = """HloModule jit_step, is_scheduled=true, entry_computation_layout={()->f32[]}

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %tanh.9 = f32[8]{0} tanh(%p), metadata={op_name="jit(step)/jvp(lm_head)/tanh"}
}

%branch_1.2 (q: f32[8]) -> f32[8] {
  %q = f32[8]{0} parameter(0)
  %ragged-dot-none.3 = f32[8]{0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  ROOT %slice-done.1 = f32[8]{0} copy(%ragged-dot-none.3)
}

%body.6 (c: f32[8]) -> f32[8] {
  %c = f32[8]{0} parameter(0)
  ROOT %cond.5 = f32[8]{0} conditional(%c, %c, %c), branch_computations={%branch_1.2, %branch_1.2}, metadata={op_name="jit(step)/transpose(jvp(moe_experts))/cond"}
}

ENTRY %main.3 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="a"}
  %copy.4 = f32[8]{0} copy(%a)
  %while.8 = f32[8]{0} while(%copy.4), condition=%cond_fn.1, body=%body.6
  fusion.2 = f32[8]{0:T(8)} fusion(%copy.4), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jit(main)/transpose(jvp(lm_head))/mul;jit(step)/x" source_file="a.py" source_line=3}
  ROOT %fusion.7 = f32[8]{0} fusion(fusion.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/transformer/h_1/checkpoint/rematted_computation/attn/attn_full/flash_attn/pad"}
}
"""
    table = device_scopes.scope_table(text)
    assert table == {"module": "jit_step", "ops": {
        "a": ["", "fwd"],
        "fusion.2": ["lm_head", "bwd"],  # the first of a merged instruction's op_names
        "fusion.7": ["attn_full/flash_attn", "recompute"],
        # a container hands its scope down where an instruction's own path holds none, at any depth: the
        # compiler's kernel with a bare op_name keeps its own pass, the wait without metadata takes both
        "cond.5": ["moe_experts", "bwd"], "ragged-dot-none.3": ["moe_experts", "fwd"],
        "slice-done.1": ["moe_experts", "bwd"], "q": ["moe_experts", "bwd"],
    }}  # no entry for the fused computation's member, for the copy without metadata, nor for the loop without a scope


def test_scope_table_gives_a_kernel_without_a_name_stack_the_scope_of_what_it_reads_or_of_what_reads_it():
    """The grouped products of an expert call that goes in ONE pass: XLA names
    them `ragged-dot-none` with no name stack and nothing contains them."""
    kernel = 'custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}'
    experts = 'metadata={op_name="jit(step)/jvp(T)/h_0/moe/moe_experts/%s"}'
    text = f"""HloModule jit_step, is_scheduled=true

ENTRY %main.9 (w: bf16[8,4,4], g: bf16[16,4]) -> (bf16[16,4], bf16[8,4,4]) {{
  %w = bf16[8,4,4]{{2,1,0}} parameter(0), metadata={{op_name="p['experts_up']"}}
  %g = bf16[16,4]{{1,0}} parameter(1), metadata={{op_name="g"}}
  %sizes.1 = (s32[9]{{0:T(128)S(1)}}, s32[1]{{0}}) fusion(%g), kind=kLoop, calls=%fc.1, {experts % "cumsum"}
  %gte.1 = s32[9]{{0:T(128)S(1)}} get-tuple-element(%sizes.1), index=0
  %ragged-dot-metadata = (s32[9]{{0:T(128)S(1)}}, s32[1]{{0:T(128)}}) custom-call(%gte.1), custom_call_target="tpu_custom_call", metadata={{op_name="ragged-dot-metadata"}}
  %gte.2 = s32[9]{{0:T(128)S(1)}} get-tuple-element(%ragged-dot-metadata), index=0
  %rows.2 = bf16[16,4]{{1,0:T(8,128)(2,1)}} fusion(%g), kind=kLoop, calls=%fc.2, {experts % "take_rows"}
  %copy.3 = bf16[8,4,4]{{1,2,0:T(8,128)(2,1)}} copy(%w), metadata={{op_name="p['experts_up']"}}
  %bitcast.3 = bf16[8,4,4]{{2,1,0}} bitcast(%copy.3)
  %ragged-dot-none.2 = bf16[16,4]{{1,0:T(8,128)(2,1)S(1)}} custom-call(%gte.2, /*index=1*/%rows.2, %w), {kernel}
  %copy-start.4 = (bf16[16,4]{{1,0}}, bf16[16,4]{{1,0}}, u32[]{{:S(2)}}) copy-start(%ragged-dot-none.2)
  %copy-done.4 = bf16[16,4]{{1,0}} copy-done(%copy-start.4)
  %ragged-dot-none.1 = bf16[16,4]{{1,0}} custom-call(%gte.2, bf16[16,4]{{1,0}} %copy-done.4, bf16[8,4,4]{{2,1,0}} %bitcast.3), {kernel}
  %sum.5 = bf16[16,4]{{1,0}} fusion(%ragged-dot-none.1), kind=kLoop, calls=%fc.3, metadata={{op_name="jit(step)/transpose(jvp(T))/h_0/moe/moe_experts/moe_grouped_ffn/add_any"}}
  %ragged-dot-none = bf16[8,4,4]{{2,1,0:T(8,128)(2,1)}} custom-call(%gte.2, %rows.2, %sum.5), {kernel}
  %adam.6 = bf16[8,4,4]{{2,1,0}} fusion(%ragged-dot-none, %w), kind=kLoop, calls=%fc.4, metadata={{op_name="jit(step)/optimizer/mul"}}
  %alone = bf16[16,4]{{1,0}} custom-call(%g), {kernel}
  ROOT %tuple.7 = (bf16[16,4]{{1,0}}, bf16[8,4,4]{{2,1,0}}) tuple(%sum.5, %adam.6)
}}
"""
    ops = device_scopes.scope_table(text)["ops"]
    assert {name: entry for name, entry in ops.items() if entry[0]} == {
        "sizes.1": ["moe_experts", "fwd"], "rows.2": ["moe_experts", "fwd"],
        "sum.5": ["moe_experts/moe_grouped_ffn", "bwd"], "adam.6": ["optimizer", "fwd"],
        # an operand's scope first (the weight gradient's user is the optimizer), through a tuple's element
        "ragged-dot-metadata": ["moe_experts", "fwd"], "ragged-dot-none.2": ["moe_experts", "fwd"],
        "ragged-dot-none": ["moe_experts", "fwd"],
        # operands with none behind what has no metadata (the other kernel lends nothing in its round): the user's.
        # So for the relayout copy of a weight, named after its parameter: who reads it is found behind the bitcast
        "ragged-dot-none.1": ["moe_experts/moe_grouped_ffn", "fwd"], "copy.3": ["moe_experts/moe_grouped_ffn", "fwd"],
    }  # each keeps its own pass, as under a container
    assert ops["alone"] == ["", "fwd"] and ops["w"] == ["", "fwd"] and "gte.2" not in ops and "copy-done.4" not in ops


def test_proxy_forwards_attributes_and_donated_arguments_and_notes_only_under_a_session(tmp_path):
    step = jax.jit(lambda s, b: (s + b, s * 2.0), donate_argnums=0)
    proxy = device_scopes.wrap(step)
    s, b = jax.device_put(jnp.ones((8,)), jax.devices()[0]), np.ones((8,), np.float32)
    assert proxy.lower(s, b).compile() is not None and proxy._cache_size() == step._cache_size()

    def counted(x):
        counted.num_traces += 1
        return x

    counted.num_traces = 0
    wrapped = device_scopes.wrap(counted)
    wrapped(1), wrapped(2)
    assert wrapped.num_traces == 2  # live: read through to the function

    out, _ = proxy(s, b)
    assert s.is_deleted() and float(out[0]) == 2.0  # donated through the proxy as without it
    assert device_scopes.tables() == []  # no session: nothing noted

    jax.profiler.start_trace(str(tmp_path))
    try:
        s2 = jax.device_put(jnp.ones((8,)), jax.devices()[0])
        proxy(s2, b), proxy(out, b)  # one signature, twice
        assert s2.is_deleted()
        double = device_scopes.wrap(jax.jit(lambda x: x * 2.0))
        double(jnp.ones((8,))), double(jnp.ones((4,))), double(jnp.ones((4,)))  # two shapes of one program
    finally:
        jax.profiler.stop_trace()
    table, *doubles = device_scopes.tables()
    assert table["module"].startswith("jit_") and table["ops"] and len(doubles) == 2


@pytest.fixture(scope="module")
def task():
    return generate_random_walks(n_nodes=15, max_length=8, n_walks=60, seed=1000)


@pytest.mark.parametrize("telemetry", [False, True], ids=["plain", "device_telemetry"])
def test_capture_life_on_a_tiny_ppo_run(task, tmp_path, monkeypatch, telemetry):
    """Reward call 1 builds the first experience, call 2 comes after
    iteration 0's train steps: until then no session was open, and nothing
    may have been noted, lowered or written. The session then spans scoring,
    iteration 1's train steps and the next generation, and closes in reward
    call 3; the boundary after it writes the file."""
    _, logit_mask, metric_fn, reward_fn = task
    config = base_config("ppo", 15, 8)
    config.train.total_steps = 12
    config.train.epochs = 6
    config.train.batch_size = 16
    config.train.eval_interval = 100
    config.train.checkpoint_interval = 0
    config.train.checkpoint_dir = str(tmp_path / "run")
    config.train.device_telemetry = telemetry
    config.method.num_rollouts = 16
    config.method.chunk_size = 16
    path = os.path.join(config.train.checkpoint_dir, device_scopes.SCOPES_FILENAME)

    built = []
    real_table = device_scopes.scope_table
    monkeypatch.setattr(device_scopes, "scope_table", lambda text: built.append(1) or real_table(text))
    calls, seen = [], {}

    def tracing_reward_fn(rows):
        calls.append(len(rows))
        if len(calls) == 2:
            seen["before"] = (len(device_scopes._NOTED), len(built), os.path.exists(path))
            jax.profiler.start_trace(str(tmp_path / "trace"))
        elif len(calls) == 3:
            seen["open"] = (len(device_scopes._NOTED) > 0, len(built), os.path.exists(path))
            jax.profiler.stop_trace()
        return reward_fn(rows)

    prompts = [[int(np.random.default_rng(i).integers(1, 15))] for i in range(32)]
    try:
        trlx_tpu.train(reward_fn=tracing_reward_fn, prompts=prompts, eval_prompts=[[1]], metric_fn=metric_fn,
                       config=config, logit_mask=logit_mask)
    finally:
        if jax.profiler.TraceAnnotation.is_enabled():
            jax.profiler.stop_trace()
    assert len(calls) >= 3
    assert seen["before"] == (0, 0, False)  # a whole iteration without a session: nothing
    assert seen["open"] == (True, 0, False)  # noted, but no text and no file inside the traced window
    with open(path) as f:
        written = json.load(f)["programs"]
    programs = {t["module"]: t for t in written}
    assert {"jit_train_step", "jit_traced"} <= set(programs)
    chains = lambda module: {chain for chain, _ in programs[module]["ops"].values()}
    scopes = lambda module: {s for chain in chains(module) for s in chain.split("/")}
    assert {"lm_head", "loss", "optimizer", "embed"} <= scopes("jit_train_step")
    assert {"prefill", "decode_loop", "kv_read", "lm_head", "sample"} <= scopes("jit_traced")
    assert any(chain.startswith("decode_loop/") and chain.endswith("kv_read") for chain in chains("jit_traced"))
    assert {which for _, which in programs["jit_train_step"]["ops"].values()} >= {"fwd", "bwd"}
    assert len(built) == len(written)  # each program's text was read once


def test_report_renders_device_time_by_scope_from_a_trace_and_the_table(tmp_path, capsys):
    """`report.py --xplane`: the recorded v5e trace (three train steps, one
    generate) joined with a hand-made table; the loop is a container and is
    left out, an operation without an entry reads `(no scope)`."""
    from trlx_tpu.observability import report

    xplane = os.path.join(os.path.dirname(PACKAGE), "benchmark", "tests", "data", "tiny_v5e.xplane.pb")
    assert "No `device_scopes.json`" in "\n".join(report._device_scopes_section(str(tmp_path), xplane))
    tables = [{"module": "jit_train_step", "ops": {"train_step.1": ["lm_head", "bwd"], "fusion.5": ["loss", "fwd"]}},
              {"module": "jit_traced", "ops": {"while": ["decode_loop", "fwd"], "fusion.8": ["decode_loop/kv_read", "fwd"]}}]
    with open(tmp_path / device_scopes.SCOPES_FILENAME, "w") as f:
        json.dump({"programs": tables}, f)
    assert report.main([str(tmp_path), "--xplane", xplane]) == 0
    rows = [[c.strip() for c in line.split("|")[1:-1]] for line in capsys.readouterr().out.splitlines()
            if line.startswith("| jit_")]
    by_key = {(r[0], r[1], r[2]): float(r[3]) for r in rows}
    assert by_key[("jit_train_step", "lm_head", "bwd")] == pytest.approx(2.6e-2, abs=2e-3)
    assert by_key[("jit_traced", "kv_read", "fwd")] == pytest.approx(7e-3, abs=1e-3)  # milliseconds, under the innermost name
    assert ("jit_traced", "decode_loop", "fwd") not in by_key  # the while itself: a container
    assert by_key[("jit_train_step", "(no scope)", "-")] > 0  # the copies, which the table does not hold
