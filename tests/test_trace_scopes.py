"""The train step names its phases in the device trace: every
`jax.named_scope` of `trace.py`'s list is a component of the `op_name`s of
the compiled step, in each pass its phase runs in, and comes out of the
benchmark's one rule for a path (`benchmark/tracered.region_of`) as the
module its metric matches; and the persistent compile cache, through
`util.enable_compile_cache`, cannot hand one program another's names.  CPU,
toy widths; the Pallas kernels run in the interpreter."""
import json
import os
import re
import subprocess
import sys

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import tracered  # noqa: E402

from tensorflowonspark_tpu.optim import make_optimizer  # noqa: E402
from tensorflowonspark_tpu.parallel.train import (  # noqa: E402
    TrainState, make_train_step)

MOE_SCOPES = ("route", "dispatch", "experts", "combine", "cast")
SCOPES = MOE_SCOPES + ("unembed_xent", "lm_loss", "optimizer", "stem")


def op_names(text):
    return set(re.findall(r'op_name="([^"]+)"', text))


def regions(names):
    """`{(pass, module)}` of some `op_name`s, by the benchmark's rule."""
    return {tracered.region_of(n + ":") for n in names}


def compiled_step(loss_fn, optimizer, params, batch):
    """`(op_names of the compiled step, of its lowering)`: the second holds
    every name the program gave, the first those XLA kept (it merges equal
    operations and keeps one's name)."""
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt_state=optimizer.init(params))
    lowered = make_train_step(loss_fn, optimizer, donate=False).lower(
        state, batch, None)
    return (op_names(lowered.compile().as_text()),
            set(re.findall(r'loc\("([^"]+)"', lowered.as_text(
                debug_info=True))))


@pytest.fixture(scope="module")
def moe_step():
    """A two-layer dropless-MoE transformer (top-2 of 4 experts, 2 held,
    gated) under `remat=True`, the head fused into the loss, fused AdamW."""
    from tensorflowonspark_tpu.models.transformer import (
        Transformer, TransformerConfig)
    from tensorflowonspark_tpu.ops.xent import fused_unembed_xent

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_seq_len=16, dtype="bfloat16", rope=True, num_experts=4,
        moe_every=1, moe_router="dropless", moe_top_k=2, moe_d_ff=16,
        moe_experts_held=2, use_bias=False, activation="silu",
        norm_type="rmsnorm", mlp_style="gated", attention_impl="dense",
        remat=True)
    model = Transformer(cfg)

    def loss_fn(p, batch, rng):
        hidden = model.apply({"params": p}, batch[:, :-1],
                             return_hidden=True)
        return fused_unembed_xent(
            hidden, p["lm_head"]["kernel"].astype(jnp.bfloat16),
            batch[:, 1:], 8)

    batch = jnp.zeros((2, 17), jnp.int32)
    params = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                          batch[:, :-1])["params"]))
    opt, _ = make_optimizer("adamw_fused", learning_rate=1e-3)
    return compiled_step(loss_fn, opt, params, batch)


@pytest.fixture(scope="module")
def resnet_step():
    """One bottleneck block behind the ImageNet stem, SGD through optax:
    the step's other optimizer branch."""
    from tensorflowonspark_tpu.models.resnet import ResNet

    model = ResNet(stage_sizes=(1,), num_classes=10, num_filters=8,
                   bottleneck=True, norm="group", dtype="bfloat16")

    def loss_fn(p, batch, rng):
        pixels, labels = batch
        logits = model.apply({"params": p}, pixels).astype(jnp.float32)
        return -jnp.mean(jnp.sum(jax.nn.log_softmax(logits)
                                 * jax.nn.one_hot(labels, 10), axis=-1))

    batch = (jnp.zeros((2, 32, 32, 3), jnp.bfloat16),
             jnp.zeros((2,), jnp.int32))
    params = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                          batch[0])["params"]))
    opt, _ = make_optimizer("sgd", learning_rate=0.1, momentum=0.9)
    return compiled_step(loss_fn, opt, params, batch)


# the recomputed block never reruns `combine`: nothing of the backward
# needs its output (its rule keeps the operands)
MOE_CASES = [(scope, which) for scope in MOE_SCOPES
             for which in ("forward", "backward", "recomputed")
             if (scope, which) != ("combine", "recomputed")]


@pytest.mark.parametrize("scope,which", MOE_CASES)
def test_expert_layer_phase_is_a_module_of_its_own(moe_step, scope, which):
    compiled, lowered = moe_step
    # XLA merges the forward cast with the recomputed one and keeps the
    # latter's name: the program's own names are those of the lowering
    names = lowered if (scope, which) == ("cast", "forward") else compiled
    assert (which, f"layer/moe/{scope}") in regions(names)


def test_recomputed_block_holds_no_combine(moe_step):
    assert ("recomputed", "layer/moe/combine") not in regions(moe_step[1])


def test_routers_product_keeps_its_own_module(moe_step):
    assert {("forward", "layer/moe/router"),
            ("backward", "layer/moe/router")} <= regions(moe_step[0])


@pytest.mark.parametrize("which", ["forward", "backward"])
def test_fused_head_and_loss_is_one_named_region(moe_step, which):
    """Called at the top of the differentiated function, the scope would
    read `jvp(unembed_xent)`, a marker: `trace.loss_scope` enters it
    twice."""
    compiled, _ = moe_step
    assert (which, "unembed_xent") in regions(compiled)
    # the loss's loop is under it, not under no module
    assert not any(tracered.region_of(n + ":")[1] == "-"
                   for n in compiled if "/while/" in n)


@pytest.mark.parametrize("which", ["forward", "backward"])
def test_lm_loss_is_a_named_region(which):
    from tensorflowonspark_tpu.models.transformer import lm_loss

    targets = jnp.zeros((2, 8), jnp.int32)
    text = jax.jit(jax.grad(lambda x: lm_loss(x, targets))).lower(
        jnp.zeros((2, 8, 16), jnp.bfloat16)).compile().as_text()
    assert (which, "lm_loss") in regions(op_names(text))


def test_optimizer_scope_holds_the_fused_kernels_and_the_norm(moe_step):
    found = regions(moe_step[0])
    assert ("rest", "optimizer/adamw_fused") in found
    assert ("rest", "optimizer") in found       # the global-norm pass
    # the norm of the step's metrics too: outside the differentiated
    # function only the step counter's increment is left unnamed
    assert {n for n in moe_step[0] if n.startswith("jit(_step)/")
            and tracered.region_of(n + ":") == ("rest", "-")} <= {
                "jit(_step)/add"}


def test_optimizer_scope_holds_the_optax_branch(resnet_step):
    compiled, _ = resnet_step
    assert ("rest", "optimizer") in regions(compiled)
    assert not any(m.startswith("optimizer")
                   for which, m in regions(compiled) if which != "rest")


@pytest.mark.parametrize("which", ["forward", "backward"])
def test_resnet_stem_is_a_named_region(resnet_step, which):
    compiled, _ = resnet_step
    assert (which, "stem") in regions(compiled)
    # the convolution and its norm keep their modules
    assert {"conv_init", "norm_init/gn"} <= {m for _, m in regions(compiled)}


@pytest.mark.parametrize("scope", SCOPES)
def test_scope_name_is_a_plain_word(scope):
    """No dot, no parentheses, none of JAX's markers: `region_of` keeps it
    as a component, and `trace.py` lists it."""
    from tensorflowonspark_tpu import trace

    assert re.fullmatch(r"[a-z_]+", scope)
    assert not tracered.WRAPPER.match(scope)
    assert tracered.region_of(f"jit(_step)/{scope}/add:") == ("rest", scope)
    assert re.search(rf"\b{scope}\b", trace.__doc__)


# ---- the persistent cache cannot serve another program's names -----------

CACHE_SCRIPT = r"""
import json, os, re, sys
import jax, jax.numpy as jnp
from tensorflowonspark_tpu import util

util.enable_compile_cache()
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    # the rule's own directory is the checkout's: a test writes elsewhere
    jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
events = []
jax.monitoring.register_event_listener(lambda e, **kw: events.append(e))


def scoped(name):
    def f(x):
        with jax.named_scope(name):
            return jnp.sin(x) * 2
    return f


x = jnp.ones((8,))
for name in sys.argv[2:]:
    del events[:]
    text = jax.jit(scoped(name)).lower(x).compile().as_text()
    print(json.dumps({
        "name": name,
        "hits": events.count("/jax/compilation_cache/cache_hits"),
        "misses": events.count("/jax/compilation_cache/cache_misses"),
        "scopes": sorted({m.split("/")[1] for m in re.findall(
            r'op_name="(jit\(f\)/[^"]+)"', text)})}))
"""


@pytest.mark.parametrize("placed", ["from_outside", "by_the_rule"])
def test_cache_tells_two_scope_names_apart(tmp_path, placed):
    """Two functions that differ in a scope's name alone, compiled into
    one cache directory: JAX's default key strips locations, the second
    function hits the first's entry and its executable says `alpha` under
    a function that says `beta`.  Under the rule the second misses and
    holds its own name; a repeat, in another process, hits that."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    if placed == "from_outside":
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)

    def run(*names):
        p = subprocess.run(
            [sys.executable, "-c", CACHE_SCRIPT, str(tmp_path), *names],
            env=env, capture_output=True, text=True, timeout=180)
        assert p.returncode == 0, p.stderr[-2000:]
        return [json.loads(line) for line in p.stdout.splitlines()
                if line.startswith("{")]

    first, second = run("alpha", "beta")
    assert (first["misses"], first["scopes"]) == (1, ["alpha"])
    assert (second["hits"], second["misses"]) == (0, 1)
    assert second["scopes"] == ["beta"]
    again, = run("beta")
    assert (again["hits"], again["misses"]) == (1, 0)
    assert again["scopes"] == ["beta"]
