"""The looped language model's layers (``RMSNorm``, rotary
``SelfAttentionLayer``, ``GatedDenseLayer``, ``LoopedBlockStack``,
``LoopLMOutputLayer``) at tiny widths on the CPU, float32: against the plain
reference (``benchmark/reference/ouro_2_6b.py``), against the same model
unrolled into the package's own layers, and against themselves with the
scan, the checkpoint and the kernels swapped for their plain forms."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import ouro_2_6b as ref
from deeplearning4j_tpu import Adam, NeuralNetConfiguration
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.monitor import get_registry
from deeplearning4j_tpu.nn.conf.graph import (ComputationGraphConfiguration,
                                              ElementWiseVertex)
from deeplearning4j_tpu.nn.conf.layers import (EmbeddingSequenceLayer,
                                               GatedDenseLayer,
                                               LoopedBlockStack,
                                               LoopLMOutputLayer, RMSNorm,
                                               RnnOutputLayer,
                                               SelfAttentionLayer)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import impl_for, looped
from deeplearning4j_tpu.nn.layers.attention import mha, rope
from deeplearning4j_tpu.nn.layers.base import FLASH_RES, NORM_IN
from deeplearning4j_tpu.nn.layers.looped import ATTN_KEYS, FFN_KEYS
from deeplearning4j_tpu.nn.layers.output import exit_distribution
from deeplearning4j_tpu.nn.losses import _reduce, get_loss
from deeplearning4j_tpu.ops import flash_attention as fa

V, D, F, HEADS, HEAD_DIM, L, T = 40, 32, 48, 4, 8, 2, 12
THETA, EPS = 1e6, 1e-6


def _builder(seed=3):
    return (NeuralNetConfiguration.builder().seed(seed)
            .updater(Adam(learning_rate=1e-3)).activation("identity")
            .graph_builder().add_inputs("ids")
            .add_layer("embed", EmbeddingSequenceLayer(n_in=V, n_out=D),
                       "ids"))


def looped_conf(passes, beta=0.05, seed=3):
    return (_builder(seed)
            .add_layer("stack", LoopedBlockStack(
                n_in=D, n_out=D, num_blocks=L, num_passes=passes,
                num_heads=HEADS, head_dim=HEAD_DIM, n_hidden=F, eps=EPS,
                rope_theta=THETA), "embed")
            .add_layer("out", LoopLMOutputLayer(n_in=D, n_out=V,
                                                entropy_weight=beta), "stack")
            .set_outputs("out").build())


def shaken(net, scale=0.2, seed=0):
    """Gains start at 1 and the gate's bias at 0: move every leaf so that
    each takes part."""
    rng = np.random.default_rng(seed)
    net.params = jax.tree_util.tree_map(
        lambda x: x + scale * jnp.asarray(rng.standard_normal(x.shape),
                                          x.dtype), net.params)
    return net


def sample(batch=2, seed=1):
    ids = np.random.default_rng(seed).integers(0, V, (batch, T + 1),
                                               dtype=np.int32)
    return DataSet(np.ascontiguousarray(ids[:, :-1]),
                   np.ascontiguousarray(ids[:, 1:]))


def assert_trees_close(a, b, tol):
    """Leaf by leaf, ||x - y|| <= tol ||y||."""
    la, ta = jax.tree_util.tree_flatten_with_path(a)
    lb, tb = jax.tree_util.tree_flatten_with_path(b)
    assert ta == tb
    for (path, x), (_, y) in zip(la, lb):
        err, norm = float(jnp.linalg.norm(x - y)), float(jnp.linalg.norm(y))
        assert err <= tol * norm, (jax.tree_util.keystr(path), err, norm)


# ------------------------------------------------------- against the reference
@pytest.mark.parametrize("passes", [1, 2, 4])
def test_system_matches_the_reference_leaf_by_leaf(passes):
    net = shaken(ComputationGraph(looped_conf(passes)).init())
    ds = sample()
    grads, loss = net.compute_gradient_and_score(ds)
    ref_loss, ref_grads = jax.value_and_grad(ref.loss)(
        net.params, jnp.asarray(ds.features), jnp.asarray(ds.labels),
        heads=HEADS, passes=passes, theta=THETA, eps=EPS, beta=0.05)
    assert abs(loss - float(ref_loss)) <= 1e-4 * abs(float(ref_loss))
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(ref_grads)):
        if passes == 1 and "['out']['gate_" in jax.tree_util.keystr(path):
            # the one pass takes all: the gate moves nothing, on either side
            assert not np.any(np.asarray(g)) and not np.any(np.asarray(r))
            continue
        err = float(jnp.linalg.norm(g - r) / jnp.linalg.norm(r))
        assert err <= 1e-4, (jax.tree_util.keystr(path), err)


@pytest.mark.parametrize("part", ["rms_norm", "rope", "gated_ffn"])
def test_each_new_layer_against_the_reference(part):
    rng = np.random.default_rng(2)
    gc = looped_conf(1).global_conf
    x = jnp.asarray(rng.standard_normal((2, T, D)), jnp.float32)
    if part == "rms_norm":
        impl = impl_for(RMSNorm(n_in=D, n_out=D, eps=EPS), gc)
        gain = jnp.asarray(rng.standard_normal(D), jnp.float32)
        got, _ = impl.forward({"gain": gain}, {}, x)
        want = ref.rms_norm(x, gain, EPS)
    elif part == "rope":
        q = x.reshape(2, T, HEADS, HEAD_DIM)
        got, want = rope(q, THETA), ref.rope(q, THETA)
        # theta and the pairing, written out for one position and pair
        t, i, half = 5, 1, HEAD_DIM // 2
        a = t * THETA ** (-i / half)
        np.testing.assert_allclose(
            got[0, t, 0, [i, i + half]],
            [q[0, t, 0, i] * np.cos(a) - q[0, t, 0, i + half] * np.sin(a),
             q[0, t, 0, i + half] * np.cos(a) + q[0, t, 0, i] * np.sin(a)],
            rtol=1e-5)
    else:
        impl = impl_for(GatedDenseLayer(n_in=D, n_out=D, n_hidden=F), gc)
        p, _ = impl.init(jax.random.PRNGKey(0))
        assert set(p) == {"Wgate", "Wup", "Wdown"}
        got, _ = impl.forward(p, {}, x)
        want = (jax.nn.silu(x @ p["Wgate"]) * (x @ p["Wup"])) @ p["Wdown"]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_rotary_scores_depend_on_the_distance_only():
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal(HEAD_DIM), jnp.float32)
    k = jnp.asarray(rng.standard_normal(HEAD_DIM), jnp.float32)

    def score(tq, tk):      # the same two vectors placed at tq and tk
        at = lambda v, t: rope(jnp.broadcast_to(v, (1, 24, 1, HEAD_DIM)),
                               100.0)[0, t, 0]
        return float(at(q, tq) @ at(k, tk))

    assert score(9, 4) == pytest.approx(score(20, 15), rel=1e-4)
    assert score(9, 4) == pytest.approx(score(5, 0), rel=1e-4)
    assert abs(score(9, 4) - score(9, 5)) > 1e-3


# --------------------------------------------------- against its own plain forms
def test_one_pass_without_entropy_is_the_plain_next_token_loss():
    net = shaken(ComputationGraph(looped_conf(1, beta=0.0)).init())
    ds = sample()
    states, _ = net.impls["stack"].forward(
        net.params["stack"], {},
        net.impls["embed"].forward(net.params["embed"], {},
                                   jnp.asarray(ds.features))[0], train=True)
    assert states.shape == (1, 2, T, D)
    plain = get_loss("sparse_mcxent")(jnp.asarray(ds.labels),
                                      states[0] @ net.params["out"]["W"],
                                      "softmax", None)
    assert net.score(ds, training=True) == pytest.approx(float(plain),
                                                         rel=1e-6)


def unrolled_conf(passes, seed=3):
    """The same model from the package's single layers: ``passes`` x L
    blocks, vertex by vertex, a final norm after every L, and a plain
    next-token head on the last state."""
    g, prev = _builder(seed), "embed"
    norm = lambda: RMSNorm(n_in=D, n_out=D, eps=EPS)
    for t in range(passes):
        for l in range(L):
            b = f"p{t}b{l}"
            g = (g.add_layer(f"{b}-g1", norm(), prev)
                 .add_layer(f"{b}-attn", SelfAttentionLayer(
                     n_in=D, n_out=D, num_heads=HEADS, head_dim=HEAD_DIM,
                     causal=True, rope_theta=THETA, has_bias=False), f"{b}-g1")
                 .add_layer(f"{b}-g2", norm(), f"{b}-attn")
                 .add_vertex(f"{b}-a", ElementWiseVertex(op="add"), prev,
                             f"{b}-g2")
                 .add_layer(f"{b}-g3", norm(), f"{b}-a")
                 .add_layer(f"{b}-ffn", GatedDenseLayer(n_in=D, n_out=D,
                                                        n_hidden=F), f"{b}-g3")
                 .add_layer(f"{b}-g4", norm(), f"{b}-ffn")
                 .add_vertex(f"{b}-y", ElementWiseVertex(op="add"), f"{b}-a",
                             f"{b}-g4"))
            prev = f"{b}-y"
        g = g.add_layer(f"p{t}-gf", norm(), prev)
        prev = f"p{t}-gf"
    return (g.add_layer("out", RnnOutputLayer(
        n_in=D, n_out=V, activation="softmax", loss="sparse_mcxent",
        has_bias=False), prev).set_outputs("out").build())


def test_looped_stack_is_the_unrolled_graph_with_tied_weights():
    passes = 2
    looped = shaken(ComputationGraph(looped_conf(passes, beta=0.0)).init())
    # a shut gate hands the whole weight to the last pass: the looped loss is
    # then the plain loss of h^R, which the unrolled graph computes
    looped.params["out"]["gate_W"] = jnp.zeros(D)
    looped.params["out"]["gate_b"] = jnp.full((1,), -50.0)
    stack = looped.params["stack"]
    net = ComputationGraph(unrolled_conf(passes)).init()
    net.params["embed"] = looped.params["embed"]
    net.params["out"] = {"W": looped.params["out"]["W"]}
    for t in range(passes):
        for l in range(L):
            b = f"p{t}b{l}"
            for i in (1, 2, 3, 4):
                net.params[f"{b}-g{i}"] = {"gain": stack[f"g{i}"][l]}
            net.params[f"{b}-attn"] = {k: stack[k][l] for k in ATTN_KEYS}
            net.params[f"{b}-ffn"] = {k: stack[k][l] for k in FFN_KEYS}
        net.params[f"p{t}-gf"] = {"gain": stack["gf"]}
    ds = sample()
    grads, loss = looped.compute_gradient_and_score(ds)
    copies, plain = net.compute_gradient_and_score(ds)
    assert loss == pytest.approx(plain, rel=1e-5)
    tied = {k: jnp.stack([sum(copies[f"p{t}b{l}-{v}"][k]
                              for t in range(passes)) for l in range(L)])
            for v, keys in (("attn", ATTN_KEYS), ("ffn", FFN_KEYS))
            for k in keys}
    for i in (1, 2, 3, 4):
        tied[f"g{i}"] = jnp.stack([sum(copies[f"p{t}b{l}-g{i}"]["gain"]
                                       for t in range(passes))
                                   for l in range(L)])
    tied["gf"] = sum(copies[f"p{t}-gf"]["gain"] for t in range(passes))
    assert_trees_close(grads["stack"], tied, 1e-5)
    assert_trees_close(grads["embed"], copies["embed"], 1e-5)
    assert_trees_close(grads["out"]["W"], copies["out"]["W"], 1e-5)


def test_scan_over_stacked_leaves_is_a_loop_over_the_blocks():
    net = shaken(ComputationGraph(looped_conf(3)).init())
    impl, p = net.impls["stack"], net.params["stack"]
    x = jnp.asarray(np.random.default_rng(5).standard_normal((2, T, D)),
                    jnp.float32)
    states, _ = impl.forward(p, {}, x)
    h, want = x, []
    for _ in range(3):
        for l in range(L):
            h = impl.block({k: v[l] for k, v in p.items() if k != "gf"}, h)
        h = ref.rms_norm(h, p["gf"], EPS)
        want.append(h)
    assert_trees_close(states, jnp.stack(want), 1e-5)
    assert len(p) == 12 and all(v.shape[0] == L for k, v in p.items()
                                if k != "gf")
    # each stacked leaf is one draw at the per-block fan-in and fan-out
    drawn = ComputationGraph(looped_conf(3)).init().params["stack"]
    for k in ATTN_KEYS + FFN_KEYS:
        assert float(jnp.std(drawn[k])) == pytest.approx(
            (2 / sum(drawn[k].shape[1:])) ** 0.5, rel=0.1), k
        assert not np.allclose(drawn[k][0], drawn[k][1]), k


# ------------------------------------- one scan over the block applications
def stack_conf(blocks, passes, dtype="float32"):
    """A looped stack alone as a graph's one vertex: 3 blocks at batch 2,
    T 16 in the tests below."""
    conf = (NeuralNetConfiguration.builder().seed(7).activation("identity")
            .graph_builder().add_inputs("x")
            .add_layer("stack", LoopedBlockStack(
                n_in=D, n_out=D, num_blocks=blocks, num_passes=passes,
                num_heads=HEADS, head_dim=HEAD_DIM, n_hidden=F, eps=EPS,
                rope_theta=THETA), "x")
            .set_outputs("stack").build())
    conf.global_conf.compute_dtype = dtype
    return conf


def nested_forward(impl, params, state, x, train=False, rng=None, mask=None,
                   ctx=None):
    """The stack as it ran before it was one scan: a scan over the blocks'
    stacked leaves inside a scan over the passes, the block under a plain
    ``jax.checkpoint``, every gradient by autodiff."""
    stacked = {k: v for k, v in params.items() if k != "gf"}
    block = lambda p, u: impl.block(p, u, mask)
    if train:
        block = jax.checkpoint(block)

    def one_pass(h, _):
        u, _ = jax.lax.scan(lambda u, p: (block(p, u), None), h, stacked)
        h = impl._norm(u, params["gf"])
        return h, h

    _, states = jax.lax.scan(one_pass, x.astype(jnp.float32), None,
                             length=int(impl.conf.num_passes))
    return states, state


def _stack(passes, dtype="float32", blocks=3, t=16):
    """(the stack's layer, its shaken leaves, an input, a cotangent)."""
    conf = stack_conf(blocks, passes, dtype)
    impl = impl_for(conf.vertices["stack"], conf.global_conf, None)
    rng = np.random.default_rng(11)
    params = jax.tree_util.tree_map(
        lambda v: v + 0.2 * jnp.asarray(rng.standard_normal(v.shape), v.dtype),
        impl.init(jax.random.PRNGKey(3))[0])
    x = jnp.asarray(rng.standard_normal((2, t, D)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((passes, 2, t, D)), jnp.float32)
    return impl, params, x, w


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-6),
                                        ("bfloat16", 2.0 ** -8)])
@pytest.mark.parametrize("passes", [2, 1])
def test_the_one_scan_is_the_nested_scans(passes, dtype, tol):
    impl, params, x, w = _stack(passes, dtype)
    assert impl.compute_dtype == jnp.dtype(dtype)
    for train in (False, True):
        flat, _ = jax.jit(lambda p, x: impl.forward(p, {}, x, train=train))(
            params, x)
        nested, _ = jax.jit(lambda p, x: nested_forward(
            impl, p, {}, x, train=train))(params, x)
        assert flat.shape == (passes, 2, 16, D) and flat.dtype == jnp.float32
        np.testing.assert_array_equal(flat, nested)
    loss = lambda forward: lambda p, x: jnp.sum(
        forward(p, {}, x, train=True)[0] * w)
    got = jax.jit(jax.grad(loss(impl.forward), argnums=(0, 1)))(params, x)
    want = jax.jit(jax.grad(loss(lambda *a, **k: nested_forward(impl, *a, **k)),
                            argnums=(0, 1)))(params, x)
    assert all(g.dtype == jnp.float32 for g in jax.tree_util.tree_leaves(got))
    assert_trees_close(got, want, tol)


@pytest.mark.parametrize("passes", [2, 1])
def test_the_one_scan_through_the_containers_loss(passes, monkeypatch):
    ds = sample()
    net = shaken(ComputationGraph(looped_conf(passes)).init())
    loss = lambda p: net._loss_fn(
        p, net.states, [jnp.asarray(ds.features)], [jnp.asarray(ds.labels)],
        None, None, True, None)[0]
    l_flat, g_flat = jax.jit(jax.value_and_grad(loss))(net.params)
    monkeypatch.setattr(type(net.impls["stack"]), "forward", nested_forward)
    l_nested, g_nested = jax.jit(jax.value_and_grad(loss))(net.params)
    assert float(l_flat) == float(l_nested)
    assert_trees_close(g_flat, g_nested, 2e-6)


def _scans(jaxpr, inside=False, found=None):
    """``[(scan equation, whether it stands inside another scan)]`` of
    ``jaxpr``, through every equation that holds a jaxpr of its own."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        scan = eqn.primitive.name == "scan"
        if scan:
            found.append((eqn, inside))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _scans(sub, inside or scan, found)
    return found


def _plain_equations(jaxpr):
    """The equations of ``jaxpr`` that hold no jaxpr of their own, through
    those that do (a call, a checkpoint, a ``cond``'s branches)."""
    for eqn in jaxpr.eqns:
        subs = list(jax.core.jaxprs_in_params(eqn.params))
        if not subs:
            yield eqn
        for sub in subs:
            yield from _plain_equations(sub)


def _stack_grad(impl, w):
    """The gradient of the training stack's weighted output by its leaves
    and its input."""
    return jax.grad(lambda p, x: jnp.sum(
        impl.forward(p, {}, x, train=True)[0] * w), argnums=(0, 1))


@pytest.mark.parametrize("passes", [2, 1])
def test_the_gradient_is_two_scans_and_adds_a_row_at_a_time(passes):
    """One forward and one backward scan of passes x blocks steps, neither
    inside another; in the backward body a value with a whole stacked
    leaf's shape is written by the row update alone: no stack of zeros, no
    whole-stack add, no second stack of gradients."""
    blocks = 3
    impl, params, x, w = _stack(passes)
    scans = _scans(jax.make_jaxpr(_stack_grad(impl, w))(params, x).jaxpr)
    assert [(e.params["length"], e.params["reverse"], inside)
            for e, inside in scans] == [(passes * blocks, False, False),
                                        (passes * blocks, True, False)]
    stacks = {v.shape for k, v in params.items() if k != "gf"}
    assert len(stacks) == 4         # [3, D], [3, D, D], [3, D, F], [3, F, D]
    whole = [e for e in _plain_equations(scans[1][0].params["jaxpr"].jaxpr)
             if any(getattr(v.aval, "shape", None) in stacks
                    for v in e.outvars)]
    assert whole and {e.primitive.name for e in whole} == {
        "dynamic_update_slice"}
    assert len(whole) == 11         # a row into each of the eleven leaves
    # and the forward body holds none at all: the leaves are its constants
    assert not [e for e in _plain_equations(scans[0][0].params["jaxpr"].jaxpr)
                if any(getattr(v.aval, "shape", None) in stacks
                       for v in e.outvars)]


@pytest.mark.parametrize("passes", [2, 1])
def test_a_passs_output_is_read_off_the_kept_stack(passes):
    """Training, a pass's output is what the next pass's first application
    took, and that is kept for the backward sweep anyway: the compiled
    forward loop stacks the stream once, passes x blocks rows of it, and
    carries no result. With nothing kept (inference) the scan carries the
    passes' rows and stacks nothing."""
    blocks = 3
    impl, params, x, w = _stack(passes)
    stream = (passes * blocks,) + x.shape

    def stacked_by(fn):
        scan, = [e for e, _ in _scans(jax.make_jaxpr(fn)(params, x).jaxpr)
                 if not e.params["reverse"]]
        return scan, [v.aval.shape for v in scan.outvars
                      if v.aval.shape == stream]

    grad = _stack_grad(impl, w)
    scan, stacks = stacked_by(grad)
    # the scan's own output and the checkpoint's kept input are one value:
    # the compiled loops carry it once (forward written, backward read),
    # beside the FFN's output, kept for the norm that reads it and, in
    # float32, as large
    assert stacks.count(stream) == 3
    text = jax.jit(grad).lower(params, x).compile().as_text()
    carried = [re.findall(r"f32\[%s\]" % ",".join(map(str, stream)), m)
               for m in re.findall(r" = \((.*?)\) while\(", text)]
    assert [len(c) for c in carried if c] == [2, 2]
    assert (passes,) + x.shape not in [v.aval.shape for v in scan.outvars]
    scan, stacks = stacked_by(lambda p, x: impl.forward(p, {}, x)[0])
    assert stacks == []
    assert (passes,) + x.shape in [v.aval.shape for v in scan.outvars]


def _keep_the_flash_residuals_alone(monkeypatch):
    """The stack's blocks under the checkpoint as it was before a norm's
    input had a name: the policy keeps the flash kernels' residuals only."""
    policy = jax.checkpoint_policies.save_only_these_names(FLASH_RES)
    monkeypatch.setattr(looped, "block_checkpoint",
                        lambda block: jax.checkpoint(block, policy=policy))


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-6),
                                        ("bfloat16", 2.0 ** -8)])
def test_the_checkpoint_keeps_what_the_last_norm_reads(dtype, tol,
                                                       monkeypatch):
    """A block names the FFN's output, which its last norm reads: the
    forward scan stacks one more value like the stream, in the compute
    dtype, and the backward scan's body holds one product fewer (the
    down-projection, which nothing but that norm's backward would read)
    than under a policy without the name; the gradients are the same."""
    blocks, passes = 3, 2
    impl, params, x, w = _stack(passes, dtype)
    stream = (passes * blocks,) + x.shape

    def read():
        grad = _stack_grad(impl, w)
        forward, backward = (e for e, _ in _scans(
            jax.make_jaxpr(grad)(params, x).jaxpr))
        products = sum(e.primitive.name == "dot_general" for e in
                       _plain_equations(backward.params["jaxpr"].jaxpr))
        kept = sorted(str(v.aval.dtype) for v in forward.outvars
                      if v.aval.shape == stream)
        return products, kept, jax.jit(grad)(params, x)

    products, kept, got = read()
    _keep_the_flash_residuals_alone(monkeypatch)
    products_before, kept_before, want = read()
    assert products == products_before - 1
    assert kept_before == ["float32", "float32"]    # scan's output, input
    assert kept == sorted(kept_before + [dtype])
    assert_trees_close(got, want, tol)


@pytest.mark.parametrize("flash", [False, True], ids=["dense", "flash"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_an_eager_gradient_keeps_three_things_an_application(dtype, flash,
                                                             monkeypatch):
    """Differentiated eagerly (the benchmark's reference check does), nothing
    is dropped as dead code: what the stack keeps once an application is its
    input, the named input of the last norm and, where a kernel runs, the
    flash kernels' residuals; every other residual is a constant of the
    scan, kept once (no carried value once an application)."""
    monkeypatch.setattr(fa, "_FORCE_INTERPRET", flash)
    blocks, passes, t = 3, 2, 2 * fa.MIN_BLOCK if flash else 16
    impl, params, x, _ = _stack(passes, dtype, t=t)
    _, back = jax.vjp(lambda p, x: impl.forward(p, {}, x, train=True)[0],
                      params, x)
    n = passes * blocks
    kept = sorted((str(v.dtype), v.shape[1:])
                  for v in jax.tree_util.tree_leaves(back)
                  if getattr(v, "ndim", 0) > 1 and v.shape[0] == n)
    heads = (x.shape[0] * HEADS, t)
    want = [("float32", x.shape), (dtype, x.shape)]
    if flash:       # q, k, v, o and a lane of the log-sum-exp
        want += [(dtype, heads + (HEAD_DIM,))] * 4 + [("float32", heads)]
    assert kept == sorted(want)


def test_the_scan_steps_gauge_reads_sixteen_at_four_by_four(monkeypatch):
    import deeplearning4j_tpu.monitor.registry as registry
    monkeypatch.setattr(registry, "_REGISTRY", registry.MetricsRegistry())
    conf = (_builder()
            .add_layer("stack", LoopedBlockStack(
                n_in=D, n_out=D, num_blocks=4, num_passes=4, num_heads=HEADS,
                head_dim=HEAD_DIM, n_hidden=F, eps=EPS, rope_theta=THETA),
                "embed")
            .add_layer("out", LoopLMOutputLayer(n_in=D, n_out=V,
                                                entropy_weight=0.05), "stack")
            .set_outputs("out").build())
    net = ComputationGraph(conf).init()
    net.fit(sample())
    net.fit(sample(seed=2))
    snap = get_registry().snapshot()
    assert [(r["labels"], r["value"]) for r in snap["looped_scan_steps"]] \
        == [({"layer": "stack"}, 16)]
    assert [r["value"] for r in snap["looped_block_applications"]
            if r["labels"] == {"network": "cg"}] == [16]
    # one named norm input an application, like the stream in float32
    assert [(r["labels"], r["value"])
            for r in snap["looped_kept_norm_input_bytes"]] \
        == [({"layer": "stack"}, 16 * 2 * T * D * 4)]


def test_block_checkpoint_changes_no_number(monkeypatch):
    ds = sample()
    on = shaken(ComputationGraph(looped_conf(2)).init())
    g_on, l_on = on.compute_gradient_and_score(ds)
    # the checkpoint is there: the recomputed forward is in the backward
    text = str(jax.make_jaxpr(lambda p: on._loss_fn(
        p, on.states, [jnp.asarray(ds.features)], [jnp.asarray(ds.labels)],
        None, None, True, None)[0])(on.params))
    assert "checkpoint" in text or "remat" in text
    assert NORM_IN in text          # and a block named its last norm's input
    # and without it every number is the same
    monkeypatch.setattr(jax, "checkpoint", lambda f, **kw: f)
    off = shaken(ComputationGraph(looped_conf(2)).init())
    text = str(jax.make_jaxpr(lambda p: off._loss_fn(
        p, off.states, [jnp.asarray(ds.features)], [jnp.asarray(ds.labels)],
        None, None, True, None)[0])(off.params))
    assert "checkpoint" not in text and "remat" not in text
    g_off, l_off = off.compute_gradient_and_score(ds)
    assert l_on == pytest.approx(l_off, rel=1e-6)
    assert_trees_close(g_on, g_off, 1e-6)


def test_exit_distribution_sums_to_one_and_the_last_pass_takes_the_rest():
    z = jnp.asarray(np.random.default_rng(6).standard_normal((4, 3, 5)) * 3,
                    jnp.float32)
    p, log_p = exit_distribution(z)
    lam = jax.nn.sigmoid(z)
    np.testing.assert_allclose(p.sum(0), 1.0, rtol=1e-6)
    np.testing.assert_allclose(p[0], lam[0], rtol=1e-6)
    np.testing.assert_allclose(p[-1], jnp.prod(1 - lam[:-1], axis=0),
                               rtol=1e-4)
    np.testing.assert_allclose(p, ref.exit_distribution(lam), rtol=1e-4,
                               atol=1e-7)
    np.testing.assert_allclose(jnp.exp(log_p), p, rtol=1e-6)
    # one pass exits with certainty, a saturated gate gives no NaN
    np.testing.assert_array_equal(exit_distribution(z[:1])[0], 1.0)
    sat, log_sat = exit_distribution(jnp.asarray([[200.0], [0.0], [0.0]]))
    assert np.isfinite(np.asarray(sat * log_sat)).all()


# ----------------------------------------------------------------- attention
@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(fa, "_FORCE_INTERPRET", True)


def test_rotary_attention_through_the_flash_kernel_matches_dense(interpret,
                                                                 monkeypatch):
    rng = np.random.default_rng(7)
    Tk = 2 * fa.MIN_BLOCK
    gc = looped_conf(1).global_conf
    impl = impl_for(SelfAttentionLayer(
        n_in=D, n_out=D, num_heads=2, head_dim=16, causal=True,
        rope_theta=THETA, has_bias=False), gc)
    p, _ = impl.init(jax.random.PRNGKey(1))
    x = jnp.asarray(rng.standard_normal((1, Tk, D)), jnp.float32)
    assert fa.supported(Tk, 16, 0.0, None)
    flash, _ = impl.forward(p, {}, x)
    monkeypatch.setattr(fa, "_FORCE_INTERPRET", False)   # the dense path
    assert not fa.supported(Tk, 16, 0.0, None)
    dense, _ = impl.forward(p, {}, x)
    np.testing.assert_allclose(flash, dense, rtol=2e-4, atol=2e-5)


def test_attention_without_rotary_and_with_bias_is_bit_equal_to_before():
    rng = np.random.default_rng(8)
    gc = looped_conf(1).global_conf
    conf = SelfAttentionLayer(n_in=D, n_out=D, num_heads=HEADS, causal=True)
    assert conf.rope_theta is None and conf.has_bias is True
    impl = impl_for(conf, gc)
    p, _ = impl.init(jax.random.PRNGKey(2))
    assert set(p) == {"Wq", "Wk", "Wv", "Wo", "b"}
    p["b"] = jnp.asarray(rng.standard_normal(D), jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, T, D)), jnp.float32)
    got, _ = impl.forward(p, {}, x)

    def before(p, x):       # the forward as it stood before this layer grew
        b, t, _ = x.shape
        q, k, v = ((x @ p[w].astype(x.dtype)).reshape(b, t, HEADS, D // HEADS)
                   for w in ("Wq", "Wk", "Wv"))
        o = mha(q, k, v, True, jnp.float32, 0.0, None, False, key_mask=None)
        o = o.reshape(b, t, D)
        return o @ p["Wo"].astype(o.dtype) + p["b"].astype(o.dtype)

    np.testing.assert_array_equal(got, before(p, x))


def test_rotary_streaming_matches_the_full_sequence():
    conf = (_builder()
            .add_layer("attn", SelfAttentionLayer(
                n_in=D, n_out=D, num_heads=HEADS, causal=True,
                rope_theta=100.0, has_bias=False, stream_max_length=T),
                "embed")
            .add_layer("out", RnnOutputLayer(n_in=D, n_out=V,
                                             activation="softmax",
                                             loss="sparse_mcxent"), "attn")
            .set_outputs("out").build())
    net = ComputationGraph(conf).init()
    ids = sample().features
    full = net.output(ids)
    net.rnn_clear_previous_state()
    parts = [net.rnn_time_step(ids[:, s:s + 4, None])
             for s in range(0, T, 4)]
    np.testing.assert_allclose(jnp.concatenate(parts, axis=1), full,
                               rtol=1e-4, atol=1e-6)


# -------------------------------------------------------- through the container
def test_trains_through_fit_and_round_trips_through_json():
    conf = looped_conf(2)
    again = ComputationGraphConfiguration.from_json(conf.to_json())
    assert again.to_json() == conf.to_json()
    assert isinstance(again.vertices["stack"], LoopedBlockStack)
    assert again.vertices["out"].entropy_weight == 0.05
    net = ComputationGraph(again).init()
    ds = sample()
    first = net.score(ds, training=True)
    for _ in range(5):
        net.fit(ds)
    assert float(net.score_) < first
    assert net.output(ds.features).shape == (2, T, V)
    np.testing.assert_allclose(net.output(ds.features).sum(-1), 1.0,
                               rtol=1e-5)
    gauge = get_registry().snapshot()["looped_block_applications"]
    assert [r["value"] for r in gauge if r["labels"] == {"network": "cg"}] \
        == [2 * L]
    # the head's rule forms a pass's logits once in a differentiated step
    gauge = get_registry().snapshot()["looped_head_logits_per_pass"]
    assert [r["value"] for r in gauge if r["labels"] == {"layer": "out"}] \
        == [1]


def test_the_looped_output_layer_takes_no_other_loss():
    conf = looped_conf(2)
    conf.vertices["out"].loss = "mse"
    with pytest.raises(ValueError, match="no other loss"):
        ComputationGraph(conf).init()


def test_a_sequence_mask_reaches_the_looped_loss():
    net = shaken(ComputationGraph(looped_conf(2)).init())
    ds = sample()
    mask = np.ones((2, T), np.float32)
    mask[:, T // 2:] = 0
    masked = DataSet(ds.features, ds.labels, labels_mask=mask)
    half = DataSet(ds.features[:, :T // 2], ds.labels[:, :T // 2])
    # causal: the first half's loss does not see the second half
    assert net.score(masked, training=True) == pytest.approx(
        net.score(half, training=True), rel=1e-5)


# ------------------------------------------------- the head's own rule
def _head(passes, bias, seed=9):
    """A looped output layer alone, shaken, with states and labels for it."""
    rng = np.random.default_rng(seed)
    impl = impl_for(LoopLMOutputLayer(n_in=D, n_out=V, has_bias=bias,
                                      entropy_weight=0.05),
                    looped_conf(passes).global_conf)
    params, _ = impl.init(jax.random.PRNGKey(seed))
    params = jax.tree_util.tree_map(
        lambda a: a + 0.3 * jnp.asarray(rng.standard_normal(a.shape), a.dtype),
        params)
    x = jnp.asarray(rng.standard_normal((passes, 2, T, D)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, (2, T)), jnp.int32)
    return impl, params, x, labels


def plain_looped_loss(params, x, labels, mask, beta=0.05):
    """The layer's loss written out for plain AD: every pass's logits at
    once, no scan, no rule."""
    z = jnp.einsum("rbtd,dv->rbtv", x, params["W"]) + params.get("b", 0.0)
    xent = jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(
        z, jnp.broadcast_to(labels, z.shape[:-1])[..., None], axis=-1)[..., 0]
    p, log_p = exit_distribution(
        jnp.einsum("rbtd,d->rbt", x, params["gate_W"]) + params["gate_b"])
    per_token = jnp.sum(p * xent, axis=0) + beta * jnp.sum(p * log_p, axis=0)
    return _reduce(per_token[..., None], mask)


def _vocabulary_products(jaxpr):
    """``dot_general``s with a vocabulary axis among their operands' or
    their result's, in ``jaxpr`` and every jaxpr below it (a scan's body
    counts once: per pass)."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and any(
                V in v.aval.shape for v in eqn.invars + eqn.outvars):
            n += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _vocabulary_products(sub)
    return n


@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
@pytest.mark.parametrize("masked", [False, True], ids=["no_mask", "mask"])
@pytest.mark.parametrize("passes", [1, 4])
def test_the_heads_rule_gives_what_plain_ad_gives(passes, masked, bias):
    impl, params, x, labels = _head(passes, bias)
    mask = None
    if masked:
        mask = jnp.asarray(np.random.default_rng(1).random((2, T)) < 0.6,
                           jnp.float32)
    assert ("b" in params) == bias
    loss, grads = jax.value_and_grad(
        lambda p, x: impl.loss_on(p, {}, x, labels, mask=mask), (0, 1))(
            params, x)
    want, want_grads = jax.value_and_grad(
        lambda p, x: plain_looped_loss(p, x, labels, mask), (0, 1))(params, x)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    if passes == 1:     # the one pass takes all: the gate moves nothing
        for g in (grads, want_grads):
            assert not np.any(g[0].pop("gate_W")) \
                and not np.any(g[0].pop("gate_b"))
    assert_trees_close(grads, want_grads, 1e-5)
    # undifferentiated (``score``) it is the same number
    assert float(impl.loss_on(params, {}, x, labels, mask=mask,
                              train=False)) == pytest.approx(float(want),
                                                             rel=1e-5)


@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
def test_a_passs_logits_are_formed_once(bias):
    impl, params, x, labels = _head(4, bias)
    loss = lambda p, x: impl.loss_on(p, {}, x, labels)
    # score: the logits' product and nothing of the gradient
    assert _vocabulary_products(jax.make_jaxpr(loss)(params, x).jaxpr) == 1
    # differentiated: the logits' product and the two gradient products,
    # once each in the one scan over the passes ...
    both = jax.make_jaxpr(jax.grad(loss, (0, 1)))(params, x)
    assert _vocabulary_products(both.jaxpr) == 3
    assert "checkpoint" not in str(both) and "remat" not in str(both)
    # ... all three in the forward sweep: the backward one scales
    _, backward = jax.vjp(loss, params, x)
    swept = jax.make_jaxpr(backward)(jnp.float32(1.0))
    assert _vocabulary_products(swept.jaxpr) == 0
    assert "dot_general" in str(swept) and "while" not in str(swept) \
        and "scan" not in str(swept)


def test_the_scopes_name_the_ops_of_the_step():
    net = ComputationGraph(looped_conf(2)).init()
    ds = sample()
    text = jax.jit(net._raw_step(False)).lower(
        net.params, net.states, net.updater_state, jnp.int32(0),
        net._next_rng(), (jnp.asarray(ds.features),),
        (jnp.asarray(ds.labels),), None, None).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))

    def named(*parts):      # an op whose op_name holds the parts in order
        pattern = re.compile(".*".join(re.escape(p) for p in parts))
        return any(pattern.search(n) for n in names)

    for sub in ("attn", "ffn"):
        assert named("jvp(stack)/while/body/", "/blocks/", sub), sub
        assert named("transpose(jvp(stack))/while/body/", "/blocks/", sub), sub
        # the recomputed forward, inside the backward pass
        assert named("transpose(jvp(stack))/while/body/",
                     "/rematted_computation", "/blocks/", sub), sub
    # one loop forward and one backward, the scopes inside its body
    assert not named("jvp(stack)", "/while/", "/while/")
    assert named("jvp(stack)/", "/final_norm")
    # a weight's gradient is added to its row of the carried sum in `blocks`
    assert named("transpose(jvp(stack))/", "/blocks/dynamic_update_slice")
    assert named("jvp(loss)/", "exit_gate")
    assert named("transpose(jvp(loss))/", "exit_gate")
    # the head's rule runs both of its sweeps where the loss runs forward:
    # the logits' product, and the two gradient products under a
    # ``transpose(`` of their own, which the scope readers take for backward
    assert named("jvp(loss)/", "head/", "/jvp()/dot_general")
    assert named("jvp(loss)/", "head/", "/transpose(jvp())/dot_general")
    # nothing of a pass is formed again, and where the loss's cotangent is
    # the constant 1 the compiler drops the backward rule's scalings whole
    assert not named("head/", "rematted_computation")
    assert not named("transpose(jvp(loss))/", "head/")
