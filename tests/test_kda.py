"""The Kimi Delta Attention mixer (``nn/layers/kda.py``): the chunked delta
rule against the recurrence token by token (loss and every gradient, several
chunk lengths, lengths that fill no chunk, strong decays, several segments),
the in-chunk matrices kept by name across the block stacks' checkpoint (the
same gradients bit for bit, one formation of the weights and one of the
inverse fewer), and the layer against its equations written out."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers import kda


def recurrence(q, k, v, g, beta):
    """``S_t = Diag(exp(g_t)) S_{t-1}``, ``S_t += beta_t k_t (v_t - S_t^T
    k_t)^T``, ``o_t = S_t^T q_t``, one step a token."""
    b, T, H, K = k.shape

    def step(S, at_t):
        q_t, k_t, v_t, g_t, b_t = at_t
        S = jnp.exp(g_t)[..., None] * S
        seen = jnp.einsum("bhkv,bhk->bhv", S, k_t)
        S = S + jnp.einsum("bhk,bhv->bhkv", b_t[..., None] * k_t, v_t - seen)
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((b, H, K, v.shape[-1]), k.dtype),
                        tuple(jnp.moveaxis(t, 1, 0)
                              for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _inputs(T, decay, seed=0, b=2, H=2, K=8):
    rng = np.random.default_rng(seed)
    unit = lambda t: t / np.linalg.norm(t, axis=-1, keepdims=True)
    q, k, v, w = (rng.normal(size=(b, T, H, K)) for _ in range(4))
    g = -decay * rng.uniform(0.0, 1.0, size=(b, T, H, K))
    beta = rng.uniform(0.0, 1.0, size=(b, T, H))
    return tuple(jnp.asarray(t, jnp.float32) for t in (
        unit(q) * K ** -0.5, unit(k), v, g, beta, w))


@pytest.mark.parametrize("chunk,T,decay", [
    (8, 40, 1.0),        # one block a chunk: the differences alone
    (16, 50, 0.05),      # a length that fills no chunk: padded
    (32, 96, 1.0),       # two blocks a chunk: one through its start
    (64, 150, 1.0),      # the family's chunk, four blocks
    (64, 128, 6.0),      # decays whose inverse overflows float32 in a chunk
    (48, 100, 1.0),      # a chunk that 16 does not divide (blocks of 16 = gcd)
])
def test_the_chunked_rule_is_the_recurrence(chunk, T, decay):
    *args, w = _inputs(T, decay)
    chunked = lambda *a: jnp.sum(
        kda.delta_rule_chunked(*a, chunk, jnp.float32) * w)
    stepwise = lambda *a: jnp.sum(recurrence(*a) * w)
    got, grads = jax.value_and_grad(chunked, (0, 1, 2, 3, 4))(*args)
    want, ref = jax.value_and_grad(stepwise, (0, 1, 2, 3, 4))(*args)
    assert float(got) == pytest.approx(float(want), rel=1e-4)
    for a, r in zip(grads, ref):
        assert float(jnp.linalg.norm(a - r) / jnp.linalg.norm(r)) < 1e-4


def test_the_state_crosses_segments_as_it_crosses_chunks(monkeypatch):
    """Five chunks in segments of two (one padded with a whole chunk): the
    checkpointed walk over segments hands the state on."""
    monkeypatch.setattr(kda, "SEGMENT_CHUNKS", 2)
    *args, w = _inputs(70, 1.0, seed=2)
    chunked = lambda *a: jnp.sum(
        kda.delta_rule_chunked(*a, 16, jnp.float32) * w)
    stepwise = lambda *a: jnp.sum(recurrence(*a) * w)
    got, grads = jax.value_and_grad(chunked, (0, 1, 2, 3, 4))(*args)
    want, ref = jax.value_and_grad(stepwise, (0, 1, 2, 3, 4))(*args)
    assert float(got) == pytest.approx(float(want), rel=1e-4)
    for a, r in zip(grads, ref):
        assert float(jnp.linalg.norm(a - r) / jnp.linalg.norm(r)) < 1e-4


def test_a_state_that_is_not_carried_shows(monkeypatch):
    """The fault the benchmark plants: every chunk starting from nought is
    another function."""
    *args, w = _inputs(64, 0.05, seed=3)
    sound = kda.delta_rule_chunked(*args, 16, jnp.float32)
    carried = kda.carried_states

    def forgetful(S, *chunks):
        out = [carried(jnp.zeros_like(S), *(c[i:i + 1] for c in chunks[:-1]),
                       chunks[-1]) for i in range(chunks[0].shape[0])]
        return out[-1][0], tuple(jnp.concatenate([o[1][j] for o in out])
                                 for j in range(2))

    monkeypatch.setattr(kda, "carried_states", forgetful)
    cut = kda.delta_rule_chunked(*args, 16, jnp.float32)
    assert float(jnp.linalg.norm(cut - sound) / jnp.linalg.norm(sound)) > 0.1
    assert np.allclose(np.asarray(cut[:, :16]), np.asarray(sound[:, :16]),
                       atol=1e-5)


def dense_within_block(left, right, G):
    """The diagonal blocks as they were formed before the bands: all m x m
    pairs of a block for every channel, the ones above the diagonal sent
    through ``exp(-inf)`` to nought."""
    m = G.shape[-2]
    seen = jnp.tril(jnp.ones((m, m), bool))[:, :, None]
    weights = jnp.exp(jnp.where(
        seen, G[..., :, None, :] - G[..., None, :, :], -jnp.inf))
    return jnp.sum(left[..., :, None, :] * right[..., None, :, :] * weights,
                   axis=-1)


def _block_inputs(m, decay, seed=4, lead=(2, 3), K=32):
    rng = np.random.default_rng(seed)
    left, right = (rng.normal(size=lead + (m, K)) for _ in range(2))
    G = np.cumsum(-decay * rng.uniform(size=lead + (m, K)), axis=-2)
    d_out = rng.normal(size=lead + (m, m))          # the upper triangle too
    return tuple(jnp.asarray(t, jnp.float32) for t in (left, right, G, d_out))


@pytest.mark.parametrize("m", [8, 16])
@pytest.mark.parametrize("decay", [1.0, 6.0])
def test_the_bands_are_the_dense_masked_form(m, decay):
    """``within_block`` against every pair formed and the upper triangle
    masked: the output bit for bit, the three cotangents to float32
    rounding, and a cotangent above the diagonal changes nothing."""
    left, right, G, d_out = _block_inputs(m, decay)
    got, vjp = jax.vjp(kda.within_block, left, right, G)
    want, ref_vjp = jax.vjp(dense_within_block, left, right, G)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    grads = vjp(d_out)
    for a, r in zip(grads, ref_vjp(d_out)):
        scale = float(jnp.max(jnp.abs(r)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=1e-5,
                                   atol=1e-6 * scale)
    below = vjp(jnp.tril(d_out))
    for a, b in zip(grads, below):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("m", [8, 16])
def test_no_weights_above_the_bands_are_formed(m):
    """No value of ``within_block``'s forward or of its rule has the dense
    form's trailing [m, m, K]; forward, every ``exp`` together forms the
    weights of ``block_pairs(m)`` pairs a block and channel, three quarters
    of the block; the rule adds one pass over all m x m, in which each pair
    below the diagonal is formed once for each cotangent that reads it
    (the masked form formed all of them twice more)."""
    left, right, G, d_out = _block_inputs(m, 1.0)
    lead, K = G.shape[:-2], G.shape[-1]
    forward = jax.make_jaxpr(kda.within_block)(left, right, G)
    backward = jax.make_jaxpr(
        lambda *a: jax.vjp(kda.within_block, *a[:3])[1](a[3]))(
        left, right, G, d_out)
    n = K * int(np.prod(lead))
    for program, pairs in ((forward, kda.block_pairs(m)),
                           (backward, kda.block_pairs(m) + m * m)):
        formed = 0
        for eqn, trips in _equations(program.jaxpr):
            for var in eqn.outvars:
                assert tuple(var.aval.shape[-3:]) != (m, m, K), eqn
            if eqn.primitive.name == "exp":
                formed += trips * eqn.outvars[0].aval.size
        assert formed == pairs * n
    assert kda.block_pairs(16) == 192 and kda.block_pairs(8) == 48


def _under_the_block_checkpoint(chunk, T, decay, compute_dtype=jnp.float32):
    """(the rule's loss and gradients on its five inputs through
    ``base.block_checkpoint``, as a function of them; the inputs). A fresh
    function a call: jax caches a function's trace, and the policy it was
    traced under with it."""
    from deeplearning4j_tpu.nn.layers.base import block_checkpoint
    *args, w = _inputs(T, decay)
    rule = lambda *a: kda.delta_rule_chunked(*a, chunk, compute_dtype)
    loss = lambda *a: jnp.sum(block_checkpoint(rule)(*a) * w)
    return jax.value_and_grad(loss, (0, 1, 2, 3, 4)), args


def _strike_the_name(monkeypatch):
    """Both policies the in-chunk matrices sit under as they were before
    they listed the matrices' name: the block stacks' one, and the
    segment's own, which kept its inputs alone."""
    from deeplearning4j_tpu.nn.layers import base
    monkeypatch.setattr(
        base, "_BLOCK_POLICY", jax.checkpoint_policies.save_only_these_names(
            base.FLASH_RES, base.NORM_IN, base.SCAN_CARRY))
    monkeypatch.setattr(kda, "_SEGMENT_POLICY",
                        jax.checkpoint_policies.nothing_saveable)


@pytest.mark.parametrize("chunk,T,decay,per_segment,compute_dtype", [
    (8, 40, 1.0, None, jnp.float32), (16, 50, 0.05, None, jnp.float32),
    (32, 96, 1.0, None, jnp.float32), (64, 150, 1.0, None, jnp.float32),
    (64, 128, 6.0, None, jnp.float32), (48, 100, 1.0, None, jnp.float32),
    # five chunks in segments of two: the last is padded
    (16, 70, 1.0, 2, jnp.float32),
    # the cell's policy: the B that is kept is the rounded one its reader
    # takes, and the inverse is rounded after it is kept
    (64, 150, 1.0, None, jnp.bfloat16), (16, 70, 1.0, 2, jnp.bfloat16),
])
def test_keeping_the_chunk_matrices_changes_no_bit(
        chunk, T, decay, per_segment, compute_dtype, monkeypatch):
    """Under the block stacks' checkpoint the rule's loss and its gradients
    on all five inputs are, bit for bit, those of the same program with the
    name struck from both policies: what is kept is what would be formed
    again."""
    if per_segment:
        monkeypatch.setattr(kda, "SEGMENT_CHUNKS", per_segment)
    grad, args = _under_the_block_checkpoint(chunk, T, decay, compute_dtype)
    kept = jax.jit(grad)(*args)
    _strike_the_name(monkeypatch)
    struck = jax.jit(_under_the_block_checkpoint(
        chunk, T, decay, compute_dtype)[0])(*args)
    for a, b in zip(jax.tree_util.tree_leaves(kept),
                    jax.tree_util.tree_leaves(struck)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def _equations(jaxpr, trips=1):
    """(equation, times it runs) of ``jaxpr`` and of every jaxpr inside it:
    a ``scan``'s body runs its ``length`` times."""
    for eqn in jaxpr.eqns:
        yield eqn, trips
        inside = trips * eqn.params.get("length", 1)
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (list, tuple))
                          else [value]):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner, inside)


def _formed(grad, args):
    """What the differentiated rule forms, by equations in its program:
    (passes over the diagonal blocks' weights: forward one ``exp`` over the
    [rows, columns, K, n] pairs of each band of a block of ``SUB`` steps,
    backward one over the whole [SUB, SUB, K, n]; ``neg`` over a
    [SUB, i SUB] block row of the inverse's substitution: one a block row
    below the first, formed nowhere else)."""
    K, chunk = args[1].shape[-1], 64
    rows = [(kda.SUB, i * kda.SUB) for i in range(1, chunk // kda.SUB)]
    bands = squares = inverse = 0
    for eqn, _ in _equations(jax.make_jaxpr(grad)(*args).jaxpr):
        if eqn.primitive.name not in ("exp", "neg"):
            continue
        shape = tuple(eqn.outvars[0].aval.shape)
        if eqn.primitive.name == "exp" and len(shape) == 4 and shape[2] == K:
            square = shape[:2] == (kda.SUB, kda.SUB)
            squares += square
            bands += not square
        inverse += eqn.primitive.name == "neg" and shape[-2:] in rows
    return bands // len(kda._bands(kda.SUB)) + squares, inverse


def test_the_backward_forms_no_chunk_matrix_again(monkeypatch):
    """The differentiated rule forms the weights of the diagonal blocks in
    two passes, each of two calls (q against k, k against k): forward, and
    for the products' cotangents; and it runs the inverse's substitution
    (three block rows a chunk of 64) once, forward. With the name struck
    the segment's recomputed forward is a third pass over the weights and a
    second substitution; struck from the block stacks' policy alone it is
    too (a name inside nested checkpoints is kept only where both list
    it)."""
    formed = lambda: _formed(*_under_the_block_checkpoint(64, 256, 1.0))
    assert formed() == (2 * 2, 3)
    from deeplearning4j_tpu.nn.layers import base
    monkeypatch.setattr(
        base, "_BLOCK_POLICY", jax.checkpoint_policies.save_only_these_names(
            base.FLASH_RES, base.NORM_IN, base.SCAN_CARRY))
    assert formed() == (3 * 2, 2 * 3)
    _strike_the_name(monkeypatch)
    assert formed() == (3 * 2, 2 * 3)


def test_the_name_on_the_inverses_result_keeps_another_value(monkeypatch):
    """The inverse's rule reads the ``T`` its forward handed it: a name
    given to the call's result instead stands on another value, and the
    substitution runs a second time for the one the rule reads."""
    monkeypatch.setattr(kda, "_unit_lower_inverse_fwd",
                        lambda N: (kda._unit_lower_inverse(N),) * 2)
    renamed = jax.custom_vjp(kda._unit_lower_inverse)
    renamed.defvjp(kda._unit_lower_inverse_fwd, kda._unit_lower_inverse_bwd)
    monkeypatch.setattr(kda, "unit_lower_inverse",
                        lambda N: kda._kept(renamed(N)))
    assert _formed(*_under_the_block_checkpoint(64, 256, 1.0)) == (2 * 2,
                                                                   2 * 3)


def test_the_kept_bytes_are_those_the_program_names():
    """``kda_kept_bytes`` reads what the layer's differentiated program
    gives the name ``CHUNK_MATS``, over all of its segments: 40 steps in
    chunks of 16 are three chunks, the last padded; ``A`` and the inverse in
    float32, ``B`` in the compute dtype."""
    from deeplearning4j_tpu.monitor import get_registry
    from deeplearning4j_tpu.nn.layers.base import CHUNK_MATS, block_checkpoint
    layer = _layer()
    layer.index = "kept"
    params, _ = layer.init(jax.random.PRNGKey(1))
    loss = lambda p, x: jnp.sum(block_checkpoint(
        lambda p, x: layer.forward(p, {}, x)[0])(p, x))
    program = jax.make_jaxpr(jax.grad(loss))(
        params, jnp.zeros((2, 40, 24), jnp.float32))
    named = sum(trips * eqn.outvars[0].aval.size
                * eqn.outvars[0].aval.dtype.itemsize
                for eqn, trips in _equations(program.jaxpr)
                if eqn.primitive.name == "name"
                and eqn.params["name"] == CHUNK_MATS)
    rows = {row["labels"]["layer"]: row["value"]
            for row in get_registry().snapshot()["kda_kept_bytes"]}
    cd = jnp.dtype(layer.compute_dtype).itemsize
    assert rows["kept"] == named == 2 * 3 * 2 * 16 * 16 * (4 + 4 + cd)


def _layer(n_in=24, H=2, K=8, chunk=16):
    from deeplearning4j_tpu import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import KimiDeltaAttentionLayer
    from deeplearning4j_tpu.nn.layers.base import impl_for
    conf = NeuralNetConfiguration.builder().seed(3).list().build()
    return impl_for(KimiDeltaAttentionLayer(
        n_in=n_in, n_out=n_in, num_heads=H, head_dim=K, chunk_size=chunk),
        conf.global_conf)


def test_the_layer_is_its_equations():
    """``KimiDeltaAttentionLayer`` against the benchmark's plain reference of
    the mixer (``benchmark/reference/kimi_linear_48b_a3b.py: kda_mixer``,
    the recurrence step by step): output and every gradient leaf."""
    from benchmark.reference import kimi_linear_48b_a3b as reference
    layer = _layer()
    params, state = layer.init(jax.random.PRNGKey(1))
    assert state == {}
    assert {k: v.shape for k, v in params.items()} == {
        "Wq": (24, 16), "Wk": (24, 16), "Wv": (24, 16), "conv_q": (16, 4),
        "conv_k": (16, 4), "conv_v": (16, 4), "W_fa": (24, 8),
        "W_fb": (8, 16), "W_b": (24, 2), "W_ga": (24, 8), "W_gb": (8, 16),
        "Wo": (16, 24), "dt_bias": (16,), "A_log": (2,), "gn": (8,)}
    # softplus(dt_bias) is a step drawn in [1e-3, 1e-1], A in [1, 16]
    step = np.log1p(np.exp(np.asarray(params["dt_bias"])))
    assert 1e-3 <= step.min() and step.max() <= 1e-1
    assert 0.0 <= np.asarray(params["A_log"]).min() \
        and np.asarray(params["A_log"]).max() <= np.log(16.0)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 37, 24)),
                    jnp.float32)
    loss = lambda f: lambda p, x: jnp.sum(jnp.sin(f(p, x)))
    got, grads = jax.value_and_grad(loss(
        lambda p, x: layer.forward(p, {}, x)[0]), (0, 1))(params, x)
    with jax.default_matmul_precision("highest"):
        want, ref = jax.value_and_grad(loss(
            lambda p, x: reference.kda_mixer(p, x, 1e-5)), (0, 1))(params, x)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for (path, a), r in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(ref)):
        assert float(jnp.linalg.norm(a - r) / jnp.linalg.norm(r)) < 1e-4, path


def test_the_layer_stacks_its_leaves_and_refuses_what_it_cannot_do():
    layer = _layer()
    params, _ = layer.init(jax.random.PRNGKey(1), lead=(3,))
    assert params["Wq"].shape == (3, 24, 16)
    assert params["A_log"].shape == (3, 2) and params["gn"].shape == (3, 8)
    one, _ = layer.init(jax.random.PRNGKey(1))
    x = jnp.zeros((1, 8, 24), jnp.float32)
    with pytest.raises(ValueError, match="key mask"):
        layer.forward(one, {}, x, mask=jnp.ones((1, 8)))
    with pytest.raises(ValueError, match="streaming"):
        layer.forward(one, {}, x, ctx={"rnn_state_in": {}})
    # the chunks' gauge is set where the layer is traced
    from deeplearning4j_tpu.monitor import get_registry
    layer.index = "probe"
    layer.forward(one, {}, jnp.zeros((1, 40, 24), jnp.float32))
    rows = {row["labels"]["layer"]: row["value"]
            for row in get_registry().snapshot()["kda_chunks"]}
    assert rows["probe"] == 3                                  # 40 / 16


def test_the_block_pairs_gauge_reads_what_a_block_forms():
    """``kda_block_pairs`` is set where the layer is traced: chunks of 16
    steps are one diagonal block of 16, whose two bands form 192 pairs a
    head and channel forward (the lower triangle 136 of them, the dense
    form 256); chunks of 8, 48 of 64."""
    from deeplearning4j_tpu.monitor import get_registry
    for chunk, pairs in ((16, 192), (8, 48)):
        layer = _layer(chunk=chunk)
        layer.index = f"pairs{chunk}"
        params, _ = layer.init(jax.random.PRNGKey(1))
        layer.forward(params, {}, jnp.zeros((1, 40, 24), jnp.float32))
        rows = {row["labels"]["layer"]: row["value"]
                for row in get_registry().snapshot()["kda_block_pairs"]}
        assert rows[f"pairs{chunk}"] == pairs == kda.block_pairs(chunk)
