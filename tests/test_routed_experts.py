"""The routed gated-expert layer (``nn/layers/moe.py: RoutedExpertsImpl``):
against a plain masked loop over the held experts (output and every gradient)
under ordinary and extreme routings, without a dropped choice; the share test
(all shares' routed parts and the shared expert once add up to the uncut
layer); the tables the grouped products walk and how far they are walked;
the rows each token's choices landed on, and the two ways the walk hands its
tiles back."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import RoutedExpertsLayer
from deeplearning4j_tpu.nn.layers import moe
from deeplearning4j_tpu.nn.layers.base import impl_for

D, F, E, K = 16, 12, 32, 8


@pytest.fixture(autouse=True)
def _toy_tiles(monkeypatch):
    """Tiles in multiples of 8 rows, not of the MXU's 128: a few dozen
    tokens fill several."""
    monkeypatch.setattr(moe, "MXU_ROWS", 8)


def _layer(held, top_k=K, shared=F, experts=E, scaling=2.446, **kw):
    conf = NeuralNetConfiguration.builder().seed(3).list().build()
    return impl_for(RoutedExpertsLayer(
        n_in=D, n_out=D, num_experts=experts, experts_held=held, top_k=top_k,
        n_hidden=F, shared_hidden=shared, routed_scaling_factor=scaling,
        **kw), conf.global_conf)


def gated(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def masked_loop(params, x, held, top_k=K, scaling=2.446, bias=None,
                score="sigmoid"):
    """The layer's equations, every held expert on every token and masked."""
    scores = (jax.nn.softmax(x @ params["Wr"], axis=-1) if score == "softmax"
              else jax.nn.sigmoid(x @ params["Wr"]))
    _, top = jax.lax.top_k(scores if bias is None else scores + bias, top_k)
    chosen = jnp.take_along_axis(scores, top, axis=-1)
    weight = scaling * chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
    y = 0.0
    if "Ws_gate" in params:
        y = gated(x, params["Ws_gate"], params["Ws_up"], params["Ws_down"])
    for i, e in enumerate(held):
        w_e = jnp.sum(jnp.where(top == e, weight, 0.0), axis=-1)
        y = y + w_e[:, None] * gated(x, params["We_gate"][i],
                                     params["We_up"][i], params["We_down"][i])
    return y


def _agree(layer, params, state, x, held, **kw):
    w = jnp.asarray(np.random.default_rng(9).normal(size=x.shape), jnp.float32)
    got, grads = jax.value_and_grad(
        lambda p, x: jnp.sum(layer.forward(p, state, x)[0] * w), (0, 1))(
            params, x)
    with jax.default_matmul_precision("highest"):
        want, ref = jax.value_and_grad(
            lambda p, x: jnp.sum(masked_loop(p, x, held, **kw) * w), (0, 1))(
                params, x)
    assert float(got) == pytest.approx(float(want), rel=1e-5, abs=1e-5)
    for (path, a), r in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(ref)):
        scale = max(float(jnp.linalg.norm(r)), 1e-6)
        assert float(jnp.linalg.norm(a - r)) / scale < 1e-4, path
    return grads


def _x(n=40, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(n, D)),
                       jnp.float32)


def test_the_layer_is_the_masked_loop_over_its_held_experts():
    held = [3, 4, 9, 17, 18, 19, 30, 31]
    layer = _layer(held)
    params, state = layer.init(jax.random.PRNGKey(0))
    assert {k: v.shape for k, v in params.items()} == {
        "Wr": (D, E), "We_gate": (8, D, F), "We_up": (8, D, F),
        "We_down": (8, F, D), "Ws_gate": (D, F), "Ws_up": (D, F),
        "Ws_down": (F, D)}
    assert state["b"].shape == (E,) and not np.asarray(state["b"]).any()
    _agree(layer, params, state, _x(), held)
    # [b, T, d] passes through as it is
    x3 = _x(24).reshape(2, 12, D)
    y3, _ = layer.forward(params, state, x3)
    flat, _ = layer.forward(params, state, x3.reshape(24, D))
    assert y3.shape == (2, 12, D)
    np.testing.assert_allclose(np.asarray(y3).reshape(24, D),
                               np.asarray(flat), rtol=1e-6, atol=1e-6)


def _steered(params, experts, strength=30.0):
    """The router pulled to ``experts`` whatever the token."""
    pull = np.full((E,), -strength, np.float32)
    pull[list(experts)] = strength
    x_dir = np.ones((D,), np.float32) / D
    return {**params, "Wr": params["Wr"] * 0.01 + jnp.asarray(
        np.outer(x_dir, pull))}


def _rows_read_once(local, held, tile):
    """``token_rows`` against ``routing_tables``: every choice that landed
    here is read exactly once, on a row of its token in a tile of its
    expert, and every other slot reads the row past the tables, which the
    walk never reaches."""
    local = jnp.asarray(local)
    n, k = local.shape
    row_token, _, tile_expert, tiles = (np.asarray(t) for t in (
        moe.routing_tables(local, jnp.ones(local.shape), held, tile)))
    rows = np.asarray(moe.token_rows(local, held, tile))
    R = len(row_token)
    assert rows.shape == (n, min(k, held)) and rows.dtype == np.int32
    assert tiles * tile <= R                 # row R is never written
    landed = np.asarray(local) < held
    for t in range(n):
        mine = rows[t][rows[t] < R]
        assert len(mine) == landed[t].sum() and (rows[t][len(mine):] == R).all()
        assert (row_token[mine] == t).all()
        assert sorted(tile_expert[mine // tile].tolist()) == sorted(
            np.asarray(local)[t][landed[t]].tolist())
    read = rows[rows < R]
    assert len(set(read.tolist())) == len(read) == (row_token < n).sum()


#: the two ways the walk hands its tiles back: combined by one gather a
#: slot (a layer whose slots are few against its walk), added by a scatter
FORMS = {"combine": 10 ** 6, "scatter": 0}


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("mxu_rows", [4, 32])
@pytest.mark.parametrize("case", ["one_held_expert_takes_every_token",
                                  "all_choices_held", "none_routed_here"])
def test_routing_edges_are_exact_and_drop_nothing(case, mxu_rows, form,
                                                  monkeypatch):
    """The worst routings for a capacity: every token's choices on this
    chip, every token to one held expert, no token here at all; at tiles
    that an expert's rows fill several times over (12 rows: the groups end
    on a tile's edge) and at tiles they do not fill (32); the walk's tiles
    combined by one gather a slot and added by a scatter. Each is the
    masked loop exactly, gradients too; each landed choice is read once;
    the products walk their standing tiles, and the tiles in use where the
    routing passes them."""
    monkeypatch.setattr(moe, "MXU_ROWS", mxu_rows)
    monkeypatch.setattr(moe, "COMBINE_PER_WALKED_ROW", FORMS[form])
    walked = []
    grouped = moe.grouped_ffn
    monkeypatch.setattr(moe, "grouped_ffn", lambda *args: (
        walked.append(int(args[7])), grouped(*args))[1])
    held = list(range(8))
    layer = _layer(held)
    params, state = layer.init(jax.random.PRNGKey(1))
    x = jnp.abs(_x(48, seed=4)) + 0.1              # x . ones > 0
    if case == "one_held_expert_takes_every_token":
        params = _steered(params, [5] + list(range(20, 27)))
    elif case == "all_choices_held":
        params = _steered(params, held)
    else:
        params = _steered(params, range(16, 24))
    grads = _agree(layer, params, state, x, held)
    _, top = jax.lax.top_k(jax.nn.sigmoid(x @ params["Wr"]), K)
    here = np.isin(np.asarray(top), held).sum()
    tile, standing = moe.tile_plan(48, K, 8, E)
    _rows_read_once(np.where(np.asarray(top) < 8, top, 8), 8, tile)
    assert here == {"one_held_expert_takes_every_token": 48,
                    "all_choices_held": 48 * 8, "none_routed_here": 0}[case]
    if case == "none_routed_here":
        assert not np.asarray(grads[0]["We_gate"]).any()
    if case == "one_held_expert_takes_every_token":
        assert np.asarray(grads[0]["We_gate"][5]).any()
        assert not np.asarray(grads[0]["We_gate"][4]).any()
    # 48 tokens of 8 choices among 32: uniform routing sends an expert 12
    assert (tile, standing) == {4: (12, 24), 32: (32, 14)}[mxu_rows]
    layer.forward(params, state, x)
    in_use = {"one_held_expert_takes_every_token": -(-48 // tile),
              "all_choices_held": 8 * -(-48 // tile), "none_routed_here": 0}
    assert walked[-1] == max(in_use[case], standing)
    assert (in_use[case] > standing) == (case == "all_choices_held")


def test_the_walk_is_planned_from_what_the_layer_sees(monkeypatch):
    """``tile_plan``: a tile is what uniform routing sends one expert, to a
    multiple of the MXU's rows and four of them at most; the standing walk
    is twice what it sends here and a tile for every held expert."""
    monkeypatch.setattr(moe, "MXU_ROWS", 128)
    assert moe.tile_plan(8192, 8, 8, 256) == (256, 24)       # the kimi cell
    assert moe.tile_plan(8192, 8, 256, 256) == (256, 768)    # all held
    assert moe.tile_plan(80, 8, 8, 32) == (128, 11)          # its rehearsal
    assert moe.tile_plan(65536, 8, 8, 64) == (512, 264)      # 8192 an expert


#: (score, published experts, held a share, the shared expert's width, the
#: routed scaling): the Kimi Linear layer's kind at 32 over four shares of 8,
#: the Mellum2 layer's at 64 over four shares of 16 (ids 0-15 .. 48-63)
SHARES = {"sigmoid": (E, 8, F, 2.446), "softmax": (64, 16, None, 1.0)}


@pytest.mark.parametrize("score", sorted(SHARES))
def test_the_shares_add_up_to_the_uncut_layer(score):
    """The share test (``model-configs`` guide, section 4): the routed parts
    that all four shares give, with a shared expert, which every chip
    computes alike, counted once where there is one, add up to what the
    uncut reference of the configuration gives for the whole layer."""
    from benchmark.reference import kimi_linear_48b_a3b, mellum2_12b_a2_5b
    experts, size, fs, scaling = SHARES[score]
    kw = dict(shared=fs, experts=experts, scaling=scaling, score=score)
    whole = _layer(None, **kw)
    params, state = whole.init(jax.random.PRNGKey(2))
    assert ("b" in state) == (score == "sigmoid")
    x = _x(64, seed=5)
    with jax.default_matmul_precision("highest"):
        if score == "sigmoid":
            uncut = kimi_linear_48b_a3b.experts_ffn(params, x, K, scaling)
            shared = kimi_linear_48b_a3b.gated(
                x, params["Ws_gate"], params["Ws_up"], params["Ws_down"])
        else:
            uncut = mellum2_12b_a2_5b.experts_ffn(params, x, K)
            shared = 0.0
    total = shared
    for first in range(0, experts, size):
        held = list(range(first, first + size))
        share = _layer(held, **kw)
        part = {k: (v[first:first + size] if k in share.EXPERT_KEYS else v)
                for k, v in params.items()}
        y, _ = share.forward(part, state, x)
        total = total + (y - shared)
        # and the reference, given the same share, gives the same part
        with jax.default_matmul_precision("highest"):
            part_ref = masked_loop(part, x, held, scaling=scaling,
                                   score=score)
        np.testing.assert_allclose(np.asarray(y), np.asarray(part_ref),
                                   rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               rtol=1e-4, atol=1e-5)
    # the uncut layer through the program too
    y, _ = whole.forward(params, state, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(uncut), rtol=1e-4,
                               atol=1e-5)


def test_softmax_scores_against_a_hand_computation():
    """``score="softmax"``: probabilities over all the published experts in
    float32 (float64 here), the 8 largest, renormalised over the 8; the
    layer holds no bias state and is the masked loop, gradients too."""
    held = list(range(16, 24))
    layer = _layer(held, shared=None, scaling=1.0, score="softmax")
    params, state = layer.init(jax.random.PRNGKey(7))
    assert state == {}
    x = _x(24, seed=8)
    chosen, weights = layer.route(x, params["Wr"], None)
    logits = np.asarray(x, np.float64) @ np.asarray(params["Wr"], np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    for t in range(24):
        top = np.argsort(-p[t])[:K]
        assert set(np.asarray(chosen[t]).tolist()) == set(top.tolist())
        want = p[t, np.asarray(chosen[t])] / p[t, top].sum()
        np.testing.assert_allclose(np.asarray(weights[t]), want, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-6)
    _agree(layer, params, state, x, held, scaling=1.0, score="softmax")
    with pytest.raises(ValueError, match="score"):
        _layer(held, score="relu")


def test_the_bias_enters_the_choice_only_and_no_gradient_reaches_it():
    held = list(range(8))
    layer = _layer(held)
    params, state = layer.init(jax.random.PRNGKey(3))
    x = _x(32, seed=6)
    bias = jnp.asarray(np.random.default_rng(1).normal(size=(E,)) * 0.3,
                       jnp.float32)
    biased = {"b": bias}
    _agree(layer, params, biased, x, held, bias=bias)
    moved, _ = layer.forward(params, biased, x)
    plain, _ = layer.forward(params, state, x)
    assert not np.allclose(np.asarray(moved), np.asarray(plain))
    grad = jax.grad(lambda s: jnp.sum(layer.forward(params, s, x)[0]))(biased)
    assert not np.asarray(grad["b"]).any()


def test_the_tables_hold_every_choice_once_and_whole_tiles():
    """``routing_tables``: each held choice is one row, in its expert's
    group, groups padded to whole tiles; the tables are sized for the worst
    routing and the tiles in use follow the routing."""
    rng = np.random.default_rng(0)
    n, k, held, tile = 50, 4, 3, 8
    local = np.stack([rng.permutation(10)[:k] for _ in range(n)])
    local = np.where(local < held, local, held).astype(np.int32)
    weights = rng.uniform(size=(n, k)).astype(np.float32)
    row_token, row_weight, tile_expert, tiles = (np.asarray(t) for t in (
        moe.routing_tables(jnp.asarray(local), jnp.asarray(weights), held,
                           tile)))
    rows = len(row_token)
    assert rows == -(-(n * min(k, held) + held * (tile - 1)) // tile) * tile
    counts = [(local == e).sum() for e in range(held)]
    assert tiles == sum(-(-c // tile) for c in counts)
    real = row_token < n
    assert real.sum() == sum(counts) and not real[tiles * tile:].any()
    assert not row_weight[~real].any()
    for t in range(int(tiles)):
        e = tile_expert[t]
        for r in range(t * tile, (t + 1) * tile):
            if real[r]:
                slot = np.flatnonzero(local[row_token[r]] == e)
                assert len(slot) == 1
                assert row_weight[r] == weights[row_token[r], slot[0]]
        tokens = row_token[t * tile:(t + 1) * tile]
        assert (np.diff(tokens) > 0).all()          # sorted and distinct
    # every (token, held choice) pair is there exactly once
    pairs = {(int(row_token[r]), int(tile_expert[r // tile]))
             for r in np.flatnonzero(real)}
    assert pairs == {(t, int(e)) for t in range(n) for e in local[t]
                     if e < held}
    # and each token's rows are found again: 3 held of 4 choices, so a
    # token has 3 slots at most
    _rows_read_once(local, held, tile)


@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_walk_lowers_to_a_scatter_only_where_it_adds(form):
    """The jitted forward and backward of ``grouped_ffn``: with the tokens'
    rows combined by gathers no scatter is left in either; without them the
    tiles are added by a scatter-add."""
    rng = np.random.default_rng(2)
    n, held, tile = 40, 4, 8
    local = jnp.asarray(rng.integers(0, held + 2, size=(n, K)).clip(0, held),
                        jnp.int32)
    tables = moe.routing_tables(local, jnp.ones((n, K)), held, tile)
    rows = moe.token_rows(local, held, tile) if form == "combine" else None
    x = jnp.ones((n, D))
    w = [jnp.ones((held, D, F)), jnp.ones((held, D, F)),
         jnp.ones((held, F, D))]

    def loss(x, w, weight):
        return jnp.sum(moe.grouped_ffn(x, *w, tables[0], weight, tables[2],
                                       tables[3], rows, tile, jnp.float32))
    for f in (loss, jax.grad(loss, (0, 1, 2))):
        text = jax.jit(f).lower(x, w, tables[1]).as_text()
        assert ("scatter" in text) == (form == "scatter")


@pytest.mark.parametrize("cell, experts, held, slots", [
    ("kimi", 256, 8, 0), ("mellum2", 64, 16, 8192 * 8)])
def test_the_cells_shapes_choose_the_form(cell, experts, held, slots,
                                          monkeypatch):
    """At the expert cells' 8192 tokens, top 8 and tiles of the MXU's 128:
    the kimi layer walks 24 tiles of 256 for 65,536 slots (10.7 a walked
    row) and adds by a scatter, the Mellum2 layer 80 tiles of 512 (1.6)
    and combines; ``moe_combine_slots`` says which."""
    monkeypatch.setattr(moe, "MXU_ROWS", 128)
    layer = _layer(list(range(held)), experts=experts, shared=None)
    params, state = layer.init(jax.random.PRNGKey(5))
    walked = []
    grouped = moe.grouped_ffn
    monkeypatch.setattr(moe, "grouped_ffn", lambda *args: (
        walked.append(args[8] is None), grouped(*args))[1])
    layer.forward(params, state, _x(8192, seed=9))
    assert walked == [slots == 0]
    from deeplearning4j_tpu.monitor import get_registry
    assert get_registry().snapshot()["moe_combine_slots"][-1]["value"] == slots


def test_the_layer_refuses_what_it_cannot_mean():
    with pytest.raises(ValueError, match="experts_held"):
        _layer([1, 1])
    with pytest.raises(ValueError, match="experts_held"):
        _layer([E])
    with pytest.raises(ValueError, match="top_k"):
        _layer([0], top_k=E + 1)
    # no shared expert: the routed sum alone; the scores as they are
    bare = _layer(list(range(8)), shared=None, renormalize=False)
    params, state = bare.init(jax.random.PRNGKey(4))
    assert "Ws_gate" not in params
    y, _ = bare.forward(params, state, _x(16))
    assert y.shape == (16, D) and np.isfinite(np.asarray(y)).all()
    from deeplearning4j_tpu.monitor import get_registry
    snap = get_registry().snapshot()
    assert {row["labels"]["which"]: row["value"]
            for row in snap["moe_experts"]} == {"held": 8, "published": E}
    assert snap["moe_rows_sized"][0]["value"] == 16 * 8 + 8 * 7   # 184 rows
    # uniform routing sends the eight 32 rows: twice that and a tile each
    assert snap["moe_rows_standing"][0]["value"] == (8 + 8) * 8
    # 16 tokens' 8 slots against those 128 walked rows: combined
    assert snap["moe_combine_slots"][0]["value"] == 16 * 8
