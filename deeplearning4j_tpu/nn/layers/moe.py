"""Mixture-of-experts dense layer (expert parallelism).

Net-new vs the 0.9.x reference (SURVEY.md §2.4: data parallelism only), the
``expert`` counterpart to the net-new tensor/sequence/pipeline axes. Dense
top-k dispatch in einsum form so the expert dimension is a *shardable array
axis*: with ``W: [E, n_in, n_out]`` sharded over the mesh ``expert`` axis
(``parallel/expert.py``), XLA partitions the per-expert einsum so each device
computes only its expert shard and the final expert-dim reduction lowers to a
psum over ICI — expert parallelism without a hand-written all-to-all.

The Switch-Transformer load-balancing auxiliary loss (num_experts × Σ_e
fraction_of_tokens_routed_to_e × mean_gate_prob_e) accumulates through the
forward ``ctx`` into the training objective (``nn/multilayer.py`` /
``nn/graph.py`` add ``ctx['aux_loss']`` to loss+reg).

:class:`RoutedExpertsImpl` is the other expert layer, a class of its own
beside the one above: gated expert FFNs with a shared expert, routed over all
the published experts without a drop, of which this chip holds the ones it
is told, their products grouped over the rows that were routed to them
(:func:`grouped_ffn`).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ...monitor import get_registry
from ..weights import host_full
from .base import LayerImpl, implements, acc_dtype, pet_dtype


@implements("MoEDenseLayer")
class MoEDenseImpl(LayerImpl):
    def init(self, rng):
        c = self.conf
        E = c.num_experts
        if E < 1 or not (1 <= c.top_k <= E):
            raise ValueError(f"MoEDenseLayer needs 1 <= top_k <= num_experts "
                             f"(got top_k={c.top_k}, num_experts={E})")
        if c.capacity_factor < 0:
            raise ValueError(f"capacity_factor must be >= 0 "
                             f"(got {c.capacity_factor})")
        kg, kw = jax.random.split(rng)
        params = {
            # router: small, always f32-precision-critical
            "Wg": self._init_w(kg, (c.n_in, E), c.n_in, E),
            # per-expert dense weights, expert dim leading (shardable)
            "W": self._init_w(kw, (E, c.n_in, c.n_out), c.n_in, c.n_out),
        }
        if c.has_bias:
            params["b"] = self._init_b((E, c.n_out))
        return params, {}

    def _router_dtype(self):
        """Router math runs at least f32 (precision-critical softmax), and
        full f64 under the gradient-check dtype policy."""
        return jnp.promote_types(jnp.float32, self.dtype)

    def _route(self, xr, Wg):
        """Top-k gates: softmax over experts, keep the k largest, renormalize.
        Returns gates [b, E] (zero outside the top-k) and the full probs."""
        c = self.conf
        logits = xr @ Wg.astype(xr.dtype)
        probs = jax.nn.softmax(logits, axis=-1)
        if c.top_k >= c.num_experts:
            return probs, probs
        # index-based mask: exactly top_k experts even on tied probs (a
        # threshold mask would gate ALL experts for an all-uniform row)
        _, idxs = jax.lax.top_k(probs, c.top_k)
        mask = jnp.sum(jax.nn.one_hot(idxs, c.num_experts, dtype=probs.dtype),
                       axis=-2)
        gates = probs * mask
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        return gates, probs

    def _dense_combine(self, params, flat, gates, cd):
        """Dense (Shazeer-style) path — every token through every expert,
        gate-masked. O(n·E·F·O) FLOPs; the correctness oracle for the sparse
        dispatch below."""
        h = jnp.einsum("nf,efo->neo", flat.astype(cd),
                       params["W"].astype(cd),
                       preferred_element_type=pet_dtype(cd))
        if "b" in params:
            h = h + params["b"].astype(h.dtype)
        # gate-weighted combine; reduction over E → psum when E is sharded
        return jnp.einsum("ne,neo->no", gates.astype(h.dtype), h,
                          preferred_element_type=pet_dtype(cd))

    def _capacity(self, n):
        c = self.conf
        k = min(c.top_k, c.num_experts)
        cap = -(-k * n * c.capacity_factor // c.num_experts)
        return int(min(max(8, -(-cap // 8) * 8), max(8, -(-n // 8) * 8)))

    def _sparse_combine(self, params, flat, gates, cd):
        """Capacity-factor token dispatch (GShard/Switch one-hot einsum form):
        each expert computes a fixed [C, F] buffer of its routed tokens, so
        expert FLOPs are E·C·F·O ≈ (top_k/E)·dense instead of n·E·F·O.

        Tokens are processed in GROUPS of ``conf.group_size`` (the GShard
        group dim): capacity is enforced per group, so the one-hot dispatch
        tensor is [g, G, E, C_g] with C_g ∝ G — memory LINEAR in token
        count (n·k·G·cf elements) instead of the groupless [n, E, C]
        (C ∝ n ⇒ quadratic: the T=8k flagship would need multi-GB dispatch
        intermediates). A short token run (n ≤ G) is a single group, so
        small-batch behavior is unchanged.

        Buffer positions are assigned slot-major within each group (all
        rank-0 assignments before rank-1), so when an expert overflows its
        per-group capacity the LOWER-gate assignments are the ones dropped.
        Dropped (token, expert) pairs simply contribute zero —
        Switch-Transformer semantics. The dispatch tensor stays
        one-hot/shardable: with ``W`` sharded over the mesh 'expert' axis
        the per-expert einsums partition and the combine reduction lowers
        to a psum, same as the dense path."""
        c = self.conf
        n, E = flat.shape[0], c.num_experts
        k = min(c.top_k, E)
        G = max(8, min(n, int(getattr(c, "group_size", 1024) or 1024)))
        g = -(-n // G)
        pad = g * G - n
        if pad:
            flat = jnp.concatenate(
                [flat, jnp.zeros((pad, flat.shape[1]), flat.dtype)], axis=0)
            gates = jnp.concatenate(
                [gates, jnp.zeros((pad, E), gates.dtype)], axis=0)
        C = self._capacity(G)
        xg = flat.reshape(g, G, -1)
        gg = gates.reshape(g, G, E)
        _, idxs = jax.lax.top_k(gg, k)                       # [g, G, k]
        mask = jax.nn.one_hot(idxs, E, dtype=jnp.int32)      # [g, G, k, E]
        if pad:
            # top_k on a padding row's all-zero gates still one-hots experts
            # 0..k-1; zero those mask rows so pads claim no buffer slots
            # (they'd otherwise displace real low-gate assignments in the
            # tail group)
            valid = (jnp.arange(g * G) < n).astype(jnp.int32).reshape(g, G)
            mask = mask * valid[:, :, None, None]
        mk = mask.transpose(0, 2, 1, 3).reshape(g, k * G, E)  # slot-major
        pos = jnp.cumsum(mk, axis=1) - 1                     # per-expert fill
        pos_t = jnp.sum(pos * mk, axis=-1)                   # [g, k*G]
        keep = (pos_t < C) & (jnp.sum(mk, axis=-1) > 0)
        slot = (jax.nn.one_hot(pos_t, C, dtype=cd)
                * keep[..., None].astype(cd))                # [g, k*G, C]
        disp = (mk.astype(cd)[..., None] * slot[..., None, :])
        disp = disp.reshape(g, k, G, E, C).sum(axis=1)       # [g, G, E, C]
        combine = disp * gg.astype(cd)[..., None]
        expert_in = jnp.einsum("gnec,gnf->egcf", disp, xg.astype(cd),
                               preferred_element_type=pet_dtype(cd))
        h = jnp.einsum("egcf,efo->egco", expert_in, params["W"].astype(cd),
                       preferred_element_type=pet_dtype(cd))
        if "b" in params:
            h = h + params["b"].astype(h.dtype)[:, None, None, :]
        y = jnp.einsum("gnec,egco->gno", combine, h,
                       preferred_element_type=pet_dtype(cd))
        return y.reshape(g * G, -1)[:n]

    def forward(self, params, state, x, train=False, rng=None, mask=None,
                ctx=None):
        c = self.conf
        x = self.maybe_dropout(x, train, rng)
        flat = x.reshape(-1, x.shape[-1])                # [n, F] (rnn-safe)
        rdt = self._router_dtype()
        gates, probs = self._route(flat.astype(rdt), params["Wg"])

        cd = self.compute_dtype
        # capacity dispatch only under TRAINING: dropping over-capacity
        # assignments is a throughput/utilization device for the train step
        # (Switch semantics); inference routes exactly, so output()/score()/
        # rnn_time_step agree with each other regardless of batch shape —
        # capacity is a function of n, and streaming steps see tiny n
        if c.capacity_factor and c.capacity_factor > 0 and train:
            y = self._sparse_combine(params, flat, gates, cd)
        else:
            y = self._dense_combine(params, flat, gates, cd)
        y = y.reshape(x.shape[:-1] + (c.n_out,))

        if ctx is not None and c.aux_loss_weight > 0.0:
            # Switch load-balancing loss: E * Σ_e f_e · P_e, where f_e is the
            # fraction of tokens whose TOP-1 expert is e and P_e the mean
            # router probability for e; minimized (=1) at uniform routing
            top1 = jnp.argmax(probs, axis=-1)
            f = jnp.mean(jax.nn.one_hot(top1, c.num_experts, dtype=rdt),
                         axis=0)
            P = jnp.mean(probs, axis=0)
            aux = c.aux_loss_weight * c.num_experts * jnp.sum(f * P)
            ctx["aux_loss"] = ctx.get("aux_loss", 0.0) + aux

        return self.activation(y).astype(self.out_dtype), state


# ------------------------------------------------------- routed gated experts
#: rows of a product that fill a side of the MXU: a tile is a multiple of it
MXU_ROWS = 128
#: the grouped products' standing walk, over the rows that uniform routing
#: sends here: a router that leans up to this far costs what a level one does
PROVISION = 2


def tile_plan(n, k, held, experts):
    """(rows a tile, tiles the grouped products always walk) of ``n`` tokens
    with ``k`` choices each among ``experts``, ``held`` of them here, from
    what the layer can see. A tile is the rows uniform routing sends one
    expert (``n k / experts``) to the next multiple of :data:`MXU_ROWS`, four
    of them at most: a tile reads its expert's three matrices whole, so
    smaller tiles read them more often, and larger ones pad more. The
    standing walk is :data:`PROVISION` times the rows uniform routing sends
    here and a tile of padding for every held expert: the buffers of a
    capacity factor without its drops, what lies beyond being walked as well
    (:func:`grouped_ffn`)."""
    mean = n * k / experts
    tile = MXU_ROWS * min(max(1, -(-int(mean) // MXU_ROWS)), 4)
    return tile, int(-(-PROVISION * mean * held // tile)) + held


#: choices :func:`token_rows` counts by one product with a triangle of ones
COUNT_BLOCK = 256
#: where a layer's slots (``n min(k, held)``, the rows the combining gather
#: reads) are at most this many times the rows it walks, the walk writes its
#: tiles in row order and gathers each token's rows after it; where they are
#: more, a turn adds its tile to its tokens by a scatter-add. On the v5e a
#: scattered row cost 0.54 to 0.93 us a pass and a combined slot 0.075 us,
#: the row buffer's fill and copies about 0.04 more: even near 5 (at 1.6
#: slots a walked row the combine took 150 ms off a 474 ms step, at 10.7 it
#: added 3 ms to a 659 ms one)
COMBINE_PER_WALKED_ROW = 4


def _rows_sized(n, k, held, tile):
    """Rows of the tables of ``n`` tokens' ``k`` choices among ``held``
    experts in tiles of ``tile``: the worst routing and every group's
    padding to a tile."""
    return -(-(n * min(k, held) + held * (tile - 1)) // tile) * tile


def routing_tables(local, weights, held, tile):
    """The rows of the grouped products, expert by expert: ``local`` [n, k]
    holds, for each of a token's k choices, the held expert's index in
    [0, ``held``) or ``held`` where the chosen expert lives elsewhere;
    ``weights`` [n, k] the choices' weights. The choices that landed here
    are put in the order of their experts (a stable sort, so a group's
    tokens stay in order), every expert's group is padded to whole tiles of
    ``tile`` rows, and the tables are sized for the worst routing,
    ``n min(k, held)`` rows and the groups' padding: no choice is dropped.

    -> (``row_token`` [R] the token a row reads and adds to, a padding row
    one of ``tile`` rows past the last token; ``row_weight`` [R], nought on
    padding; ``tile_expert`` [R / tile]; the tiles in use, a traced scalar)."""
    n, k = local.shape
    rows = _rows_sized(n, k, held, tile)
    key = local.reshape(-1)
    order = jnp.argsort(key, stable=True)
    counts = jnp.bincount(key, length=held + 1)[:held]
    padded = -(-counts // tile) * tile
    ends = jnp.cumsum(padded)
    r = jnp.arange(rows)
    expert = jnp.minimum(jnp.searchsorted(ends, r, side="right"), held - 1)
    rank = r - (ends - padded)[expert]
    valid = (rank < counts[expert]) & (r < ends[-1])
    choice = order[jnp.clip((jnp.cumsum(counts) - counts)[expert] + rank, 0,
                            n * k - 1)]
    row_token = jnp.where(valid, choice // k, n + r % tile).astype(jnp.int32)
    row_weight = jnp.where(valid, weights.reshape(-1)[choice], 0)
    return (row_token, row_weight, expert[::tile].astype(jnp.int32),
            (ends[-1] // tile).astype(jnp.int32))


def token_rows(local, held, tile):
    """The rows of :func:`routing_tables` that each token's choices landed
    on, from the same ``local`` [n, k], ``held`` and ``tile`` -> [n, min(k,
    held)] int32 (the most rows a token can have here); a slot that no
    choice filled points at the row one past the tables, which the walk
    never writes and which holds nought. The tables put the r-th choice of
    held expert e (in the order of the choices) on row r of e's group; that
    r is counted here by products with a triangle of ones, a block of
    :data:`COUNT_BLOCK` choices at a time, so no sort, scatter or gather of
    single numbers runs (on the v5e, at 8192 tokens of 8 choices, 0.65 ms
    where a stable sort of the tables' rows by token took 2.4)."""
    n, k = local.shape
    rows = _rows_sized(n, k, held, tile)
    flat = local.reshape(-1)
    block = min(COUNT_BLOCK, flat.shape[0])
    pad = -flat.shape[0] % block
    hit = (jnp.pad(flat, (0, pad), constant_values=held)[:, None]
           == jnp.arange(held)).astype(jnp.float32)
    within = jnp.einsum("ij,bjh->bih", jnp.tril(jnp.ones((block, block))),
                        hit.reshape(-1, block, held),
                        precision=jax.lax.Precision.HIGHEST).astype(jnp.int32)
    counted = jnp.cumsum(within[:, -1], axis=0)
    rank = (within + (counted - within[:, -1])[:, None]).reshape(-1, held)
    padded = -(-counted[-1] // tile) * tile
    first = jnp.cumsum(padded) - padded
    row = jnp.sum(jnp.where(hit > 0, rank - 1 + first, 0), axis=-1)
    row = jnp.where(flat < held, row[:flat.shape[0]], rows).reshape(n, k)
    if held < k:
        row = jnp.sort(row, axis=-1)[:, :held]
    return row.astype(jnp.int32)


def _tile(i, tile, *tables):
    return tuple(jax.lax.dynamic_slice_in_dim(t, i * tile, tile)
                 for t in tables)


def _expert(leaves, e, compute_dtype):
    return tuple(jax.lax.dynamic_index_in_dim(w, e, 0, keepdims=False)
                 .astype(compute_dtype) for w in leaves)


def _dots(compute_dtype):
    sd = acc_dtype(compute_dtype)
    dot = lambda a, b, dims: jax.lax.dot_general(
        a.astype(compute_dtype), b.astype(compute_dtype), (dims, ((), ())),
        preferred_element_type=sd)
    return (lambda a, b: dot(a, b, ((1,), (0,))),       # a b
            lambda a, b: dot(a, b, ((1,), (1,))),       # a b^T
            lambda a, b: dot(a, b, ((0,), (0,))))       # a^T b


def _combine(row_out, token_rows):
    """``out[t] = sum_j row_out[token_rows[t, j]]``: one gather a slot,
    added in ``row_out``'s dtype."""
    out = row_out[token_rows[:, 0]]
    for j in range(1, token_rows.shape[1]):
        out = out + row_out[token_rows[:, j]]
    return out


def _sink(n, d, tile, rows, token_rows, dtype):
    """The buffer a walk writes its tiles into: in token order, ``n +
    tile`` rows (a padding row adds to one of the tile's rows past the last
    token), where ``token_rows`` is None; else in row order, a row of the
    tables and one past them, which stays nought."""
    return jnp.zeros((n + tile if token_rows is None else rows + 1, d), dtype)


def _put(out, i, tile, rows, part, token_rows):
    """Tile ``i``'s ``part`` into the walk's buffer: added to its tokens'
    rows, or written to its own rows."""
    if token_rows is None:
        return out.at[rows].add(part, unique_indices=True,
                                indices_are_sorted=True)
    return jax.lax.dynamic_update_slice_in_dim(out, part, i * tile, 0)


def _drain(out, n, token_rows):
    """[n, d] of the walk's buffer: its first ``n`` rows, or each token's
    rows combined."""
    if token_rows is None:
        return out[:n]
    with jax.named_scope("dispatch"):
        return _combine(out, token_rows)


def grouped_ffn(x, w_gate, w_up, w_down, row_token, row_weight, tile_expert,
                tiles, token_rows, tile, compute_dtype):
    """``y[t] = sum over the rows r of token t of row_weight[r] *
    Expert_{e(r)}(x[t])`` with ``Expert_e(x) = (silu(x Wgate_e) * (x Wup_e))
    Wdown_e``: ``x`` [n, d], the held experts' leaves ``w_gate``, ``w_up``
    [E, d, f], ``w_down`` [E, f, d], :func:`routing_tables`' tables and
    :func:`token_rows`' (or None) -> [n, d] in the accumulator dtype. One
    loop over the first ``tiles`` tiles, a traced count that is at least the
    tiles in use (the layer walks its standing provision when fewer are,
    :func:`tile_plan`, and the tiles in use when more: the work never
    follows the tables' size, the worst routing's; a tile past those in use
    holds padding rows of weight nought and adds nothing); a turn gathers a
    tile's rows, runs one expert's three products on them and writes the
    weighted result to the tile's own rows of a buffer in row order, which
    one gather a slot turns into each token's sum after the loop; without
    ``token_rows`` a turn adds the result to its tokens' rows instead
    (:data:`COMBINE_PER_WALKED_ROW`). Differentiated by a rule of its own (a
    loop of a traced length has no transpose): the same walk again,
    recomputing a tile's hidden state."""
    n, d = x.shape
    ab, _, _ = _dots(compute_dtype)

    def turn(i, out):
        with jax.named_scope("dispatch"):
            rows, weight = _tile(i, tile, row_token, row_weight)
            xt = x[rows]
        with jax.named_scope("experts"):
            wg, wu, wd = _expert((w_gate, w_up, w_down), tile_expert[i],
                                 compute_dtype)
            yt = ab(jax.nn.silu(ab(xt, wg)) * ab(xt, wu), wd)
        with jax.named_scope("dispatch"):
            return _put(out, i, tile, rows,
                        weight[:, None].astype(yt.dtype) * yt, token_rows)

    out = _sink(n, d, tile, row_token.shape[0], token_rows,
                acc_dtype(compute_dtype))
    return _drain(jax.lax.fori_loop(0, tiles, turn, out), n, token_rows)


def _grouped_ffn_fwd(x, w_gate, w_up, w_down, row_token, row_weight,
                     tile_expert, tiles, token_rows, tile, compute_dtype):
    args = (x, w_gate, w_up, w_down, row_token, row_weight, tile_expert, tiles,
            token_rows)
    return _grouped_ffn(*args, tile, compute_dtype), args


def _grouped_ffn_bwd(tile, compute_dtype, kept, dy):
    (x, w_gate, w_up, w_down, row_token, row_weight, tile_expert, tiles,
     token_rows) = kept
    n, d = x.shape
    sd = acc_dtype(compute_dtype)
    ab, abt, atb = _dots(compute_dtype)
    add_row = lambda acc, e, part: jax.lax.dynamic_update_index_in_dim(
        acc, jax.lax.dynamic_index_in_dim(acc, e, 0) + part[None], e, 0)

    def turn(i, carry):
        dx, dwg, dwu, dwd, dweight = carry
        e = tile_expert[i]
        with jax.named_scope("dispatch"):
            rows, weight = _tile(i, tile, row_token, row_weight)
            xt, dyt = x[rows], dy[rows].astype(sd)
        with jax.named_scope("experts"):
            wg, wu, wd = _expert((w_gate, w_up, w_down), e, compute_dtype)
            g, u = ab(xt, wg), ab(xt, wu)
            gate = jax.nn.sigmoid(g)
            act = g * gate
            h = act * u
            dweight_t = jnp.sum(dyt * ab(h, wd), axis=-1)
            dyt = weight[:, None].astype(sd) * dyt
            dh = abt(dyt, wd)
            du, dg = dh * act, dh * u * (gate * (1 + g * (1 - gate)))
            dxt = abt(dg, wg) + abt(du, wu)
            dwg, dwu, dwd = (add_row(dwg, e, atb(xt, dg)),
                             add_row(dwu, e, atb(xt, du)),
                             add_row(dwd, e, atb(h, dyt)))
        with jax.named_scope("dispatch"):
            dx = _put(dx, i, tile, rows, dxt, token_rows)
            dweight = jax.lax.dynamic_update_slice_in_dim(
                dweight, dweight_t.astype(dweight.dtype), i * tile, 0)
        return dx, dwg, dwu, dwd, dweight

    zeros = lambda like: jnp.zeros(like.shape, sd)
    dx, dwg, dwu, dwd, dweight = jax.lax.fori_loop(0, tiles, turn, (
        _sink(n, d, tile, row_token.shape[0], token_rows, sd), zeros(w_gate),
        zeros(w_up), zeros(w_down),
        jnp.zeros(row_weight.shape, row_weight.dtype)))
    no = lambda t: None if t is None else np.zeros(t.shape, jax.dtypes.float0)
    return (_drain(dx, n, token_rows).astype(x.dtype),
            dwg.astype(w_gate.dtype), dwu.astype(w_up.dtype),
            dwd.astype(w_down.dtype), no(row_token), dweight,
            no(tile_expert), no(tiles), no(token_rows))


_grouped_ffn = grouped_ffn
grouped_ffn = jax.custom_vjp(_grouped_ffn, nondiff_argnums=(9, 10))
grouped_ffn.defvjp(_grouped_ffn_fwd, _grouped_ffn_bwd)


@implements("RoutedExpertsLayer")
class RoutedExpertsImpl(LayerImpl):
    """See the config class. Leaves: ``Wr`` [n_in, num_experts] (the
    router, over every published expert), the held experts' ``We_gate``,
    ``We_up`` [held, n_in, n_hidden], ``We_down`` [held, n_hidden, n_out],
    the shared expert's ``Ws_gate``, ``Ws_up`` [n_in, shared_hidden],
    ``Ws_down`` [shared_hidden, n_out]. State, of sigmoid scores only: ``b``
    [num_experts], the score-correction bias, which enters the choice only
    and which no gradient reaches."""

    EXPERT_KEYS = ("We_gate", "We_up", "We_down")
    SHARED_KEYS = ("Ws_gate", "Ws_up", "Ws_down")

    def __init__(self, conf, gc, input_type=None):
        super().__init__(conf, gc, input_type)
        c = conf
        held = list(c.experts_held if c.experts_held is not None
                    else range(c.num_experts))
        if (not held or len(set(held)) != len(held)
                or not all(0 <= e < c.num_experts for e in held)):
            raise ValueError(
                f"RoutedExpertsLayer: experts_held lists distinct ids of the "
                f"{c.num_experts} published experts; got {held}")
        if not 1 <= c.top_k <= c.num_experts:
            raise ValueError(f"RoutedExpertsLayer needs 1 <= top_k <= "
                             f"num_experts (got {c.top_k}, {c.num_experts})")
        if c.score not in ("sigmoid", "softmax"):
            raise ValueError(f"RoutedExpertsLayer: score is 'sigmoid' or "
                             f"'softmax'; got {c.score!r}")
        self.held = held

    def init(self, rng, lead=()):
        """``lead``: leading dimensions of every leaf (a stack of layers)."""
        c = self.conf
        E, f, fs = len(self.held), int(c.n_hidden), int(c.shared_hidden or 0)
        shapes = {"Wr": (c.n_in, c.num_experts),
                  "We_gate": (E, c.n_in, f), "We_up": (E, c.n_in, f),
                  "We_down": (E, f, c.n_out)}
        if fs:
            shapes.update({"Ws_gate": (c.n_in, fs), "Ws_up": (c.n_in, fs),
                           "Ws_down": (fs, c.n_out)})
        params = {name: self._init_w(key, lead + shape, *shape[-2:])
                  for (name, shape), key in zip(
                      shapes.items(), jax.random.split(rng, len(shapes)))}
        if c.score == "softmax":
            return params, {}
        return params, {"b": host_full(lead + (c.num_experts,), 0, self.dtype)}

    def route(self, x, w_router, bias):
        """(the k chosen experts [n, k], their weights [n, k]) of ``x``
        [n, n_in], at least float32 and with the product at full precision:
        a choice is a comparison of scores. ``bias``: the sigmoid scores'
        correction, None for softmax scores."""
        c = self.conf
        rdt = jnp.promote_types(jnp.float32, self.dtype)
        logits = jnp.dot(x.astype(rdt), w_router.astype(rdt),
                         precision=jax.lax.Precision.HIGHEST)
        if c.score == "softmax":
            scores = jax.nn.softmax(logits, axis=-1)
            _, chosen = jax.lax.top_k(scores, int(c.top_k))
        else:
            scores = jax.nn.sigmoid(logits)
            _, chosen = jax.lax.top_k(
                scores + jax.lax.stop_gradient(bias.astype(rdt)),
                int(c.top_k))
        weights = jnp.take_along_axis(scores, chosen, axis=-1)
        if c.renormalize:
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                                 + 1e-20)
        return chosen, weights * c.routed_scaling_factor

    def forward(self, params, state, x, train=False, rng=None, mask=None,
                ctx=None):
        c = self.conf
        cd = self.compute_dtype
        x = self.maybe_dropout(x, train, rng)
        flat = x.reshape(-1, x.shape[-1])
        held = len(self.held)
        tile, standing = tile_plan(flat.shape[0], int(c.top_k), held,
                                   c.num_experts)
        with jax.named_scope("router"):
            chosen, weights = self.route(flat, params["Wr"], state.get("b"))
        with jax.named_scope("dispatch"):
            local = np.full((c.num_experts,), held, np.int32)
            local[self.held] = np.arange(held)
            local = jnp.asarray(local)[chosen]
            *tables, in_use = routing_tables(local, weights, held, tile)
            standing = min(standing, tables[0].shape[0] // tile)
            slots = flat.shape[0] * min(int(c.top_k), held)
            combine = slots <= COMBINE_PER_WALKED_ROW * standing * tile
            tables += [jnp.maximum(in_use, standing),
                       token_rows(local, held, tile) if combine else None]
            layer = str(getattr(self, "index", ""))
            gauge = get_registry().gauge
            for which, count in (("held", held),
                                 ("published", c.num_experts)):
                gauge("moe_experts",
                      "Experts of a routed expert layer: held on this chip "
                      "and published (the router's width), set when the "
                      "layer is traced", layer=layer, which=which).set(count)
            gauge("moe_rows_sized",
                  "Rows the tables of a routed expert layer's grouped "
                  "products are sized for (the worst routing; the products "
                  "walk the standing rows, or the tiles in use where those "
                  "are more), set when the layer is traced",
                  layer=layer).set(int(tables[0].shape[0]))
            gauge("moe_rows_standing",
                  "Rows a routed expert layer's grouped products walk "
                  "whatever the routing (twice what uniform routing sends "
                  "here and a tile for every held expert), set when the "
                  "layer is traced", layer=layer).set(standing * tile)
            gauge("moe_combine_slots",
                  "Rows the gather that adds up each token's rows of a "
                  "routed expert layer reads a pass (min(k, held) a token; "
                  "0 where the layer adds its rows by a scatter-add), set "
                  "when the layer is traced",
                  layer=layer).set(slots if combine else 0)
        xc = flat.astype(cd)
        y = grouped_ffn(xc, *(params[k] for k in self.EXPERT_KEYS), *tables,
                        tile, cd)
        if "Ws_gate" in params:
            with jax.named_scope("shared"):
                ab = _dots(cd)[0]
                y = y + ab(jax.nn.silu(ab(xc, params["Ws_gate"]))
                           * ab(xc, params["Ws_up"]), params["Ws_down"])
        return self.activation(y.reshape(x.shape[:-1] + (c.n_out,))).astype(
            self.out_dtype), state
