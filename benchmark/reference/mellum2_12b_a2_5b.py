"""Plain reference of ``mellum2_12b_a2_5b`` (JetBrains' Mellum2
12B-A2.5B-Instruct, ``model_type`` mellum; ``config.json`` of
``JetBrains/Mellum2-12B-A2.5B-Instruct``), written from the equations in
float32 ``jax.numpy``; d the hidden size, H query heads over G key-value
heads of D channels, head n reading key-value head n // (H / G):

    RMSNorm_g(x) = x / sqrt(mean(x^2, last axis) + eps) * g
    h0 = E[ids]                                          (no multiplier)
    layer:  u = h + Attn(RMSNorm_g1(h));  h' = u + Experts(RMSNorm_g2(u))
    Attn:   q = x Wq [H, D];  k = x Wk, v = x Wv [G, D]   (no bias)
            q, k rotated at positions 0 .. T-1, the pair (i, i + D/2) by
            t * inv_freq_i (rotate-half):
              sliding layer:  inv_freq_i = theta^(-2i/D), cos and sin as they are
              full layer, YaRN (theta, factor s, original length L, beta_fast
              bf, beta_slow bs, attention factor m):
                ext_i = theta^(-2i/D);  low = floor(D ln(L / (bf 2 pi)) / (2 ln theta))
                high = ceil(D ln(L / (bs 2 pi)) / (2 ln theta))
                r_i = clip((i - low) / (high - low), 0, 1)
                inv_freq_i = ext_i / s * r_i + ext_i * (1 - r_i);  cos, sin times m
            o_t = softmax_j(D^-0.5 q_t . k_j) v_j over the visible j:
              sliding layer j in (t - window, t];  full layer j <= t
            out = o Wo
    Experts (E published, k chosen, the held ids 0 .. e - 1):
            p = softmax(x Wr)                               [E]
            top = the k largest of p;  w_j = p_j / sum_{i in top} p_i
            y = sum_{j in top, j held} w_j (silu(x Wgate_j) * (x Wup_j)) Wdown_j
    logits = RMSNorm_gf(h_L) W_head                      (untied head)
    loss = sum over tokens of -log softmax(logits)[label] / batch

The sum over the held experts is this chip's part of the published layer
(``model-configs`` guide, section 4): what the absent experts would add is
left out here as in the program; ``experts`` says how many are held, and the
whole layer is ``experts`` = E.

It is handed the network's own parameters and knows their names and layout:
``embed.W`` [V, d], ``out.W`` [d, V]; ``stack`` holds every run of like
layers stacked leaf by leaf [n, ...] under ``r<run>.<leaf>`` (``Wq`` [n, d,
H D], ``Wk``, ``Wv`` [n, d, G D], ``Wo`` [n, H D, d]; ``Wr`` [n, d, E],
``We_gate``, ``We_up`` [n, e, d, f], ``We_down`` [n, e, f, d]; the gains
``g1``, ``g2``) and the final norm's ``gf``. The layers come in the order of
the runs, and their kinds in the order of ``layer_types``; the head size, the
window, the rotations, the experts chosen a token and the norms' eps default
to the published values of ``configs/mellum2_12b_a2_5b.json``.

Nothing of the program's kernels, blocks of pairs, tables, tiles or grouped
products. Departures from the literal text, all so that one 8192-token
sample's loss AND gradients fit on one chip, none changing a number:
attention goes head by head and, within a head, in blocks of ``QBLOCK``
queries (``lax.map``, each under ``jax.checkpoint``), a sliding layer's block
reading only the ``QBLOCK`` + window keys around it (the rest are hidden by
the window anyway); every layer and every held expert is under
``jax.checkpoint``; the experts are a plain loop over the held ids, each run on
every token and weighted by nought where it was not chosen; the cross-entropy
runs in chunks of ``CHUNK`` tokens.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: by the configuration's compute dtype; ``loss`` relative, ``grads``
#: ||g - g_ref|| / ||g_ref|| over all parameters together. float32 is the
#: CPU test's bar. bfloat16 is the chip's, from readings on the v5e at the
#: cell's sizes (one 8192-token sample, published widths; my chip runs,
#: PR 41; PERF.md section 6):
#:  - the system as it ships (the embedding drawn from N(0, 1)), four whole
#:    runs of the cell, the harness's eager check: loss 3.7e-7 .. 6.4e-6,
#:    all gradients 1.13e-2 .. 1.18e-2 (the worst leaf a router's, ``r0.Wr``
#:    3.6e-2 .. 3.9e-2); as first handed in (the embedding by Xavier),
#:    five runs: loss 1.8e-6 .. 5.4e-6, all gradients 2.81e-2 .. 3.50e-2;
#:  - control, the reference in the program's place with every product's
#:    operands rounded to float8 (e4m3's three mantissa bits), the precision
#:    below the bf16 the configuration states
#:    (``tests/benchmark/test_benchmark_mellum2.py`` has it): as it ships,
#:    seed 3141000505, loss 2.9e-5 and all gradients 0.0727, 4.5 and 6.2
#:    times the largest sound readings; with the Xavier embedding, seed
#:    3141000101, all gradients 0.182 but loss 6.6e-7, under that seed's
#:    sound 5.3e-6: the loss alone does not tell the control on every seed;
#:  - faults planted in the program, read with the Xavier embedding, seed
#:    3141000101 (sound 3.44e-2): the window not applied 0.983 on all
#:    gradients, loss 1.1e-3; YaRN replaced by the default rotary 0.373
#:    (``r1.Wq`` 0.842), loss 1.8e-5; YaRN's attention factor dropped 0.186
#:    (``r1.Wq`` 0.413), loss 2.0e-6; the renormalisation over the held
#:    choices only 2.06, loss 3.8e-4; every one fails the float32 limit at
#:    rehearsal size too.
#: The limits, each between its two readings: all gradients 0.03, 2.5
#: times over the largest sound reading as it ships and 2.4 under the
#: control's; the loss 1.5e-5, 2.3 times over the largest sound reading as
#: it ships and 1.9 under the control's. Not read as it ships: the faults
#: (PERF.md section 7). What no limit catches is in the configuration's
#: ``correct_sample.does_not_hold``.
TOLERANCE = {"float32": {"loss": 1e-4, "grads": 1e-4},
             "bfloat16": {"loss": 1.5e-5, "grads": 0.03}}

_HI = lax.Precision.HIGHEST
#: tokens whose logits are alive at once in the cross-entropy
CHUNK = 1024
#: queries of one block of attention
QBLOCK = 1024
#: the published ``layer_types``' period, and its rotations
LAYER_TYPES = ["sliding_attention"] * 3 + ["full_attention"]
ROPE = {"full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                           "factor": 16, "original_max_position_embeddings":
                           8192, "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}


def rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def dot(a, w):
    return jnp.dot(a, w, precision=_HI)


def gated(x, w_gate, w_up, w_down):
    return dot(jax.nn.silu(dot(x, w_gate)) * dot(x, w_up), w_down)


def inv_freq(D, rope):
    """(inv_freq [D / 2] as float64, the factor on cos and sin) of one kind
    of layer's ``rope_parameters``."""
    theta = float(rope["rope_theta"])
    ext = theta ** (-2.0 * np.arange(D // 2) / D)
    if rope.get("rope_type", "default") == "default":
        return ext, 1.0
    L, s = float(rope["original_max_position_embeddings"]), rope["factor"]
    edge = lambda turns: D * math.log(L / (turns * 2 * math.pi)) / (
        2 * math.log(theta))
    low = max(math.floor(edge(rope["beta_fast"])), 0)
    high = min(math.ceil(edge(rope["beta_slow"])), D - 1)
    r = np.clip((np.arange(D // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return ext / s * r + ext * (1 - r), float(rope["attention_factor"])


def rotate(x, rope):
    """``x`` [b, T, heads, D] rotated at positions 0 .. T-1."""
    T, D = x.shape[1], x.shape[-1]
    freq, m = inv_freq(D, rope)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(
        freq, jnp.float32)
    cos, sin = m * jnp.cos(angle)[:, None], m * jnp.sin(angle)[:, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window):
    """``q`` [b, T, H, D], ``k``, ``v`` [b, T, G, D] -> [b, T, H, D]: each
    query head against its key-value head, causal, and within ``window``
    keys where it is not None."""
    b, T, H, D = q.shape
    group = H // k.shape[2]
    block = QBLOCK if T % QBLOCK == 0 else T
    span = T if window is None else min(block + window, T)

    def head(n):
        qh = lax.dynamic_index_in_dim(q, n, axis=2, keepdims=False)
        kh, vh = (lax.dynamic_index_in_dim(t, n // group, axis=2,
                                           keepdims=False) for t in (k, v))

        @jax.checkpoint
        def rows(i):
            start = i * block
            qb = lax.dynamic_slice_in_dim(qh, start, block, axis=1)
            first = jnp.clip(start + block - span, 0, T - span)
            kb, vb = (lax.dynamic_slice_in_dim(t, first, span, axis=1)
                      for t in (kh, vh))
            qpos = start + jnp.arange(block)[:, None]
            kpos = first + jnp.arange(span)[None, :]
            seen = kpos <= qpos
            if window is not None:
                seen &= kpos > qpos - window
            s = jnp.einsum("bqd,bkd->bqk", qb, kb, precision=_HI) * D ** -0.5
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return jnp.einsum("bqk,bkd->bqd", p, vb, precision=_HI)

        out = lax.map(rows, jnp.arange(T // block))      # [n, b, block, D]
        return jnp.moveaxis(out, 0, 1).reshape(b, T, D)

    return jnp.moveaxis(lax.map(head, jnp.arange(H)), 0, 2)


def attention_mixer(p, x, kind, window, head_dim, rope):
    b, T, _ = x.shape
    heads = lambda t: t.reshape(b, T, -1, head_dim)
    q, k, v = heads(dot(x, p["Wq"])), heads(dot(x, p["Wk"])), heads(
        dot(x, p["Wv"]))
    q, k = rotate(q, rope[kind]), rotate(k, rope[kind])
    o = attention(q, k, v, window if kind == "sliding_attention" else None)
    return dot(o.reshape(b, T, -1), p["Wo"])


def experts_ffn(p, x, top_k, experts=None):
    """The part of the routed sum that the experts 0 .. ``experts`` - 1 give
    (None: as many as the leaves hold)."""
    probs = jax.nn.softmax(dot(x, p["Wr"]), axis=-1)
    _, top = lax.top_k(probs, top_k)
    chosen = jnp.take_along_axis(probs, top, axis=-1)
    weight = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    one = jax.checkpoint(gated)
    y = jnp.zeros_like(x)
    for e in range(p["We_gate"].shape[0] if experts is None else experts):
        w_e = jnp.sum(jnp.where(top == e, weight, 0.0), axis=-1)
        y = y + w_e[..., None] * one(x, p["We_gate"][e], p["We_up"][e],
                                     p["We_down"][e])
    return y


def layers_of(stack):
    """One dict of leaves a layer, in order, from the stacked runs."""
    out, i = [], 0
    while any(k.startswith(f"r{i}.") for k in stack):
        run = {k.partition(".")[2]: v for k, v in stack.items()
               if k.startswith(f"r{i}.")}
        out += [{k: v[l] for k, v in run.items()}
                for l in range(run["g1"].shape[0])]
        i += 1
    return out


def hidden_state(params, ids, top_k, window, head_dim, eps, layer_types,
                 rope):
    """The final-normed state [b, T, d]."""

    def layer(p, h, kind):
        u = h + attention_mixer(p, rms_norm(h, p["g1"], eps), kind, window,
                                head_dim, rope)
        return u + experts_ffn(p, rms_norm(u, p["g2"], eps), top_k)

    layer = jax.checkpoint(layer, static_argnums=(2,))
    h = params["embed"]["W"][ids.astype(jnp.int32)]
    for p, kind in zip(layers_of(params["stack"]), layer_types):
        h = layer(p, h, kind)
    return rms_norm(h, params["stack"]["gf"], eps)


def next_token_xent(h, head, labels):
    """-log softmax(h W_head)[label] per token: ``h`` [b, T, d], ``labels``
    [b, T] -> [b, T]."""
    b, T, d = h.shape
    n = b * T
    chunk = CHUNK if n % CHUNK == 0 else n

    @jax.checkpoint
    def one(args):
        hc, lc = args
        logp = jax.nn.log_softmax(dot(hc, head), axis=-1)
        return -jnp.take_along_axis(logp, lc[:, None], axis=-1)[:, 0]

    out = lax.map(one, (h.reshape(n // chunk, chunk, d),
                        labels.reshape(n // chunk, chunk)))
    return out.reshape(b, T)


def loss(params, ids, labels, experts_per_token=8, window=1024, head_dim=128,
         eps=1e-6, layer_types=tuple(LAYER_TYPES), rope=ROPE):
    """``ids``, ``labels``: int [b, T]. Summed over tokens, averaged over
    the batch, as the system's ``sparse_mcxent`` reduces."""
    h = hidden_state(params, ids, experts_per_token, window, head_dim, eps,
                     layer_types, rope)
    xent = next_token_xent(h, params["out"]["W"], labels.astype(jnp.int32))
    return jnp.sum(xent) / ids.shape[0]
