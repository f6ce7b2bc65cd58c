"""Plain reference of ``granite_4_0_h_micro`` (IBM Granite 4.0-H Micro,
``model_type`` granitemoehybrid with no experts: Mamba-2 state-space layers
and grouped-query attention without positions; ``config.json`` of
``ibm-granite/granite-4.0-h-micro``), written from the equations in float32
``jax.numpy``; d the hidden size, r the residual multiplier:

    RMSNorm_g(x) = x / sqrt(mean(x^2, last axis) + eps) * g
    h0 = 12 E[ids]                                    (embedding multiplier)
    layer:  u = h + r Mixer(RMSNorm_g1(h));  h' = u + r MLP(RMSNorm_g2(u))
    MLP(x) = (silu(x Wgate) * (x Wup)) Wdown
    attention mixer:  q = x Wq [h heads of D];  k, v = x Wk, x Wv [g heads];
        query head i reads key-value head i // (h / g); no positions;
        o = softmax(0.015625 q k^T, causal) v;  out = o Wo
    state-space mixer (Mamba-2, H heads of P, state N, one group):
        [z | xBC | dt] = x W_in
        xBC = silu(conv1d(xBC))      depthwise, causal, width K, with bias
        [x | B | C] = xBC;  D_t = softplus(dt_t + dt_bias)   [H]
        a_t = exp(D_t A),  A = -exp(A_log)
        S_t = a_t S_{t-1} + D_t x_t (x) B_t   [H, P, N],  S_0 = 0
        y_t = S_t C_t + D x_t
        out = RMSNorm_gn(y * silu(z)) W_out     over all H P channels
    logits = RMSNorm_gf(h_L) E^T / 8          (tied head, logits scaling)
    loss = sum over tokens of -log softmax(logits)[label] / batch

It is handed the network's own parameters and knows their names and layout:
``embed.W`` [V, d] (the head too: ``out`` holds nothing); ``stack`` holds
every run of like layers stacked leaf by leaf [n, ...] under ``r<run>.<leaf>``
(a state-space run: ``W_in`` [n, d, 2 H P + 2 N + H], ``conv_W`` [n, H P +
2 N, K], ``conv_bias``, ``dt_bias``, ``A_log``, ``D`` [n, H], ``gn``
[n, H P], ``W_out`` [n, H P, d]; an attention run: ``Wq``, ``Wo``, ``Wk``,
``Wv``; both: ``Wgate``, ``Wup`` [n, d, F], ``Wdown`` [n, F, d], the gains
``g1``, ``g2`` [n, d]) and the final norm's ``gf`` [d]. A run's kind and all
sizes but the number of query heads are read off the leaves; the heads and
the four multipliers default to the published values of
``configs/granite_4_0_h_micro.json``.

The state-space layer is the recurrence itself, one ``lax.scan`` step a
token: nothing of the program's chunks, cumulative sums or masked products.
Departures from the literal text, all so that one 8192-token sample's loss
AND gradients fit on one chip, none changing a number: the recurrence runs
as an outer scan over blocks of ``STEPS`` steps under ``jax.checkpoint``
(one [H, P, N] state a step is 2 MB, 17 GB over 8192 steps; kept are the
states at the blocks' ends), every layer is under ``jax.checkpoint``,
attention goes head by head (``lax.map``, one [T, T] score matrix alive),
and the cross-entropy runs in chunks of ``CHUNK`` tokens (one [CHUNK, V]
logit matrix alive).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: by the configuration's compute dtype; ``loss`` relative, ``grads``
#: ||g - g_ref|| / ||g_ref|| over all parameters together, ``leaves`` the
#: same of single leaves by their path. float32 is the CPU test's bar.
#: bfloat16 is the chip's, from readings on the v5e at the cell's sizes (one
#: 8192-token sample, published widths; my chip runs, PR 33: twelve seeds in
#: one process, the control and the faults on four of them;
#: ``chiprun_out/pr33/survey.jsonl`` while it lasts, PERF.md section 6):
#:  - the system, 15 seeds (12 of the survey, 3 whole runs of the cell):
#:    loss 2.0e-7 .. 2.1e-6 (the logits, the softmax statistics and the loss
#:    are float32 on both sides); all gradients 2.058e-2 .. 2.068e-2 (every
#:    leaf reads 1.8e-2 .. 2.5e-2: the bf16 operands of the gemms, nothing
#:    that swings with the seed); the tied leaf ``embed.W`` 2.021e-2 ..
#:    2.031e-2; ``r0.A_log`` (64 numbers a layer, five layers) 1.75e-2 ..
#:    2.96e-2;
#:  - control, the reference in the program's place with every product's
#:    operands rounded to float8 (e4m3's three mantissa bits), the precision
#:    below the bf16 the configuration states
#:    (``tests/benchmark/test_benchmark_granite.py`` has it), four seeds:
#:    all gradients 0.2637 .. 0.2644, ``embed.W`` 0.2571 .. 0.2577,
#:    ``r0.A_log`` 0.253 .. 0.287; its loss 7.6e-6 .. 1.9e-5, under the
#:    loss's limit: it is not correct by the three gradient limits;
#:  - fault, the state not carried across a chunk boundary (every chunk of
#:    the system's scan starts from nought), the same four seeds: all
#:    gradients 0.111 .. 0.154, ``embed.W`` 0.109 .. 0.150, ``r0.A_log``
#:    0.304 .. 0.498 (the decay's own leaf is where it shows most); its
#:    loss 3.0e-7 .. 7.8e-6, as a sound run's: at random weights the state
#:    a chunk inherits moves the loss by nothing a float32 loss shows;
#:  - fault, the tie cut (the head reads the embedding's values and its
#:    gradient goes nowhere: head and embedding as two leaves), the same
#:    four: ``embed.W`` 0.2968 .. 0.2982, all gradients 0.1897 .. 0.1906;
#:    every other leaf and the loss read as the sound run's, so it is not
#:    correct by two of the limits and not by ``r0.A_log``'s.
#: The limits: all gradients 0.05, 2.4 times over the system's largest and
#: 2.2 under the smallest of control and faults (the state not carried,
#: 0.111); ``embed.W`` 0.05, 2.5 over and 2.2 under (the same fault, 0.109);
#: ``r0.A_log`` 0.09, 3.0 over its largest and 2.8 under the control's
#: smallest (0.253); the loss 1e-4, the harness's, 48 times over the
#: system's largest. NOT held (two seeds on the chip, PR 33; the
#: configuration's ``does_not_hold`` has the readings): bf16 logits, a bf16
#: residual stream, a bf16 state between chunks.
TOLERANCE = {"float32": {"loss": 1e-4, "grads": 1e-4},
             "bfloat16": {"loss": 1e-4, "grads": 0.05,
                          "leaves": {"['embed']['W']": 0.05,
                                     "['stack']['r0.A_log']": 0.09}}}

_HI = lax.Precision.HIGHEST
#: tokens whose logits are alive at once in the cross-entropy
CHUNK = 1024
#: steps of the recurrence between two kept states
STEPS = 64


def rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def attention(q, k, v, scale):
    """Causal softmax attention, ``q`` [b, T, h, D], ``k``, ``v``
    [b, T, g, D]: query head i reads key-value head i // (h / g)."""
    T, h = q.shape[1], q.shape[2]
    group = h // k.shape[2]
    causal = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def head(i):
        qh = lax.dynamic_index_in_dim(q, i, axis=2, keepdims=False)
        kh = lax.dynamic_index_in_dim(k, i // group, axis=2, keepdims=False)
        vh = lax.dynamic_index_in_dim(v, i // group, axis=2, keepdims=False)
        s = jnp.einsum("bqd,bkd->bqk", qh, kh, precision=_HI) * scale
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", p, vh, precision=_HI)

    return jnp.moveaxis(lax.map(head, jnp.arange(h)), 0, 2)


def attention_mixer(p, x, heads, scale):
    b, T, _ = x.shape
    D = p["Wq"].shape[1] // heads
    q, k, v = (jnp.dot(x, p[w], precision=_HI).reshape(b, T, -1, D)
               for w in ("Wq", "Wk", "Wv"))
    return jnp.dot(attention(q, k, v, scale).reshape(b, T, -1), p["Wo"],
                   precision=_HI)


def selective_scan(x, delta, a, B, C):
    """``y_t = S_t C_t`` of ``S_t = a_t S_{t-1} + delta_t x_t (x) B_t``,
    ``S_0 = 0``, step by step: ``x`` [b, T, H, P], ``delta``, ``a``
    [b, T, H], ``B``, ``C`` [b, T, N] -> [b, T, H, P]."""
    b, T, H, P = x.shape
    steps = STEPS if T % STEPS == 0 else T

    def step(S, at_t):
        x_t, d_t, a_t, B_t, C_t = at_t
        S = (a_t[..., None, None] * S
             + (d_t[..., None] * x_t)[..., None] * B_t[:, None, None, :])
        return S, jnp.sum(S * C_t[:, None, None, :], axis=-1)

    @jax.checkpoint
    def block(S, over_block):
        return lax.scan(step, S, over_block)

    by_step = [jnp.moveaxis(t, 1, 0).reshape((T // steps, steps)
                                             + t.shape[:1] + t.shape[2:])
               for t in (x, delta, a, B, C)]
    _, y = lax.scan(block, jnp.zeros((b, H, P, B.shape[-1]), x.dtype),
                    tuple(by_step))
    return jnp.moveaxis(y.reshape(T, b, H, P), 0, 1)


def causal_depthwise_conv(x, w, bias):
    """``x`` [b, T, C], ``w`` [C, K]: channel c at step t reads its own
    steps t - K + 1 .. t (nought before the first) against ``w[c]``."""
    K = w.shape[1]
    y = lax.conv_general_dilated(
        x, jnp.transpose(w)[:, None, :], window_strides=(1,),
        padding=[(K - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=x.shape[-1], precision=_HI)
    return y + bias


def mamba_mixer(p, x, eps):
    b, T, _ = x.shape
    H = p["A_log"].shape[0]
    d_inner, conv = p["W_out"].shape[0], p["conv_W"].shape[0]
    N = (conv - d_inner) // 2
    zxbcdt = jnp.dot(x, p["W_in"], precision=_HI)
    z, xbc, dt = (zxbcdt[..., :d_inner], zxbcdt[..., d_inner:d_inner + conv],
                  zxbcdt[..., d_inner + conv:])
    xbc = jax.nn.silu(causal_depthwise_conv(xbc, p["conv_W"], p["conv_bias"]))
    xs = xbc[..., :d_inner].reshape(b, T, H, d_inner // H)
    B, C = xbc[..., d_inner:d_inner + N], xbc[..., d_inner + N:]
    delta = jax.nn.softplus(dt + p["dt_bias"])
    a = jnp.exp(delta * -jnp.exp(p["A_log"]))
    y = selective_scan(xs, delta, a, B, C) + p["D"][:, None] * xs
    y = rms_norm(y.reshape(b, T, d_inner) * jax.nn.silu(z), p["gn"], eps)
    return jnp.dot(y, p["W_out"], precision=_HI)


def mlp(p, x):
    dot = lambda a, w: jnp.dot(a, w, precision=_HI)
    return dot(jax.nn.silu(dot(x, p["Wgate"])) * dot(x, p["Wup"]), p["Wdown"])


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


def hidden_state(params, ids, heads, attention_multiplier,
                 embedding_multiplier, residual_multiplier, eps):
    """The final-normed state [b, T, d]."""
    r = residual_multiplier

    @jax.checkpoint
    def layer(p, h):
        n = rms_norm(h, p["g1"], eps)
        mixed = (mamba_mixer(p, n, eps) if "W_in" in p
                 else attention_mixer(p, n, heads, attention_multiplier))
        u = h + r * mixed
        return u + r * mlp(p, rms_norm(u, p["g2"], eps))

    h = embedding_multiplier * params["embed"]["W"][ids.astype(jnp.int32)]
    for p in layers_of(params["stack"]):
        h = layer(p, h)
    return rms_norm(h, params["stack"]["gf"], eps)


def next_token_xent(h, embedding, labels, logits_scaling):
    """-log softmax(h E^T / scaling)[label] per token: ``h`` [b, T, d],
    ``labels`` [b, T] -> [b, T]."""
    b, T, d = h.shape
    n = b * T
    chunk = CHUNK if n % CHUNK == 0 else n

    @jax.checkpoint
    def one(args):
        hc, lc = args
        z = jnp.dot(hc, embedding.T, precision=_HI) / logits_scaling
        logp = jax.nn.log_softmax(z, axis=-1)
        return -jnp.take_along_axis(logp, lc[:, None], axis=-1)[:, 0]

    out = lax.map(one, (h.reshape(n // chunk, chunk, d),
                        labels.reshape(n // chunk, chunk)))
    return out.reshape(b, T)


def loss(params, ids, labels, heads=32, attention_multiplier=0.015625,
         embedding_multiplier=12.0, residual_multiplier=0.22,
         logits_scaling=8.0, eps=1e-5):
    """``ids``, ``labels``: int [b, T]. Summed over tokens, averaged over
    the batch, as the system's ``sparse_mcxent`` reduces."""
    h = hidden_state(params, ids, heads, attention_multiplier,
                     embedding_multiplier, residual_multiplier, eps)
    xent = next_token_xent(h, params["embed"]["W"], labels.astype(jnp.int32),
                           logits_scaling)
    return jnp.sum(xent) / ids.shape[0]
