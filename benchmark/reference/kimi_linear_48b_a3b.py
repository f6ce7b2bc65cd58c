"""Plain reference of ``kimi_linear_48b_a3b`` (Moonshot AI's Kimi Linear
48B-A3B-Instruct, ``model_type`` kimi_linear; ``config.json`` of
``moonshotai/Kimi-Linear-48B-A3B-Instruct``), written from the equations in
float32 ``jax.numpy``; d the hidden size:

    RMSNorm_g(x) = x / sqrt(mean(x^2, last axis) + eps) * g
    h0 = E[ids]                                          (no multiplier)
    layer:  u = h + Mixer(RMSNorm_g1(h));  h' = u + FFN(RMSNorm_g2(u))
    KDA mixer (H heads of K channels for keys and values alike):
        q, k, v = silu(conv(x Wq)), silu(conv(x Wk)), silu(conv(x Wv))
                                     depthwise, causal, width 4, no bias
        q, k = q / sqrt(sum(q^2) + 1e-6) * K^-0.5,  k / sqrt(sum(k^2) + 1e-6)
                                     per head, over its K channels
        g_t    = -exp(A_log[h]) softplus((x W_fa W_fb)_t + dt_bias)   [H, K]
        beta_t = sigmoid(x W_b)_t                                     [H]
        S_t = Diag(exp(g_t)) S_{t-1};  S_t += beta_t k_t (v_t - S_t^T k_t)^T
        o_t = S_t^T q_t                       S [K (key), K (value)], S_0 = 0
        out = (RMSNorm_gn(o) * sigmoid(x W_ga W_gb)) Wo     gn [K], per head
    MLA mixer (h heads; no rotation anywhere, ``mla_use_nope``):
        q = x Wq [h, N + R];  [c | kr] = x Wkv_a   c [rank], kr [R], one for
        all heads;  [kn | v] = RMSNorm_gc(c) Wkv_b   kn [h, N], v [h, Dv]
        k = [kn | kr];  o = softmax((N + R)^-0.5 q k^T, causal) v;  out = o Wo
    FFN, the first layer:  (silu(x Wgate) * (x Wup)) Wdown
    FFN, every later layer (E published experts, top k, the held ids 0..e-1):
        s   = sigmoid(x Wr)                                [E]
        top = the k largest of s   (+ b, the score-correction bias: nought
              and unmoved here, so it is left out)
        w_e = scaling * s_e / (sum_{j in top} s_j + 1e-20)       for e in top
        y   = Shared(x) + sum_{e in top, e held} w_e Expert_e(x)
        Shared, Expert_e: the gated form above at the experts' width
    logits = RMSNorm_gf(h_L) W_head                      (untied head)
    loss = sum over tokens of -log softmax(logits)[label] / batch

The sum over the held experts is this chip's part of the published layer
(``model-configs`` guide, section 4); ``experts`` says how many are held, and
the whole layer is ``experts`` = E.

It is handed the network's own parameters and knows their names and layout:
``embed.W`` [V, d], ``out.W`` [d, V]; ``stack`` holds every run of like layers
stacked leaf by leaf [n, ...] under ``r<run>.<leaf>`` (the KDA mixer's ``Wq``,
``Wk``, ``Wv`` [n, d, H K], ``conv_q``, ``conv_k``, ``conv_v`` [n, H K, 4],
``W_fa``, ``W_ga`` [n, d, K], ``W_fb``, ``W_gb`` [n, K, H K], ``dt_bias``
[n, H K], ``A_log`` [n, H], ``W_b`` [n, d, H], ``gn`` [n, K], ``Wo``; the MLA
mixer's ``Wq``, ``Wkv_a`` [n, d, rank + R], ``gc`` [n, rank], ``Wkv_b``
[n, rank, h (N + Dv)] (a head's N key channels, then its Dv value channels),
``Wo``; the dense MLP's ``Wgate``, ``Wup``, ``Wdown``; the experts' ``Wr``
[n, d, E], ``We_gate``, ``We_up`` [n, e, d, f], ``We_down`` [n, e, f, d],
``Ws_gate``, ``Ws_up``, ``Ws_down``; the gains ``g1``, ``g2``) and the final
norm's ``gf``. A layer's kind and every size are read off the leaves; the
experts chosen a token, the routed scaling factor and the norms' eps default
to the published values of ``configs/kimi_linear_48b_a3b.json``.

The KDA layer is the recurrence itself, one ``lax.scan`` step a token: nothing
of the program's chunks, cumulative decays, triangular solve or carried
states. The experts are a plain loop over the held ids, each expert run on
every token and masked: nothing of the program's sorting, tiles or grouped
products. Departures from the literal text, all so that one 8192-token
sample's loss AND gradients fit on one chip, none changing a number: the
recurrence runs as an outer scan over blocks of ``STEPS`` steps under
``jax.checkpoint`` (one [H, K, K] state a step is 2 MB, 17 GB over 8192
steps; kept are the states at the blocks' ends), every layer and every held
expert is under ``jax.checkpoint``, attention goes head by head
(``lax.map``, one [T, T] score matrix alive), and the cross-entropy runs in
chunks of ``CHUNK`` tokens (one [CHUNK, V] logit matrix alive).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: by the configuration's compute dtype; ``loss`` relative, ``grads``
#: ||g - g_ref|| / ||g_ref|| over all parameters together, ``leaves`` the
#: same of single leaves by their path. float32 is the CPU test's bar.
#: bfloat16 is the chip's, from readings on the v5e at the cell's sizes (one
#: 8192-token sample, published widths; my chip runs, PR 39: nineteen sound
#: seeds, eleven in one process and eight whole runs of the cell, the
#: control and the four faults on three of them;
#: ``chiprun_out/pr39/survey_a.jsonl`` while it lasts, PERF.md section 6):
#:  - the system, 19 seeds: loss 1.0e-6 .. 3.4e-5 (the logits, the softmax
#:    statistics and the loss are float32 on both sides); all gradients
#:    5.53e-2 .. 7.50e-2, and nearly every leaf reads what the whole does
#:    (the head 3.6e-2 .. 4.9e-2, the last layer's mixer up to 0.10): three
#:    times the other two language models' 2e-2, because a choice of experts
#:    is a comparison: a token whose 8th and 9th scores lie within the
#:    stream's bf16 rounding is routed otherwise by the float32 reference,
#:    its layer's output moves by a whole expert's share and every gradient
#:    upstream with it. The held experts' own leaves read most: ``r1.We_gate``
#:    0.152 .. 0.198 (``r3.We_*`` 0.22 .. 0.30, the routers ``Wr`` 0.20 ..
#:    0.49, which swing most and are held to nothing). Fourteen more whole
#:    runs of the cell after the review read 5.38e-2 .. 7.38e-2 and
#:    ``r1.We_gate`` 0.147 .. 0.200 under these limits (the harness prints a
#:    limit to one digit: 0.15 as ``1e-01``, 0.26 as ``3e-01``);
#:  - that cause, shown (PR 39, on the CPU in the configuration's bfloat16 at
#:    the published widths, 2048 rows of the vocabulary and 512 tokens, two
#:    seeds: a reading of agreement, no device's): against the reference as
#:    it is, all gradients 6.4e-2 and 7.5e-2, the experts' leaves 0.13 ..
#:    0.27, the routers 0.13 .. 0.39, and 52 to 122 of a layer's 512 tokens
#:    choose otherwise than the program did (1 to 22 of them in a held
#:    expert); against the reference handed the program's own choice, layer
#:    by layer, all gradients 3.6e-2 and 3.5e-2 and every expert's and
#:    router's leaf 0.028 .. 0.042. The comparison that decides ``correct``
#:    keeps the reference's own choice: it takes nothing from the program;
#:  - control, the reference in the program's place with every product's
#:    operands rounded to float8 (e4m3's three mantissa bits), the precision
#:    below the bf16 the configuration states
#:    (``tests/benchmark/test_benchmark_kimi_linear.py`` has it), three
#:    seeds: all gradients 0.411 .. 0.432, ``r1.We_gate`` 0.64 .. 0.67; its
#:    loss 5.6e-5 .. 1.0e-4, at the loss's limit: it is not correct by the
#:    two gradient limits;
#:  - fault, the state not carried across a chunk boundary (every chunk of
#:    the rule starts from nought), the same three seeds: all gradients 1.24
#:    .. 1.26, loss 1.8e-4 .. 7.5e-4: not correct by all three;
#:  - fault, one decay a head (the mean of its key channels'): all gradients
#:    1.29 .. 1.31, loss 3.9e-4 .. 7.5e-4: not correct by all three;
#:  - fault, the renormalisation taken over the held choices only: all
#:    gradients 0.95 .. 1.08, the held experts' leaves 3.7 .. 4.9: not
#:    correct by both gradient limits (its loss 7e-5 .. 3.2e-4);
#:  - fault, a held expert's rows dropped (the first held expert's choices
#:    weigh nought): all gradients 0.102 .. 0.198, ``r1.We_gate`` 0.36 ..
#:    0.53 (an eighth of the leaf is nought), loss as a sound run's: it is
#:    not correct by the leaf's limit alone, and by the limit on all
#:    gradients on one seed of three.
#: The limits: all gradients 0.15, 2.0 times over the system's largest and
#: 2.7 under the control's smallest (0.411); ``r1.We_gate`` 0.26, 1.3 over
#: its largest sound reading and 1.4 under the smallest of the fault that
#: only it catches (0.36), 2.5 under the control's; the loss 1e-4, the
#: harness's, 10 times over the first reading (1.0e-5) and 2.9 over the
#: largest of the nineteen. What no limit catches is in the configuration's
#: ``does_not_hold``.
TOLERANCE = {"float32": {"loss": 1e-4, "grads": 1e-4},
             "bfloat16": {"loss": 1e-4, "grads": 0.15,
                          "leaves": {"['stack']['r1.We_gate']": 0.26}}}

_HI = lax.Precision.HIGHEST
#: tokens whose logits are alive at once in the cross-entropy
CHUNK = 1024
#: steps of the recurrence between two kept states
STEPS = 64


def rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def dot(a, w):
    return jnp.dot(a, w, precision=_HI)


def gated(x, w_gate, w_up, w_down):
    return dot(jax.nn.silu(dot(x, w_gate)) * dot(x, w_up), w_down)


def causal_depthwise_conv(x, w):
    """``x`` [b, T, C], ``w`` [C, K]: channel c at step t reads its own
    steps t - K + 1 .. t (nought before the first) against ``w[c]``."""
    K = w.shape[1]
    return lax.conv_general_dilated(
        x, jnp.transpose(w)[:, None, :], window_strides=(1,),
        padding=[(K - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=x.shape[-1], precision=_HI)


def delta_rule(q, k, v, g, beta):
    """``o_t = S_t^T q_t`` of ``S_t = Diag(exp(g_t)) S_{t-1}``, then
    ``S_t += beta_t k_t (v_t - S_t^T k_t)^T``, ``S_0 = 0``, step by step:
    ``q``, ``k``, ``v``, ``g`` [b, T, H, K], ``beta`` [b, T, H] ->
    [b, T, H, K]."""
    b, T, H, K = k.shape
    steps = STEPS if T % STEPS == 0 else T

    def step(S, at_t):
        q_t, k_t, v_t, g_t, b_t = at_t
        S = jnp.exp(g_t)[..., None] * S
        seen = jnp.sum(S * k_t[..., None], axis=-2)          # S^T k
        S = S + (b_t[..., None] * k_t)[..., None] * (v_t - seen)[..., None, :]
        return S, jnp.sum(S * q_t[..., None], axis=-2)

    @jax.checkpoint
    def block(S, over_block):
        return lax.scan(step, S, over_block)

    by_step = [jnp.moveaxis(t, 1, 0).reshape((T // steps, steps)
                                             + t.shape[:1] + t.shape[2:])
               for t in (q, k, v, g, beta)]
    _, o = lax.scan(block, jnp.zeros((b, H, K, K), k.dtype), tuple(by_step))
    return jnp.moveaxis(o.reshape(T, b, H, K), 0, 1)


def kda_mixer(p, x, eps):
    b, T, _ = x.shape
    H, K = p["A_log"].shape[0], p["gn"].shape[0]
    heads = lambda t: t.reshape(b, T, H, K)
    conv = lambda w, taps: heads(jax.nn.silu(
        causal_depthwise_conv(dot(x, p[w]), p[taps])))
    unit = lambda t: t / jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True)
                                  + 1e-6)
    q = unit(conv("Wq", "conv_q")) * K ** -0.5
    k = unit(conv("Wk", "conv_k"))
    v = conv("Wv", "conv_v")
    g = -jnp.exp(p["A_log"])[:, None] * heads(jax.nn.softplus(
        dot(dot(x, p["W_fa"]), p["W_fb"]) + p["dt_bias"]))
    beta = jax.nn.sigmoid(dot(x, p["W_b"]))
    o = rms_norm(delta_rule(q, k, v, g, beta), p["gn"], eps)
    gate = jax.nn.sigmoid(dot(dot(x, p["W_ga"]), p["W_gb"]))
    return dot(o.reshape(b, T, H * K) * gate, p["Wo"])


def attention(q, k, v, scale):
    """Causal softmax attention head by head: ``q``, ``k`` [b, T, h, D],
    ``v`` [b, T, h, Dv] -> [b, T, h, Dv]."""
    T, h = q.shape[1], q.shape[2]
    causal = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def head(i):
        qh, kh, vh = (lax.dynamic_index_in_dim(t, i, axis=2, keepdims=False)
                      for t in (q, k, v))
        s = jnp.einsum("bqd,bkd->bqk", qh, kh, precision=_HI) * scale
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", p, vh, precision=_HI)

    return jnp.moveaxis(lax.map(head, jnp.arange(h)), 0, 2)


def mla_mixer(p, x, eps):
    b, T, _ = x.shape
    # the sizes off the leaves: Wkv_a makes the latent and the R shared key
    # channels, Wq h (N + R) columns, Wkv_b h (N + Dv), Wo reads h Dv
    rank = p["gc"].shape[0]
    rope = p["Wkv_a"].shape[1] - rank
    nope_all = p["Wkv_b"].shape[1] - p["Wo"].shape[0]
    heads = (p["Wq"].shape[1] - nope_all) // rope
    nope, v_dim = nope_all // heads, p["Wo"].shape[0] // heads
    q = dot(x, p["Wq"]).reshape(b, T, heads, nope + rope)
    latent = dot(x, p["Wkv_a"])
    kv = dot(rms_norm(latent[..., :rank], p["gc"], eps),
             p["Wkv_b"]).reshape(b, T, heads, nope + v_dim)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        latent[:, :, None, rank:], (b, T, heads, rope))], axis=-1)
    o = attention(q, k, kv[..., nope:], (nope + rope) ** -0.5)
    return dot(o.reshape(b, T, heads * v_dim), p["Wo"])


def experts_ffn(p, x, top_k, scaling, experts=None):
    """The shared expert and the part of the routed sum that the experts
    0 .. ``experts`` - 1 give (None: as many as the leaves hold)."""
    scores = jax.nn.sigmoid(dot(x, p["Wr"]))
    _, top = lax.top_k(scores, top_k)
    chosen = jnp.take_along_axis(scores, top, axis=-1)
    weight = scaling * chosen / (jnp.sum(chosen, axis=-1, keepdims=True)
                                 + 1e-20)
    y = gated(x, p["Ws_gate"], p["Ws_up"], p["Ws_down"])
    one = jax.checkpoint(gated)
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


def hidden_state(params, ids, top_k, scaling, eps):
    """The final-normed state [b, T, d]."""

    @jax.checkpoint
    def layer(p, h):
        n = rms_norm(h, p["g1"], eps)
        u = h + (kda_mixer(p, n, eps) if "A_log" in p
                 else mla_mixer(p, n, eps))
        n = rms_norm(u, p["g2"], eps)
        return u + (experts_ffn(p, n, top_k, scaling) if "Wr" in p
                    else gated(n, p["Wgate"], p["Wup"], p["Wdown"]))

    h = params["embed"]["W"][ids.astype(jnp.int32)]
    for p in layers_of(params["stack"]):
        h = layer(p, h)
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


def loss(params, ids, labels, experts_per_token=8,
         routed_scaling_factor=2.446, eps=1e-5):
    """``ids``, ``labels``: int [b, T]. Summed over tokens, averaged over
    the batch, as the system's ``sparse_mcxent`` reduces."""
    h = hidden_state(params, ids, experts_per_token, routed_scaling_factor,
                     eps)
    xent = next_token_xent(h, params["out"]["W"], labels.astype(jnp.int32))
    return jnp.sum(xent) / ids.shape[0]
