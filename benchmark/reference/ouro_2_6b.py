"""Plain reference of ``ouro_2_6b`` (ByteDance Ouro, a looped language model:
"Scaling Latent Reasoning via Looped Language Models", 2025; ``config.json``
of ``ByteDance/Ouro-2.6B``), written from the equations in float32
``jax.numpy``:

    RMSNorm_g(x) = x / sqrt(mean(x^2, last axis) + eps) * g
    Block(x):  n = RMSNorm_g1(x);  q, k, v = n Wq, n Wk, n Wv  -> [b, T, H, D]
               q, k <- RoPE(q), RoPE(k): positions 0..T-1, the whole head,
                       rotate-half pairing (i, i + D/2)
               o = softmax(q k^T / sqrt(D), causal) v
               a = x + RMSNorm_g2(o Wo)
               m = RMSNorm_g3(a);  f = (silu(m Wgate) * (m Wup)) Wdown
               Block(x) = a + RMSNorm_g4(f)
    h^0 = E[ids];  for t = 1..R:  u = h^{t-1};  for l = 1..L: u <- Block_l(u)
                                  h^t = RMSNorm_gf(u)      same weights every t
    per token:  z^t = h^t W_head;  l^t = -log softmax(z^t)[label]
                lambda_t = sigmoid(h^t . w_gate + b_gate)
                p_1 = lambda_1,  p_t = lambda_t prod_{j<t}(1 - lambda_j),
                p_R = prod_{j<R}(1 - lambda_j)
    loss = sum over tokens [ sum_t p_t l^t - beta H(p) ] / batch,
           H(p) = -sum_t p_t log p_t

It is handed the network's own parameters and knows their names and layout:
``embed.W`` [V, d]; ``stack`` holds the blocks' weights stacked leaf by leaf
[L, ...] (``Wq``, ``Wk``, ``Wv``, ``Wo`` [L, d, d]; ``Wgate``, ``Wup``
[L, d, F]; ``Wdown`` [L, F, d]; the gains ``g1``..``g4`` [L, d]) and the
final norm's ``gf`` [d]; ``out`` holds ``W`` [d, V], ``gate_W`` [d] and
``gate_b`` [1]. What the parameters' shapes do not say (heads, passes, theta,
eps, beta) defaults to the published or assumed value of
``configs/ouro_2_6b.json``.

Departures from the literal text, all so that one 4096-token sample's loss
AND gradients fit beside two live networks and the system's gradients on one
chip (13.0 GB are taken before this function runs), none changing a number:
the passes run as ``lax.scan`` (so each weight's gradient accumulates in one
carry; as a Python loop the four passes' gradient trees were alive at once,
4 x 1.2 GB) around a Python loop over the blocks, each block under
``jax.checkpoint``; attention goes head by head (``lax.map``, one [T, T]
score matrix alive); the head's cross-entropy runs after the passes over all
their states in chunks of ``CHUNK`` tokens (one [CHUNK, V] logit matrix
alive, one accumulator for the head's gradient), both under
``jax.checkpoint``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.scipy.special import xlogy

#: by the configuration's compute dtype; ``loss`` relative, ``grads``
#: ||g - g_ref|| / ||g_ref|| over all parameters together, ``leaves`` the
#: same of single leaves by their path. float32 is the CPU test's bar.
#: bfloat16 is the chip's, from readings on the v5e at the cell's sizes (one
#: 4096-token sample, published widths; PERF.md section 6 has them):
#:  - the system, about eighty seeds (36 in PR 28, 13 in PR 31, 7 and a
#:    survey of 16 in PR 32): loss 2.7e-7 .. 2.1e-5 (the logits, the softmax
#:    statistics and the loss are float32 on both sides; what differs is the
#:    bf16 operands of the gemms below them); gradients 9.3e-3 .. 2.77e-2
#:    and one seed (3200111) at 4.96e-2, on the parent's tree too: it failed
#:    PR 28's limit of 0.045. The ratio's denominator swings: the exit
#:    gate's share of the gradient is a difference of nearly equal per-pass
#:    losses, and over 16 seeds the reference's norm of ``gate_W``'s
#:    gradient reads 3.9e2 .. 2.0e3, of the embedding's 5.6e3 .. 1.6e4,
#:    the distance largest on the seed where the gate's is smallest. The
#:    head's own leaf is steady: its norm 2.25e3 .. 2.58e3, its distance
#:    1.05e-2 .. 1.51e-2;
#:  - control, the reference in the program's place with every product's
#:    operands rounded to float8 (e4m3's three mantissa bits), the precision
#:    below the bf16 the configuration states
#:    (``tests/benchmark/test_benchmark_ouro.py`` has it), four seeds, the
#:    system's quietest and loudest among them: gradients 1.36e-1, 1.73e-1,
#:    2.95e-1, 3.07e-1; the head's leaf 1.60e-1 .. 1.97e-1;
#:  - fault, half of the tokens left out of the system's loss (the sum over
#:    the rest, and twice it, the mean), the same four seeds: gradients
#:    5.6e-1 .. 7.8e-1 and 5.4e-1 .. 1.13; the head's leaf 6.9e-1 .. 1.0;
#:  - NOT held: the head's logits and softmax statistics in bf16 where the
#:    configuration says float32 (PR 28's control, which set 0.045 from two
#:    seeds at 9.9e-2 and 1.15e-1): over 16 seeds it reads 1.44e-2 ..
#:    1.19e-1 in all gradients, under the system's largest on 10 of them,
#:    and 1.06e-2 .. 1.51e-2 in the head's leaf, the system's own range (the
#:    head's gemms already take bf16 operands); its loss 3.3e-6 .. 2.0e-4.
#:    Nor the residual stream rounded to bf16 after every block (two seeds,
#:    PR 28: gradients 2.05e-2 and 1.08e-2, loss 1.2e-6 and 1.7e-6).
#: The limits: all gradients 0.09, 1.8 times over the system's largest and
#: 1.5 under the fp8 control's smallest (6 under the fault's); the head's
#: leaf 0.05, 3.3 over its largest and 3.2 under the control's smallest;
#: the loss five times over the system's largest.
TOLERANCE = {"float32": {"loss": 1e-4, "grads": 1e-4},
             "bfloat16": {"loss": 1e-4, "grads": 0.09,
                          "leaves": {"['out']['W']": 0.05}}}

_HI = lax.Precision.HIGHEST
#: tokens whose logits are alive at once in the head's cross-entropy
CHUNK = 1024


def rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, theta):
    """``x``: [b, T, H, D]; position t rotates the pair (i, i + D/2) by
    t * theta^(-2i/D)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """Causal softmax attention, ``q``, ``k``, ``v``: [b, T, H, D]."""
    T, D = q.shape[1], q.shape[-1]
    causal = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def head(qkv):                       # each [b, T, D]
        qh, kh, vh = qkv
        s = jnp.einsum("bqd,bkd->bqk", qh, kh, precision=_HI) / jnp.sqrt(
            jnp.float32(D))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", p, vh, precision=_HI)

    by_head = [jnp.moveaxis(x, 2, 0) for x in (q, k, v)]
    return jnp.moveaxis(lax.map(head, tuple(by_head)), 0, 2)


def block(p, x, heads, theta, eps):
    b, T, d = x.shape
    dot = lambda a, w: jnp.dot(a, w, precision=_HI)
    n = rms_norm(x, p["g1"], eps)
    q, k, v = (dot(n, p[w]).reshape(b, T, heads, -1)
               for w in ("Wq", "Wk", "Wv"))
    o = attention(rope(q, theta), rope(k, theta), v).reshape(b, T, -1)
    a = x + rms_norm(dot(o, p["Wo"]), p["g2"], eps)
    m = rms_norm(a, p["g3"], eps)
    f = dot(jax.nn.silu(dot(m, p["Wgate"])) * dot(m, p["Wup"]), p["Wdown"])
    return a + rms_norm(f, p["g4"], eps)


def next_token_xent(states, w_head, labels):
    """-log softmax(h W_head)[label] per pass and token: ``states``
    [R, b, T, d], ``labels`` [b, T] -> [R, b, T]."""
    R, b, T, d = states.shape
    n = R * b * T
    chunk = CHUNK if n % CHUNK == 0 else n

    @jax.checkpoint
    def one(args):
        hc, lc = args
        logp = jax.nn.log_softmax(jnp.dot(hc, w_head, precision=_HI), axis=-1)
        return -jnp.take_along_axis(logp, lc[:, None], axis=-1)[:, 0]

    every = jnp.broadcast_to(labels, (R, b, T))
    out = lax.map(one, (states.reshape(n // chunk, chunk, d),
                        every.reshape(n // chunk, chunk)))
    return out.reshape(R, b, T)


def exit_distribution(lam):
    """``lam``: the R gates [R, ...] -> p [R, ...], summing to 1 over R."""
    p, stay = [], jnp.ones_like(lam[0])
    for t in range(lam.shape[0] - 1):
        p.append(lam[t] * stay)
        stay = stay * (1.0 - lam[t])
    return jnp.stack(p + [stay])


def hidden_states(params, ids, heads=16, passes=4, theta=1e6, eps=1e-6):
    """h^1 .. h^R stacked [R, b, T, d]."""
    stack = params["stack"]
    blocks = [{k: v[l] for k, v in stack.items() if k != "gf"}
              for l in range(stack["Wq"].shape[0])]
    one = jax.checkpoint(lambda p, u: block(p, u, heads, theta, eps))

    def one_pass(h, _):
        u = h
        for p in blocks:
            u = one(p, u)
        h = rms_norm(u, stack["gf"], eps)
        return h, h

    h0 = params["embed"]["W"][ids.astype(jnp.int32)]
    return lax.scan(one_pass, h0, None, length=passes)[1]


def loss(params, ids, labels, heads=16, passes=4, theta=1e6, eps=1e-6,
         beta=0.05):
    """``ids``, ``labels``: int [b, T]. Summed over tokens, averaged over
    the batch, as the system's ``sparse_mcxent`` reduces."""
    out = params["out"]
    states = hidden_states(params, ids, heads, passes, theta, eps)
    xent = next_token_xent(states, out["W"], labels.astype(jnp.int32))
    lam = jax.nn.sigmoid(jnp.dot(states, out["gate_W"], precision=_HI)
                         + out["gate_b"][0])
    p = exit_distribution(lam)
    entropy = -jnp.sum(xlogy(p, p), axis=0)
    return jnp.sum(jnp.sum(p * xent, axis=0) - beta * entropy) / ids.shape[0]
