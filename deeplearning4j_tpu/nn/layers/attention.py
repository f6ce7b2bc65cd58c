"""Multi-head self-attention layer.

Net-new vs the 0.9.x reference (which has no attention layers — SURVEY.md §5
"Long-context: absent"), included because long-context support is first-class in
the TPU build. The layer is written so the sequence dimension can be sharded:
under ``parallel.sequence`` the same parameters run blockwise ring attention
across a mesh 'sp' axis (see ``deeplearning4j_tpu/parallel/sequence.py``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..weights import host_full
from .base import LayerImpl, implements, acc_dtype, pet_dtype
from .normalization import rms_norm


def mha(q, k, v, causal, compute_dtype, dropout_rate=0.0, rng=None, train=False,
        key_mask=None, scale=None, window=None):
    """q: [b, T, h, d], k: [b, T, h_kv, d], v: [b, T, h_kv, d_v] with
    ``h_kv`` dividing ``h`` (grouped queries: head i reads key-value head
    ``i // (h // h_kv)``; the value heads may have a size of their own).
    Returns [b, T, h, d_v]. Scaled dot-product attention (``scale`` None:
    ``1 / sqrt(d)``) with f32 softmax accumulation (bf16-safe).
    ``key_mask``: [b, S] with 1 for real keys, 0 for padding — padded keys
    are excluded from the softmax. ``window`` (causal only): query i sees
    the keys i - window < j <= i (a sliding window of ``window`` keys,
    itself among them), which the flash kernels walk as a band of blocks.

    Long sequences route through the Pallas flash-attention kernel
    (``ops/flash_attention.py``): blockwise online softmax, O(T) memory
    instead of materializing the [b, h, T, T] logits — key-padding masks
    AND train-time attention dropout included (both run in-kernel; the
    dropout mask is regenerated blockwise from a counter-hash PRNG). The
    dense path below remains the oracle and the fallback (short or
    non-block-divisible sequences) — but not for a long causal call on the
    TPU, which raises rather than form [b, h, T, T].

    Grouped heads reach the kernels repeated to ``h`` heads (the repeat's
    transpose sums a group's dk and dv): the kernels' index maps read one
    head's block per grid row.
    """
    from ...ops import flash_attention as _fa

    if window is not None and not causal:
        raise ValueError("mha: a window bands causal attention only")
    T, d = q.shape[1], q.shape[-1]
    rate = dropout_rate if (train and rng is not None) else 0.0
    k, v = _repeat_kv(k, v, q.shape[2])
    if q.shape == k.shape and _fa.supported(T, d, rate, key_mask,
                                            v.shape[-1]):
        seed = None
        if rate > 0.0:
            # per-step scalar seed for the in-kernel counter-hash dropout
            # PRNG (derived from the layer rng, so each train step draws a
            # fresh mask exactly like the dense path's jax.random.bernoulli)
            seed = jax.random.randint(rng, (), 0, jnp.iinfo(jnp.int32).max,
                                      dtype=jnp.int32)
        return _fa.flash_attention(
            q.astype(compute_dtype), k.astype(compute_dtype),
            v.astype(compute_dtype), causal=causal, scale=scale,
            key_mask=key_mask, dropout_rate=rate, dropout_seed=seed,
            window=window)
    if causal and T >= _fa.MIN_SEQ and _fa._on_tpu():
        raise ValueError(
            f"mha: a causal call of {T} tokens (head size {d}, keys "
            f"{k.shape[1]}) does not fit the flash kernels (lengths equal and "
            f"multiples of {_fa.MIN_BLOCK}, head sizes <= 256, a [b, T] key "
            f"mask), and the dense path would form the "
            f"[{q.shape[0]}, {q.shape[2]}, {T}, {k.shape[1]}] scores")
    visible = None
    if causal:
        T, S = q.shape[1], k.shape[1]
        visible = jnp.tril(jnp.ones((T, S), bool))[None, None]
        if window is not None:
            visible &= ~jnp.tril(jnp.ones((T, S), bool), -window)[None, None]
    if key_mask is not None:
        km = (key_mask[:, None, None, :] > 0)
        visible = km if visible is None else (visible & km)
    return _dense_attention(q, k, v, visible, compute_dtype,
                            dropout_rate=dropout_rate, rng=rng, train=train,
                            scale=scale)


def _repeat_kv(k, v, heads):
    """``k``, ``v`` [b, S, h_kv, d] with every head repeated so that query
    head i of ``heads`` finds its key-value head at i; as they are where
    ``h_kv`` is ``heads``."""
    group = heads // k.shape[2]
    if group == 1:
        return k, v
    return jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)


def _dense_attention(q, k, v, visible, compute_dtype, dropout_rate=0.0,
                     rng=None, train=False, scale=None):
    """Shared dense scaled-dot-product body (full-sequence AND KV-cache
    streaming paths — one implementation so masking/dropout/numerics cannot
    diverge). ``visible``: broadcastable-to-[b, h, Tq, Tk] bool mask or
    None. ``k``, ``v`` may hold fewer (grouped) heads than ``q``."""
    d = q.shape[-1]
    k, v = _repeat_kv(k, v, q.shape[2])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(compute_dtype),
                        k.astype(compute_dtype),
                        preferred_element_type=pet_dtype(compute_dtype))
    if scale is None:
        logits = logits / jnp.sqrt(jnp.asarray(d, jnp.float32))
    else:
        logits = logits * jnp.asarray(scale, jnp.float32)
    if visible is not None:
        logits = jnp.where(visible, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    if visible is not None:
        # a query row with NO visible key outputs 0 (softmax over all -1e30
        # would silently average every value vector) — same convention as
        # the flash kernels, so the oracle and kernel cannot diverge on
        # fully-padded rows
        probs = jnp.where(jnp.any(visible, axis=-1, keepdims=True),
                          probs, 0.0)
    if train and dropout_rate > 0.0 and rng is not None:
        keep = jax.random.bernoulli(rng, 1.0 - dropout_rate, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_rate), 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(compute_dtype),
                      v.astype(compute_dtype),
                      preferred_element_type=pet_dtype(compute_dtype))


def yarn(theta, half, factor, original_max_position_embeddings, beta_fast=32,
         beta_slow=1, attention_factor=None, **_):
    """(inv_freq [half], attention factor) of YaRN's rotary scaling (Peng et
    al. 2023; ``_compute_yarn_parameters`` of transformers), on the host in
    float64: channel i keeps its frequency ``theta ** (-i / half)`` below
    the channel whose wavelength fits ``beta_fast`` times into the original
    context, is divided by ``factor`` above the one where ``beta_slow``
    does, and is blended linearly between them; the cosine and sine are
    multiplied by ``attention_factor`` (None: ``0.1 ln(factor) + 1``)."""
    dim, ctx = 2 * half, float(original_max_position_embeddings)
    edge = lambda turns: (dim * math.log(ctx / (turns * 2 * math.pi))
                          / (2 * math.log(theta)))
    low = max(math.floor(edge(beta_fast)), 0)
    high = min(math.ceil(edge(beta_slow)), dim - 1)
    if high == low:
        high += 0.001
    ramp = np.clip((np.arange(half) - low) / (high - low), 0, 1)
    extrapolated = float(theta) ** (-np.arange(half) / half)
    inv_freq = extrapolated / factor * ramp + extrapolated * (1 - ramp)
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return inv_freq, float(attention_factor)


def rope(x, theta, start=0, scaling=None):
    """Rotary position embedding of ``x`` [b, T, h, d] at positions
    ``start`` .. ``start + T - 1``: the pair (i, i + d/2) of every head is
    rotated by ``position * theta ** (-2 i / d)`` (the rotate-half pairing).
    ``scaling``: a published ``rope_parameters`` block whose ``rope_type``
    is ``"yarn"`` (:func:`yarn`'s frequencies, the rotation scaled by its
    attention factor), or None. Angles and rotation in float32; returned in
    ``x``'s dtype."""
    T, half = x.shape[1], x.shape[-1] // 2
    if scaling is None:
        inv_freq = 1.0 / (float(theta) ** (jnp.arange(half, dtype=jnp.float32)
                                           / half))
    else:
        if scaling.get("rope_type") != "yarn":
            raise ValueError(f"rope: scaling {scaling.get('rope_type')!r} "
                             f"is not supported (yarn is)")
        inv_freq, factor = yarn(float(theta), half, **scaling)
        inv_freq = jnp.asarray(inv_freq, jnp.float32)
    angle = (start + jnp.arange(T, dtype=jnp.float32))[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    if scaling is not None:
        cos, sin = cos * factor, sin * factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


@implements("SelfAttentionLayer")
class SelfAttentionImpl(LayerImpl):
    def _dims(self):
        c = self.conf
        h = c.num_heads
        if c.kv_latent_rank is not None:
            return h, int(c.qk_nope_head_dim + c.qk_rope_head_dim)
        d = c.head_dim or (c.n_out // h)
        return h, d

    def _kv_heads(self):
        h = self.conf.num_heads
        kv = self.conf.num_kv_heads or h
        if h % kv:
            raise ValueError(f"SelfAttentionLayer: num_kv_heads={kv} does not "
                             f"divide num_heads={h}")
        return kv

    def _latent(self):
        """(latent rank, the keys' part made from the latent, the keys' part
        all heads share, the value head size) of the latent layout, or None:
        see the config class."""
        c = self.conf
        if c.kv_latent_rank is None:
            return None
        if c.num_kv_heads not in (None, c.num_heads) or c.head_dim not in (
                None, c.qk_nope_head_dim + c.qk_rope_head_dim):
            raise ValueError(
                "SelfAttentionLayer: the latent layout makes num_heads key "
                "and value heads from one latent; its head size is "
                "qk_nope_head_dim + qk_rope_head_dim")
        return (int(c.kv_latent_rank), int(c.qk_nope_head_dim),
                int(c.qk_rope_head_dim), int(c.v_head_dim))

    def _init_latent(self, rng):
        c = self.conf
        h = c.num_heads
        rank, nope, shared, d_v = self._latent()
        w = lambda key, n_in, n_out: self._init_w(key, (n_in, n_out), n_in,
                                                  n_out)
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        return {"Wq": w(k1, c.n_in, h * (nope + shared)),
                "Wkv_a": w(k2, c.n_in, rank + shared),
                "gc": host_full((rank,), 1, self.dtype),
                "Wkv_b": w(k3, rank, h * (nope + d_v)),
                "Wo": w(k4, h * d_v, c.n_out)}

    def init(self, rng):
        c = self.conf
        if self._latent():
            params = self._init_latent(rng)
        else:
            h, d = self._dims()
            kv = self._kv_heads()
            k1, k2, k3, k4 = jax.random.split(rng, 4)
            params = {
                "Wq": self._init_w(k1, (c.n_in, h * d), c.n_in, h * d),
                "Wk": self._init_w(k2, (c.n_in, kv * d), c.n_in, kv * d),
                "Wv": self._init_w(k3, (c.n_in, kv * d), c.n_in, kv * d),
                "Wo": self._init_w(k4, (h * d, c.n_out), h * d, c.n_out),
            }
        if c.has_bias:
            params["b"] = self._init_b((c.n_out,))
        return params, {}

    def _qkv(self, params, x):
        """q [b, T, h, d], k [b, T, h_kv, d], v [b, T, h_kv, d_v] of ``x``
        [b, T, n_in]. The latent layout: keys and values come through one
        normed latent, and every head's key ends in the channels that
        ``Wkv_a`` makes beside the latent, one set for all heads."""
        c = self.conf
        b, T, _ = x.shape
        h = c.num_heads
        proj = lambda t, w: t @ params[w].astype(t.dtype)
        if not self._latent():
            d, kv = self._dims()[1], self._kv_heads()
            return (proj(x, "Wq").reshape(b, T, h, d),
                    proj(x, "Wk").reshape(b, T, kv, d),
                    proj(x, "Wv").reshape(b, T, kv, d))
        rank, nope, shared, d_v = self._latent()
        q = proj(x, "Wq").reshape(b, T, h, nope + shared)
        latent = proj(x, "Wkv_a")
        normed = rms_norm(latent[..., :rank], params["gc"],
                          c.latent_norm_eps, acc_dtype(self.compute_dtype))
        kv = proj(normed.astype(x.dtype), "Wkv_b").reshape(b, T, h, nope + d_v)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(
                latent[:, :, None, rank:], (b, T, h, shared))], axis=-1)
        return q, k, kv[..., nope:]

    #: training forward is scan-free — the stream state must not disable
    #: the conv-net remat policy the way true RNN carries do (base.py)
    scan_free_training = True

    def init_stream_state(self, batch):
        """KV cache for streaming inference / cross-segment TBPTT: circular
        buffer of ``stream_max_length`` capacity (static shapes keep one
        compiled step), PER-EXAMPLE per-slot global positions (-1 =
        empty/masked — per-example so non-uniform key padding across the
        batch stays exact), and the global token counter."""
        c = self.conf
        h, d = self._kv_heads(), self._dims()[1]
        d_v = self._latent()[3] if self._latent() else d
        L = int(c.stream_max_length)
        cd = self.compute_dtype
        return (jnp.zeros((batch, L, h, d), cd),
                jnp.zeros((batch, L, h, d_v), cd),
                jnp.full((batch, L), -1, jnp.int32),
                jnp.zeros((), jnp.int32))

    def _cached_attention(self, q, k, v, carry, cd, key_mask, dropout_rate,
                          rng, train):
        """Streaming attention against the circular KV cache (a SLIDING
        WINDOW — past capacity the OLDEST entries are evicted).

        Attention is computed BEFORE this chunk's writes land, over the
        concatenation [retained cache keys | this chunk's keys], so a
        multi-token chunk that rolls the buffer past capacity cannot evict
        keys still inside the window of the chunk's EARLIER queries: each
        causal query at global position p sees exactly the keys at positions
        in (p - L, p], byte-identical to feeding the chunk one token at a
        time. Key-mask-padded tokens advance time but are never visible,
        tracked per example. One shared dense body with ``mha`` —
        masking/dropout semantics cannot diverge."""
        k_c, v_c, pos_c, n = carry
        b, T, h, d = q.shape
        L = k_c.shape[1]
        if T > L:
            raise ValueError(
                f"SelfAttentionLayer stream chunk of {T} tokens exceeds "
                f"stream_max_length={L}; raise stream_max_length on the "
                f"layer config (it must cover the TBPTT segment length)")
        chunk_pos = jnp.broadcast_to(n + jnp.arange(T), (b, T))      # [b, T]
        if key_mask is not None:
            chunk_pos = jnp.where(key_mask > 0, chunk_pos, -1)
        # attend over [cache | chunk] with position-based visibility
        k_all = jnp.concatenate([k_c, k.astype(k_c.dtype)], axis=1)
        v_all = jnp.concatenate([v_c, v.astype(v_c.dtype)], axis=1)
        pos_all = jnp.concatenate([pos_c, chunk_pos], axis=1)        # [b, L+T]
        qpos = n + jnp.arange(T)                        # [T] global positions
        valid = pos_all[:, None, :] >= 0                # [b, Tq, L+T]
        if self.conf.causal:
            # window (p - L, p]: eviction emulated per query, not per chunk
            visible = (valid
                       & (pos_all[:, None, :] <= qpos[None, :, None])
                       & (pos_all[:, None, :] > qpos[None, :, None]
                          - min(L, self.conf.window or L)))
        else:
            # non-causal streaming: every key retained after this chunk's
            # writes (positions > n + T - 1 - L), matching write-then-attend
            visible = valid & (pos_all[:, None, :] > n + T - 1 - L)
        o = _dense_attention(q, k_all, v_all, visible[:, None], cd,
                             dropout_rate=dropout_rate, rng=rng, train=train,
                             scale=self.conf.attention_scale)
        # now land the chunk's writes (evicting the oldest slots)
        slots = (n + jnp.arange(T)) % L
        k_c = k_c.at[:, slots].set(k.astype(k_c.dtype))
        v_c = v_c.at[:, slots].set(v.astype(v_c.dtype))
        pos_c = pos_c.at[:, slots].set(chunk_pos)
        return o, (k_c, v_c, pos_c, n + T)

    def forward(self, params, state, x, train=False, rng=None, mask=None, ctx=None):
        c = self.conf
        h = c.num_heads
        b, T, _ = x.shape
        x = self.maybe_dropout(x, train, rng)
        cd = self.compute_dtype
        kv = self._kv_heads()
        q, k, v = self._qkv(params, x)
        idx = getattr(self, "index", None)
        carry = (ctx.get("rnn_state_in", {}).get(idx)
                 if ctx is not None and idx is not None else None)
        from ...parallel.sequence import current_sp_axis
        sp_axis = current_sp_axis()
        if c.rope_theta is not None:
            if sp_axis is not None:
                raise ValueError(
                    "SelfAttentionLayer: rope_theta under a sequence-parallel "
                    "step is not supported (a shard does not know its "
                    "global positions)")
            start = 0 if carry is None else carry[3]
            q, k = (rope(t, c.rope_theta, start, c.rope_scaling)
                    for t in (q, k))
        if carry is not None:
            o, new_carry = self._cached_attention(
                q, k, v, carry, cd, key_mask=mask,
                dropout_rate=c.dropout_rate, rng=rng, train=train)
            ctx.setdefault("rnn_state_out", {})[idx] = new_carry
        elif sp_axis is not None:
            # sequence-parallel step (parallel/sequence.py::
            # sequence_parallel_step): this forward runs PER DEVICE inside
            # shard_map with the time dim sharded over ``sp_axis`` — attend
            # via the ring (flash kernel per block when shapes allow).
            # Attention dropout runs IN the ring kernels at global
            # coordinates: rng is replicated across shards, so every shard
            # derives the same seed — the same derivation as mha's flash
            # path, giving each train step a fresh mask
            from ...parallel.sequence import sp_attend

            if kv != h or c.attention_scale is not None or c.window:
                raise ValueError(
                    "SelfAttentionLayer: num_kv_heads, attention_scale and "
                    "window under a sequence-parallel step are not supported "
                    "(the ring takes equal heads, 1/sqrt(head_dim) and the "
                    "whole causal triangle)")
            rate = c.dropout_rate if (train and rng is not None) else 0.0
            seed = None
            if rate > 0.0:
                seed = jax.random.randint(rng, (), 0,
                                          jnp.iinfo(jnp.int32).max,
                                          dtype=jnp.int32)
            o = sp_attend(q.astype(cd), k.astype(cd), v.astype(cd),
                          sp_axis, bool(c.causal), dropout_rate=rate,
                          dropout_seed=seed)
        else:
            o = mha(q, k, v, c.causal, cd, c.dropout_rate, rng, train,
                    key_mask=mask, scale=c.attention_scale, window=c.window)
        o = o.reshape(b, T, h * v.shape[-1])
        y = o @ params["Wo"].astype(o.dtype)
        if "b" in params:
            y = y + params["b"].astype(o.dtype)
        return self.activation(y).astype(self.out_dtype), state
