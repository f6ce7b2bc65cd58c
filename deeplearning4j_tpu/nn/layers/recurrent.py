"""Recurrent layer implementations: LSTM, GravesLSTM, GravesBidirectionalLSTM,
SimpleRnn, Bidirectional and LastTimeStep wrappers.

TPU-native equivalents of reference ``nn/layers/recurrent/`` — the shared
forward/backward math in ``LSTMHelpers.java:68`` (activateHelper) and the ifog
block gemm (:206-212) become a ``lax.scan`` whose *input projection is hoisted*
out of the loop: one big [b·T, nIn]×[nIn, 4H] gemm feeds the MXU, and the scan
body only does the [b, H]×[H, 4H] recurrent gemm plus elementwise gate math.
Backward-through-time is AD of the scan (no hand-written BPTT).

Sequence layout is [batch, time, features] (reference: [b, features, T]).
Where the persistent Pallas kernel applies (``ops/lstm_cell.supported``) the
LSTM layers hand it their streams TIME-major and in the gemms' own dtype:
the narrow input [b, T, nIn] is swapped once, in the compute dtype, and
projected to ``xw`` [T, b, 4H] (no upcast, no bias: the kernel adds the f32
bias and sums its gradient), and ``ys`` [T, b, H] comes back in the layer's
activation dtype and is swapped once. Which streams follow the compute
dtype and which the ``DL4J_TPU_LSTM_STREAM_DTYPE`` knob (the reserve only)
is in ``ops/lstm_cell.py``'s docstring. The scan path below is unchanged:
batch-major projection, f32 carries, bias added in XLA.
Gate order in the fused 4H dimension is i, f, o, g matching the reference's
IFOG convention (``LSTMParamInitializer``). Param keys: "W" (input weights
[nIn, 4H]), "RW" (recurrent [H, 4H]), "b" ([4H]); Graves peepholes "pi","pf","po".

Streaming state (``rnnTimeStep``) flows through ``ctx``: the network places
per-layer previous (h, c) under ``ctx['rnn_state_in'][layer_index]`` and collects
``ctx['rnn_state_out']`` — the functional replacement for the reference's mutable
``stateMap`` (``BaseRecurrentLayer.java``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .base import LayerImpl, implements, impl_for, acc_dtype
from ..weights import host_full
from ..activations import get_activation


def _match_vma(z, ref):
    """Give a fresh scan-carry init the shard_map varying-axes type of ``ref``.

    Under ``shard_map`` (ParallelWrapper local-SGD), batch inputs are
    device-varying while a ``jnp.zeros`` carry init is not; ``lax.scan``
    rejects the carry-type mismatch. Outside shard_map this is a no-op."""
    want = set(jax.typeof(ref).vma) - set(jax.typeof(z).vma)
    if not want:
        return z
    return jax.lax.pcast(z, tuple(want), to="varying")


class _BaseLSTMImpl(LayerImpl):
    peepholes = False

    def init_stream_state(self, batch):
        """Zero (h, c) carry for rnnTimeStep / TBPTT streaming."""
        H = self.conf.n_out
        ad = acc_dtype(self.compute_dtype)
        return (jnp.zeros((batch, H), ad),
                jnp.zeros((batch, H), ad))

    def init(self, rng):
        c = self.conf
        H = c.n_out
        k1, k2, k3 = jax.random.split(rng, 3)
        params = {
            "W": self._init_w(k1, (c.n_in, 4 * H), c.n_in, H),
            "RW": self._init_w(k2, (H, 4 * H), H, H),
            "b": self._init_b((4 * H,)),
        }
        # forget-gate bias init (reference LSTMParamInitializer sets f-gate
        # slice of the bias to forgetGateBiasInit)
        fb = getattr(c, "forget_gate_bias_init", 1.0)
        params["b"] = params["b"].at[H:2 * H].set(fb)
        if self.peepholes:
            params["pi"] = host_full((H,), 0, self.dtype)
            params["pf"] = host_full((H,), 0, self.dtype)
            params["po"] = host_full((H,), 0, self.dtype)
        return params, {}

    def _run(self, params, x, mask, h0c0, reverse=False):
        c = self.conf
        H = c.n_out
        act = self.activation
        gate_act = get_activation(getattr(c, "gate_activation", "sigmoid"))
        b, T, _ = x.shape
        # the step mask is data, not a differentiable input: stop_gradient
        # here so the scan path's AD agrees with the persistent kernel's
        # custom_vjp (which returns a zero mask cotangent) — no silent
        # kernel-vs-fallback gradient divergence for soft masks
        mask = None if mask is None else lax.stop_gradient(mask)
        if reverse:
            x = jnp.flip(x, axis=1)
            mask = jnp.flip(mask, axis=1) if mask is not None else None
        ad = acc_dtype(self.compute_dtype)
        if h0c0 is None:
            h0 = jnp.zeros((b, H), ad)
            c0 = jnp.zeros((b, H), ad)
        else:
            h0, c0 = h0c0
        peep = ((params["pi"], params["pf"], params["po"])
                if self.peepholes else None)
        # recurrent weights ride in COMPUTE dtype (bf16 policy): the
        # per-step gemm is a native MXU bf16 pass accumulated in f32 (pet
        # below); h/c and the gate math stay in the accumulation dtype
        rw = params["RW"].astype(self.compute_dtype)

        # persistent-kernel fast path: the whole time loop as ONE Pallas
        # grid with RW resident in VMEM (ops/lstm_cell.py) — kills the
        # per-step HBM weight stream that bounds the scan path. The scan
        # below remains the oracle/fallback (odd dims, other activations).
        from ...ops import lstm_cell as _lk

        gate_name = getattr(c, "gate_activation", "sigmoid")
        if _lk.supported(b, T, H, self.activation_name, str(gate_name),
                         weight_bytes=jnp.dtype(rw.dtype).itemsize):
            # the kernels are time-major, so swap the NARROW input ([b, T,
            # nIn], compute dtype) and project it time-major: no [·, ·, 4H]
            # tensor is transposed, upcast or biased in XLA, and under AD
            # dW = x_tmᵀ·dz and dx = dz·Wᵀ come out time-major too. Where b
            # fills whole tiles (a multiple of 16 in bf16) both reshapes
            # are views. Between stacked LSTM layers this swap and the
            # previous layer's swap-back are an inverse pair that XLA
            # cancels. The bias goes to the kernel, which adds it in f32.
            cd = self.compute_dtype
            x_tm = jnp.swapaxes(x.astype(cd), 0, 1)
            xw = (x_tm.reshape(T * b, -1)
                  @ params["W"].astype(cd)).reshape(T, b, 4 * H)
            h0, c0 = _match_vma(h0, xw), _match_vma(c0, xw)
            ys, (hT, cT) = _lk.lstm_scan(
                xw, params["b"], rw, peep, h0, c0,
                None if mask is None else jnp.swapaxes(mask, 0, 1),
                out_dtype=self.out_dtype)
            y = jnp.swapaxes(ys, 0, 1)
            if reverse:
                y = jnp.flip(y, axis=1)
            return y, (hT, cT)

        # hoisted input projection: [b*T, nIn] @ [nIn, 4H] on the MXU
        xp = (x.reshape(b * T, -1).astype(self.compute_dtype)
              @ params["W"].astype(self.compute_dtype)).astype(ad)
        xp = xp.reshape(b, T, 4 * H) + params["b"].astype(ad)
        h0, c0 = _match_vma(h0, xp), _match_vma(c0, xp)

        def step(carry, inp):
            h, cc = carry
            xp_t, m_t = inp
            z = xp_t + lax.dot_general(
                h.astype(rw.dtype), rw, (((1,), (0,)), ((), ())),
                preferred_element_type=xp_t.dtype)
            zi, zf, zo, zg = jnp.split(z, 4, axis=-1)
            if peep is not None:
                zi = zi + cc * peep[0]
                zf = zf + cc * peep[1]
            i = gate_act(zi)
            f = gate_act(zf)
            g = act(zg)
            c_new = f * cc + i * g
            zo2 = zo + c_new * peep[2] if peep is not None else zo
            o = gate_act(zo2)
            h_new = o * act(c_new)
            if m_t is not None:
                mm = m_t[:, None].astype(h_new.dtype)
                h_new = mm * h_new + (1 - mm) * h
                c_new = mm * c_new + (1 - mm) * cc
            return (h_new, c_new), h_new

        xs = jnp.swapaxes(xp, 0, 1)
        if mask is not None:
            ms = jnp.swapaxes(mask, 0, 1)
            (hT, cT), ys = lax.scan(step, (h0, c0), (xs, ms))
        else:
            (hT, cT), ys = lax.scan(lambda cr, xt: step(cr, (xt, None)), (h0, c0), xs)
        y = jnp.swapaxes(ys, 0, 1)
        if reverse:
            y = jnp.flip(y, axis=1)
        return y.astype(self.out_dtype), (hT, cT)

    def forward(self, params, state, x, train=False, rng=None, mask=None, ctx=None):
        x = self.maybe_dropout(x, train, rng)
        h0c0 = None
        idx = getattr(self, "index", None)
        if ctx is not None and idx is not None:
            h0c0 = ctx.get("rnn_state_in", {}).get(idx)
        y, hc = self._run(params, x, mask, h0c0)
        if ctx is not None and idx is not None:
            ctx.setdefault("rnn_state_out", {})[idx] = hc
        return y, state


@implements("LSTM")
class LSTMImpl(_BaseLSTMImpl):
    peepholes = False


@implements("GravesLSTM")
class GravesLSTMImpl(_BaseLSTMImpl):
    peepholes = True


@implements("GravesBidirectionalLSTM")
class GravesBidirectionalLSTMImpl(_BaseLSTMImpl):
    """Two param sets (suffix F/B, reference ``GravesBidirectionalLSTMParamInitializer``);
    direction outputs are summed (output stays [b, T, nOut])."""
    peepholes = True

    def init(self, rng):
        kf, kb = jax.random.split(rng)
        pf, _ = super().init(kf)
        pb, _ = super().init(kb)
        params = {k + "F": v for k, v in pf.items()}
        params.update({k + "B": v for k, v in pb.items()})
        return params, {}

    def forward(self, params, state, x, train=False, rng=None, mask=None, ctx=None):
        x = self.maybe_dropout(x, train, rng)
        pf = {k[:-1]: v for k, v in params.items() if k.endswith("F")}
        pb = {k[:-1]: v for k, v in params.items() if k.endswith("B")}
        yf, _ = self._run(pf, x, mask, None)
        yb, _ = self._run(pb, x, mask, None, reverse=True)
        return yf + yb, state


@implements("SimpleRnn")
class SimpleRnnImpl(LayerImpl):
    """h_t = act(x_t W + h_{t-1} RW + b) (post-0.9 reference ``SimpleRnn``)."""

    def init(self, rng):
        c = self.conf
        k1, k2 = jax.random.split(rng)
        params = {
            "W": self._init_w(k1, (c.n_in, c.n_out), c.n_in, c.n_out),
            "RW": self._init_w(k2, (c.n_out, c.n_out), c.n_out, c.n_out),
            "b": self._init_b((c.n_out,)),
        }
        return params, {}

    def init_stream_state(self, batch):
        return jnp.zeros((batch, self.conf.n_out),
                         acc_dtype(self.compute_dtype))

    def forward(self, params, state, x, train=False, rng=None, mask=None, ctx=None):
        x = self.maybe_dropout(x, train, rng)
        b, T, _ = x.shape
        H = self.conf.n_out
        ad = acc_dtype(self.compute_dtype)
        xp = (x.reshape(b * T, -1).astype(self.compute_dtype)
              @ params["W"].astype(self.compute_dtype)).astype(ad)
        xp = xp.reshape(b, T, H) + params["b"].astype(ad)
        rw = params["RW"].astype(self.compute_dtype)   # bf16-gemm policy
        act = self.activation

        def step(h, inp):
            xt, mt = inp
            h_new = act(xt + lax.dot_general(
                h.astype(rw.dtype), rw, (((1,), (0,)), ((), ())),
                preferred_element_type=xt.dtype))
            if mt is not None:
                mm = mt[:, None].astype(h_new.dtype)
                h_new = mm * h_new + (1 - mm) * h
            return h_new, h_new

        idx = getattr(self, "index", None)
        h0 = None
        if ctx is not None and idx is not None:
            h0 = ctx.get("rnn_state_in", {}).get(idx)
        if h0 is None:
            h0 = jnp.zeros((b, H), ad)
        h0 = _match_vma(h0, xp)
        xs = jnp.swapaxes(xp, 0, 1)
        if mask is not None:
            ms = jnp.swapaxes(mask, 0, 1)
            hT, ys = lax.scan(step, h0, (xs, ms))
        else:
            hT, ys = lax.scan(lambda h, xt: step(h, (xt, None)), h0, xs)
        if ctx is not None and idx is not None:
            ctx.setdefault("rnn_state_out", {})[idx] = hT
        return jnp.swapaxes(ys, 0, 1).astype(self.out_dtype), state


class _WrapperImpl(LayerImpl):
    def __init__(self, conf, gc, input_type=None):
        super().__init__(conf, gc, input_type)
        self.inner = impl_for(conf.inner, gc, input_type)

    def regularization(self, params):
        return self.inner.regularization(params)


@implements("Bidirectional")
class BidirectionalImpl(_WrapperImpl):
    """Generic bidirectional wrapper (modes concat/add/mul/ave)."""

    def init(self, rng):
        kf, kb = jax.random.split(rng)
        pf, sf = self.inner.init(kf)
        pb, sb = self.inner.init(kb)
        return {"fwd": pf, "bwd": pb}, {"fwd": sf, "bwd": sb}

    def _merge(self, a, b):
        mode = self.conf.mode
        if mode == "concat":
            return jnp.concatenate([a, b], axis=-1)
        if mode == "add":
            return a + b
        if mode == "mul":
            return a * b
        if mode == "ave":
            return 0.5 * (a + b)
        raise ValueError(f"Unknown Bidirectional mode {mode}")

    def _run_directions(self, params, state, x, train, rng, mask):
        kf = kb = None
        if rng is not None:
            kf, kb = jax.random.split(rng)
        yf, sf = self.inner.forward(params["fwd"], state["fwd"], x, train=train,
                                    rng=kf, mask=mask, ctx=None)
        xr = jnp.flip(x, axis=1)
        mr = None if mask is None else jnp.flip(mask, axis=1)
        yb, sb = self.inner.forward(params["bwd"], state["bwd"], xr, train=train,
                                    rng=kb, mask=mr, ctx=None)
        return yf, yb, {"fwd": sf, "bwd": sb}

    def forward(self, params, state, x, train=False, rng=None, mask=None, ctx=None):
        yf, yb, new_state = self._run_directions(params, state, x, train, rng,
                                                 mask)
        return self._merge(yf, jnp.flip(yb, axis=1)), new_state

    def regularization(self, params):
        return (self.inner.regularization(params["fwd"])
                + self.inner.regularization(params["bwd"]))

    def forward_last(self, params, state, x, train=False, rng=None, mask=None,
                     ctx=None):
        """Per-direction final outputs, merged (reference/Keras
        ``Bidirectional(..., return_sequences=False)`` semantics): the
        BACKWARD direction's last step is its state after consuming the whole
        reversed sequence — full left context — not the t=T-1 slot of the
        flipped output sequence. Mask-correct for right-padded sequences:
        the recurrent impls freeze state on masked steps, so each direction's
        final output IS its last valid state (forward: padding freezes after
        the data; backward: the flipped mask holds state zero through the
        leading padding)."""
        yf, yb, new_state = self._run_directions(params, state, x, train, rng,
                                                 mask)
        return self._merge(yf[:, -1, :], yb[:, -1, :]), new_state


@implements("LastTimeStep")
class LastTimeStepImpl(_WrapperImpl):
    """Mask-aware last-timestep extraction (reference ``LastTimeStepVertex`` /
    ``LastTimeStep`` wrapper)."""

    def init(self, rng):
        return self.inner.init(rng)

    def forward(self, params, state, x, train=False, rng=None, mask=None, ctx=None):
        if hasattr(self.inner, "forward_last"):
            # bidirectional inner: each direction contributes ITS OWN final
            # step (full context both ways), not the t=T-1 concat slot
            return self.inner.forward_last(params, state, x, train=train,
                                           rng=rng, mask=mask, ctx=ctx)
        y, new_state = self.inner.forward(params, state, x, train=train, rng=rng,
                                          mask=mask, ctx=ctx)
        if mask is None:
            out = y[:, -1, :]
        else:
            last = jnp.maximum(jnp.sum(mask > 0, axis=1).astype(jnp.int32) - 1, 0)
            out = jnp.take_along_axis(y, last[:, None, None], axis=1)[:, 0, :]
        return out, new_state
