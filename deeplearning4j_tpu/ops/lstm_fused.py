"""Fused two-layer persistent LSTM kernel — one grid step per U timesteps
of BOTH stacked layers.

Why: a stack of two LSTMs (the char-RNN headline config, reference
``GravesLSTM`` twice) otherwise runs as two *sequential* persistent-kernel
chains (``ops/lstm_cell.py``) with the inter-layer activation doing a full
HBM round trip: layer 1 writes ys1 [T, b, H], a hoisted gemm turns it into
layer 2's xp2 [T, b, 4H] (another write + read). The measured bound at the
char-RNN shape is per-grid-step latency x chain length (PERF.md round-5:
unroll saturates at U=2, ~580k chars/s = 7.5% of the HBM roofline), so
halving the chain and deleting the xp2 stream attacks both terms at once:
one grid step computes layer-1 cell -> layer-2 cell back-to-back with
h1 handed over in registers, all three weight matrices (RW1, W2, RW2 — and
their transposes in the backward) VMEM-resident.

The cell math here is the UNMASKED core of ``lstm_cell._fwd_kernel`` /
``_bwd_kernel`` (tanh/sigmoid, Graves peepholes); step masks route pairs to
the per-layer kernels instead (``supported2`` returns False) — masked
batches are padding-dominated anyway, and keeping this kernel mask-free
keeps its VMEM budget honest. Backward is the same hand-written BPTT with
the extra inter-layer term: dh1_t += dz2_t @ W2^T. Parity for BOTH passes
is pinned against the composition of two ``lstm_cell.lstm_scan`` calls
(tests/test_lstm_fused.py), which are themselves pinned against the
``lax.scan`` oracle.

Reference: ``CudnnLSTMHelper.java`` (persistent RNN promise) — realized
here across the layer boundary, which cuDNN never fused.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import _vspec, _scratch, _interpret
from .lstm_cell import _sig, _stream_dtype, _unroll_factor

__all__ = ["lstm_scan2", "supported2"]


def _vmem_fits2(b: int, H: int, weight_bytes: int, u: int = 1) -> bool:
    """Budget for the fused pair: THREE resident [H, 4H] matrices (RW1, W2,
    RW2; the backward holds their transposes instead) plus ~1.7x the
    single-kernel streamed-block footprint (xp1 in; ys/gates/cseq reserves
    for BOTH layers + dz1/dz2 out) -> 12*H^2*wb + 50*sb*u*b*H bytes under
    the same 12 MB cap as ``lstm_cell._vmem_fits`` (VMEM is ~16 MB/core;
    the slack absorbs double-buffering + scratch). At the char-RNN shape
    (b=64, H=512, bf16 weights) this admits the fusion only under bf16
    streams — exactly the pairing the stream-dtype policy exists for."""
    sb = jnp.dtype(_stream_dtype()).itemsize
    return 12 * H * H * weight_bytes + 50 * sb * u * b * H <= 12 * 2 ** 20


def _unroll2(T: int, b: int, H: int, weight_bytes: int) -> int:
    """Same cap/decrement rule as ``lstm_cell._unroll_factor`` but against
    the fused budget."""
    u = _unroll_factor(T, b, H, weight_bytes)   # honors DL4J_TPU_LSTM_UNROLL
    while u > 1 and (T % u or not _vmem_fits2(b, H, weight_bytes, u)):
        u -= 1
    return u


def _cell_fwd(z, c, H, pi, pf, po):
    """Unmasked LSTM cell from pre-activations z [b, 4H] (f32): returns
    (h_new, c_new, gates [b, 4H] as i|f|o|g). Peephole terms apply when
    pi/pf/po are not None (Graves variant, lstm_cell._fwd_kernel:114)."""
    zi, zf, zo, zg = (z[:, :H], z[:, H:2 * H], z[:, 2 * H:3 * H],
                      z[:, 3 * H:])
    if pi is not None:
        zi = zi + c * pi[None, :]
        zf = zf + c * pf[None, :]
    i = _sig(zi)
    f = _sig(zf)
    g = jnp.tanh(zg)
    c_new = f * c + i * g
    if po is not None:
        zo = zo + c_new * po[None, :]
    o = _sig(zo)
    h_new = o * jnp.tanh(c_new)
    return h_new, c_new, jnp.concatenate([i, f, o, g], axis=-1)


def _cell_bwd(dh_tot, dc_tot, gts, c_out, c_prev, H, pi, pf, po):
    """Unmasked LSTM cell BPTT (lstm_cell._bwd_kernel core): returns
    (dz [b, 4H], dc_prev, (dzi, dzf, dzo)) — the dz* tuple feeds the
    peephole-gradient accumulators."""
    i, f, o, g = (gts[:, :H], gts[:, H:2 * H], gts[:, 2 * H:3 * H],
                  gts[:, 3 * H:])
    tc = jnp.tanh(c_out)
    do = dh_tot * tc
    dzo = do * o * (1.0 - o)
    dc = dc_tot + dh_tot * o * (1.0 - tc * tc)
    if po is not None:
        dc = dc + dzo * po[None, :]
    di = dc * g
    df = dc * c_prev
    dg = dc * i
    dzi = di * i * (1.0 - i)
    dzf = df * f * (1.0 - f)
    dzg = dg * (1.0 - g * g)
    dc_prev = dc * f
    if pi is not None:
        dc_prev = dc_prev + dzi * pi[None, :] + dzf * pf[None, :]
    return (jnp.concatenate([dzi, dzf, dzo, dzg], axis=-1), dc_prev,
            (dzi, dzf, dzo))


# ------------------------------------------------------------------ forward
def _fwd2_kernel(xp_ref, rw1_ref, w2_ref, b2_ref, rw2_ref, peep_ref,
                 h0_ref, ys1_ref, ys2_ref, g1_ref, c1_ref, g2_ref, c2_ref,
                 hc_ref, h1_s, c1_s, h2_s, c2_s, *, nb, H, peep, U, save):
    """One grid step: U timesteps of BOTH layers. ``h0_ref`` packs the four
    initial states [4, b, H] (h01, c01, h02, c02); ``peep_ref`` packs both
    layers' peepholes [8, H] (rows 0-2 layer 1, rows 3-5 layer 2);
    ``b2_ref`` is layer 2's bias broadcast row [8, 4H] (row 0)."""
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        h1_s[:] = h0_ref[0].astype(jnp.float32)
        c1_s[:] = h0_ref[1].astype(jnp.float32)
        h2_s[:] = h0_ref[2].astype(jnp.float32)
        c2_s[:] = h0_ref[3].astype(jnp.float32)

    h1, c1, h2, c2 = h1_s[:], c1_s[:], h2_s[:], c2_s[:]
    rw1 = rw1_ref[...]            # resident, source (bf16-policy) dtype
    w2 = w2_ref[...]
    rw2 = rw2_ref[...]
    b2 = b2_ref[0].astype(jnp.float32)                    # [4H]
    if peep:
        p1 = tuple(peep_ref[r].astype(jnp.float32) for r in range(3))
        p2 = tuple(peep_ref[r].astype(jnp.float32) for r in range(3, 6))
    else:
        p1 = p2 = (None, None, None)
    for u in range(U):
        z1 = xp_ref[u].astype(jnp.float32) + jax.lax.dot_general(
            h1.astype(rw1.dtype), rw1, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        h1, c1, gts1 = _cell_fwd(z1, c1, H, *p1)
        # the inter-layer handoff: h1 stays in registers — no ys1->xp2 HBM
        # round trip, no second sequential pass
        z2 = (b2[None, :]
              + jax.lax.dot_general(h1.astype(w2.dtype), w2,
                                    (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
              + jax.lax.dot_general(h2.astype(rw2.dtype), rw2,
                                    (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32))
        h2, c2, gts2 = _cell_fwd(z2, c2, H, *p2)
        ys2_ref[u] = h2.astype(ys2_ref.dtype)
        if save:
            # ys1 is a training residual (dW2 = ys1^T dz2, dRW1 h_prev) —
            # the inference primal never writes it (no dead HBM stream)
            ys1_ref[u] = h1.astype(ys1_ref.dtype)
            g1_ref[u] = gts1.astype(g1_ref.dtype)
            c1_ref[u] = c1.astype(c1_ref.dtype)
            g2_ref[u] = gts2.astype(g2_ref.dtype)
            c2_ref[u] = c2.astype(c2_ref.dtype)
    h1_s[:], c1_s[:], h2_s[:], c2_s[:] = h1, c1, h2, c2

    @pl.when(t == nb - 1)
    def _():
        hc_ref[0] = h1.astype(hc_ref.dtype)
        hc_ref[1] = c1.astype(hc_ref.dtype)
        hc_ref[2] = h2.astype(hc_ref.dtype)
        hc_ref[3] = c2.astype(hc_ref.dtype)


def _fwd2(xp, rw1, w2, b2, rw2, peep, h0, save_reserve=True):
    """xp: [T, b, 4H] (layer-1 input projection + bias), rw1/w2/rw2:
    [H, 4H], b2: [8, 4H] (row 0 = layer-2 bias), peep: [8, H] or None,
    h0: [4, b, H] -> (ys1, ys2 [T, b, H], reserves g1/c1/g2/c2, hcT
    [4, b, H]); ``save_reserve=False`` omits the four reserve outputs."""
    T, b, H4 = xp.shape
    H = H4 // 4
    U = _unroll2(T, b, H, jnp.dtype(rw1.dtype).itemsize)
    nb = T // U
    kern = functools.partial(_fwd2_kernel, nb=nb, H=H,
                             peep=peep is not None, U=U, save=save_reserve)
    stream = lambda t: (t, 0, 0)
    const2 = lambda t: (0, 0)
    const3 = lambda t: (0, 0, 0)
    specs = [
        _vspec((U, b, H4), stream),                       # xp (streamed)
        _vspec((H, H4), const2),                          # RW1 (resident)
        _vspec((H, H4), const2),                          # W2 (resident)
        _vspec((8, H4), const2),                          # b2 row
        _vspec((H, H4), const2),                          # RW2 (resident)
    ]
    ops = [xp, rw1, w2, b2, rw2]
    if peep is not None:
        specs.append(_vspec((8, H), const2))
        ops.append(peep)
    specs.append(_vspec((4, b, H), const3))               # h0 pack
    ops.append(h0)

    def shim(*refs):
        n_in = 5 + int(peep is not None) + 1
        ins, rest = refs[:n_in], refs[n_in:]
        peep_ref = ins[5] if peep is not None else None
        h0_ref = ins[-1]
        if save_reserve:
            (ys1_ref, ys2_ref, g1_ref, c1_ref, g2_ref, c2_ref, hc_ref,
             h1_s, c1_s, h2_s, c2_s) = rest
        else:
            (ys2_ref, hc_ref, h1_s, c1_s, h2_s, c2_s) = rest
            ys1_ref = g1_ref = c1_ref = g2_ref = c2_ref = None
        return kern(ins[0], ins[1], ins[2], ins[3], ins[4], peep_ref,
                    h0_ref, ys1_ref, ys2_ref, g1_ref, c1_ref, g2_ref,
                    c2_ref, hc_ref, h1_s, c1_s, h2_s, c2_s)

    sd = _stream_dtype()
    out_specs = []
    out_shape = []
    if save_reserve:
        out_specs += [_vspec((U, b, H), stream)]          # ys1 (residual)
        out_shape += [jax.ShapeDtypeStruct((T, b, H), sd)]
    out_specs += [_vspec((U, b, H), stream)]              # ys2
    out_shape += [jax.ShapeDtypeStruct((T, b, H), sd)]
    if save_reserve:
        out_specs += [_vspec((U, b, H4), stream),         # gates1
                      _vspec((U, b, H), stream),          # cseq1
                      _vspec((U, b, H4), stream),         # gates2
                      _vspec((U, b, H), stream)]          # cseq2
        out_shape += [jax.ShapeDtypeStruct((T, b, H4), sd),
                      jax.ShapeDtypeStruct((T, b, H), sd),
                      jax.ShapeDtypeStruct((T, b, H4), sd),
                      jax.ShapeDtypeStruct((T, b, H), sd)]
    out_specs.append(_vspec((4, b, H), const3))           # final states
    out_shape.append(jax.ShapeDtypeStruct((4, b, H), jnp.float32))
    res = pl.pallas_call(
        shim,
        grid=(nb,),
        in_specs=specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        scratch_shapes=[_scratch((b, H))] * 4,
        interpret=_interpret(),
        name="lstm_fused_fwd",
    )(*ops)
    if save_reserve:
        return res
    ys2, hc = res
    return None, ys2, None, None, None, None, hc


# ----------------------------------------------------------------- backward
def _bwd2_kernel(dy_ref, g1_ref, c1_ref, c1p_ref, g2_ref, c2_ref, c2p_ref,
                 rw1t_ref, w2t_ref, rw2t_ref, peep_ref, c0_ref, dhcT_ref,
                 dz1_ref, dz2_ref, dhc0_ref, dpeep_ref,
                 dh1_s, dc1_s, dh2_s, dc2_s, dp_s, *, nb, H, peep, U):
    """Reverse BPTT for the fused pair, U timesteps per grid step walked
    u = U-1..0. ``c0_ref`` packs (c01, c02) [2, b, H] for the sequence
    start; ``dhcT_ref`` packs the four incoming state cotangents
    [4, b, H]; ``c1p_ref``/``c2p_ref`` stream the previous block's last c
    row (lstm_cell._bwd_kernel's clamped-stream trick, per layer)."""
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        dh1_s[:] = dhcT_ref[0].astype(jnp.float32)
        dc1_s[:] = dhcT_ref[1].astype(jnp.float32)
        dh2_s[:] = dhcT_ref[2].astype(jnp.float32)
        dc2_s[:] = dhcT_ref[3].astype(jnp.float32)
        if peep:
            dp_s[:] = jnp.zeros_like(dp_s)

    rt_is_first = t == nb - 1
    rw1t = rw1t_ref[...]
    w2t = w2t_ref[...]
    rw2t = rw2t_ref[...]
    if peep:
        p1 = tuple(peep_ref[r].astype(jnp.float32) for r in range(3))
        p2 = tuple(peep_ref[r].astype(jnp.float32) for r in range(3, 6))
    else:
        p1 = p2 = (None, None, None)
    dh1, dc1 = dh1_s[:], dc1_s[:]
    dh2, dc2 = dh2_s[:], dc2_s[:]
    for u in reversed(range(U)):
        g1 = g1_ref[u].astype(jnp.float32)
        g2 = g2_ref[u].astype(jnp.float32)
        c1o = c1_ref[u].astype(jnp.float32)
        c2o = c2_ref[u].astype(jnp.float32)
        if u > 0:
            c1prev = c1_ref[u - 1].astype(jnp.float32)
            c2prev = c2_ref[u - 1].astype(jnp.float32)
        else:
            c1prev = jnp.where(rt_is_first, c0_ref[0].astype(jnp.float32),
                               c1p_ref[0].astype(jnp.float32))
            c2prev = jnp.where(rt_is_first, c0_ref[1].astype(jnp.float32),
                               c2p_ref[0].astype(jnp.float32))
        # layer 2 first (it owns the incoming dy), then its dz feeds
        # layer 1 through W2^T — the reverse of the forward handoff
        dh2_tot = dy_ref[u].astype(jnp.float32) + dh2
        dz2, dc2, dpz2 = _cell_bwd(dh2_tot, dc2, g2, c2o, c2prev, H, *p2)
        dh2 = jax.lax.dot_general(dz2.astype(rw2t.dtype), rw2t,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dh1_tot = dh1 + jax.lax.dot_general(
            dz2.astype(w2t.dtype), w2t, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dz1, dc1, dpz1 = _cell_bwd(dh1_tot, dc1, g1, c1o, c1prev, H, *p1)
        dh1 = jax.lax.dot_general(dz1.astype(rw1t.dtype), rw1t,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        if peep:
            dp_s[0] = dp_s[0] + jnp.sum(dpz1[0] * c1prev, axis=0)
            dp_s[1] = dp_s[1] + jnp.sum(dpz1[1] * c1prev, axis=0)
            dp_s[2] = dp_s[2] + jnp.sum(dpz1[2] * c1o, axis=0)
            dp_s[3] = dp_s[3] + jnp.sum(dpz2[0] * c2prev, axis=0)
            dp_s[4] = dp_s[4] + jnp.sum(dpz2[1] * c2prev, axis=0)
            dp_s[5] = dp_s[5] + jnp.sum(dpz2[2] * c2o, axis=0)
        dz1_ref[u] = dz1.astype(dz1_ref.dtype)
        dz2_ref[u] = dz2.astype(dz2_ref.dtype)
    dh1_s[:], dc1_s[:] = dh1, dc1
    dh2_s[:], dc2_s[:] = dh2, dc2

    @pl.when(t == nb - 1)
    def _():
        dhc0_ref[0] = dh1.astype(dhc0_ref.dtype)
        dhc0_ref[1] = dc1.astype(dhc0_ref.dtype)
        dhc0_ref[2] = dh2.astype(dhc0_ref.dtype)
        dhc0_ref[3] = dc2.astype(dhc0_ref.dtype)
        if peep:
            dpeep_ref[...] = dp_s[:].astype(dpeep_ref.dtype)
        else:
            dpeep_ref[...] = jnp.zeros(dpeep_ref.shape, dpeep_ref.dtype)


def _bwd2_call(dy, g1, c1seq, g2, c2seq, rw1t, w2t, rw2t, peep, c0, dhcT):
    T, b, H = dy.shape
    H4 = 4 * H
    U = _unroll2(T, b, H, jnp.dtype(rw1t.dtype).itemsize)
    nb = T // U
    kern = functools.partial(_bwd2_kernel, nb=nb, H=H,
                             peep=peep is not None, U=U)
    rev = lambda t: (nb - 1 - t, 0, 0)
    rev_prev = lambda t: (jnp.maximum((nb - 1 - t) * U - 1, 0), 0, 0)
    const2 = lambda t: (0, 0)
    const3 = lambda t: (0, 0, 0)
    specs = [
        _vspec((U, b, H), rev),                           # dy (= dys2)
        _vspec((U, b, H4), rev),                          # gates1
        _vspec((U, b, H), rev),                           # cseq1
        _vspec((1, b, H), rev_prev),                      # c1_{t-1} stream
        _vspec((U, b, H4), rev),                          # gates2
        _vspec((U, b, H), rev),                           # cseq2
        _vspec((1, b, H), rev_prev),                      # c2_{t-1} stream
        _vspec((H4, H), const2),                          # RW1^T
        _vspec((H4, H), const2),                          # W2^T
        _vspec((H4, H), const2),                          # RW2^T
    ]
    ops = [dy, g1, c1seq, c1seq, g2, c2seq, c2seq, rw1t, w2t, rw2t]
    if peep is not None:
        specs.append(_vspec((8, H), const2))
        ops.append(peep)
    specs += [_vspec((2, b, H), const3),                  # (c01, c02)
              _vspec((4, b, H), const3)]                  # dhcT pack
    ops += [c0, dhcT]

    def shim(*refs):
        n_in = 10 + int(peep is not None) + 2
        ins, rest = refs[:n_in], refs[n_in:]
        peep_ref = ins[10] if peep is not None else None
        return kern(ins[0], ins[1], ins[2], ins[3], ins[4], ins[5], ins[6],
                    ins[7], ins[8], ins[9], peep_ref, ins[-2], ins[-1],
                    *rest)

    sd = _stream_dtype()
    f32 = jnp.float32
    return pl.pallas_call(
        shim,
        grid=(nb,),
        in_specs=specs,
        out_specs=(
            _vspec((U, b, H4), rev),                      # dz1
            _vspec((U, b, H4), rev),                      # dz2
            _vspec((4, b, H), const3),                    # dhc0 pack
            _vspec((8, H), const2),                       # dpeep pack
        ),
        out_shape=(jax.ShapeDtypeStruct((T, b, H4), sd),
                   jax.ShapeDtypeStruct((T, b, H4), sd),
                   jax.ShapeDtypeStruct((4, b, H), f32),
                   jax.ShapeDtypeStruct((8, H), f32)),
        scratch_shapes=[_scratch((b, H))] * 4 + [_scratch((8, H))],
        interpret=_interpret(),
        name="lstm_fused_bwd",
    )(*ops)


# ------------------------------------------------------------- public entry
@functools.partial(jax.custom_vjp, nondiff_argnums=())
def _lstm2(xp, rw1, w2, b2, rw2, peep, h0):
    ys1, ys2, _, _, _, _, hc = _fwd2(xp, rw1, w2, b2, rw2, peep, h0,
                                     save_reserve=False)
    return ys2, hc


def _lstm2_fwd(xp, rw1, w2, b2, rw2, peep, h0):
    ys1, ys2, g1, c1, g2, c2, hc = _fwd2(xp, rw1, w2, b2, rw2, peep, h0)
    return (ys2, hc), (rw1, w2, b2, rw2, peep, h0, ys1, ys2, g1, c1, g2, c2)


def _lstm2_bwd(res, grads):
    rw1, w2, b2, rw2, peep, h0, ys1, ys2, g1, c1seq, g2, c2seq = res
    dy2, dhc = grads
    dy2 = dy2.astype(jnp.float32)
    c0pack = jnp.stack([h0[1].astype(jnp.float32),
                        h0[3].astype(jnp.float32)])
    dz1, dz2, dhc0, dpeep = _bwd2_call(
        dy2, g1, c1seq, g2, c2seq,
        jnp.swapaxes(rw1, 0, 1), jnp.swapaxes(w2, 0, 1),
        jnp.swapaxes(rw2, 0, 1), peep, c0pack, dhc.astype(jnp.float32))
    # batched-over-time weight gradients as single MXU gemms (outside):
    #   z1 = xp + h1_{t-1} @ RW1          -> dRW1 = sum h1_{t-1}^T dz1
    #   z2 = h1_t @ W2 + b2 + h2_{t-1} @ RW2
    #     -> dW2 = sum ys1_t^T dz2,  db2 = sum dz2,
    #        dRW2 = sum h2_{t-1}^T dz2
    h1_prev = jnp.concatenate([h0[0].astype(ys1.dtype)[None], ys1[:-1]], 0)
    h2_prev = jnp.concatenate([h0[2].astype(ys2.dtype)[None], ys2[:-1]], 0)
    drw1 = jnp.einsum("tbh,tbg->hg", h1_prev.astype(rw1.dtype),
                      dz1.astype(rw1.dtype),
                      preferred_element_type=jnp.float32).astype(rw1.dtype)
    dw2 = jnp.einsum("tbh,tbg->hg", ys1.astype(w2.dtype),
                     dz2.astype(w2.dtype),
                     preferred_element_type=jnp.float32).astype(w2.dtype)
    drw2 = jnp.einsum("tbh,tbg->hg", h2_prev.astype(rw2.dtype),
                      dz2.astype(rw2.dtype),
                      preferred_element_type=jnp.float32).astype(rw2.dtype)
    db2 = jnp.zeros_like(b2).at[0].set(
        jnp.sum(dz2.astype(jnp.float32), axis=(0, 1)).astype(b2.dtype))
    dpeep_out = None if peep is None else dpeep.astype(peep.dtype)
    return (dz1, drw1, dw2, db2, drw2, dpeep_out, dhc0)


_lstm2.defvjp(_lstm2_fwd, _lstm2_bwd)


def supported2(b: int, T: int, H: int, weight_bytes: int = 4) -> bool:
    """Whether the fused two-layer kernel applies (the caller must already
    have checked each layer's ``lstm_cell.supported`` contract: tanh cell +
    sigmoid gates, aligned dims). ``DL4J_TPU_NO_FUSED_LSTM=1`` is the
    escape hatch (same first-hardware insurance as the per-layer kernel's
    ``DL4J_TPU_NO_PERSISTENT_LSTM``)."""
    import os
    if os.environ.get("DL4J_TPU_NO_FUSED_LSTM"):
        return False
    if os.environ.get("DL4J_TPU_NO_PERSISTENT_LSTM"):
        return False
    from . import flash_attention as _fa
    if not (_fa._FORCE_INTERPRET or _fa._on_tpu()):
        return False
    if not _vmem_fits2(b, H, weight_bytes) or b > 1024:
        return False
    return H % 128 == 0 and b % 8 == 0 and T >= 1


def lstm_scan2(xp1, rw1, peep1, w2, b2, rw2, peep2, h01, c01, h02, c02):
    """Fused two-layer LSTM sequence step. ``xp1``: [b, T, 4H] (layer-1
    hoisted input projection + bias), ``rw1``/``rw2``: [H, 4H] recurrent
    weights, ``w2``: [H, 4H] layer-2 input weights, ``b2``: [4H] layer-2
    bias, ``peep1``/``peep2``: (pi, pf, po) tuples or None (must agree on
    None-ness — mixed stacks take the per-layer path), ``h01``..``c02``:
    [b, H] initial states. No step masks (route masked batches to
    ``lstm_cell.lstm_scan`` per layer). Returns
    (ys2 [b, T, H] in the stream dtype, (h1T, c1T), (h2T, c2T) in f32)."""
    b, T, H4 = xp1.shape
    H = H4 // 4
    xp_tm = jnp.swapaxes(xp1, 0, 1)
    pk = None
    if peep1 is not None:
        pk = jnp.zeros((8, H), jnp.float32)
        for r, v in enumerate(peep1 + tuple(peep2)):
            pk = pk.at[r].set(v.astype(jnp.float32))
    b2row = jnp.zeros((8, H4), jnp.float32).at[0].set(
        b2.astype(jnp.float32))
    h0 = jnp.stack([h01.astype(jnp.float32), c01.astype(jnp.float32),
                    h02.astype(jnp.float32), c02.astype(jnp.float32)])
    ys2, hc = _lstm2(xp_tm.astype(_stream_dtype()), rw1, w2, b2row, rw2,
                     pk, h0)
    return (jnp.swapaxes(ys2, 0, 1), (hc[0], hc[1]), (hc[2], hc[3]))
