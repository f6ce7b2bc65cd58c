"""Pallas persistent-LSTM kernel — the recurrent hot loop with the
recurrent weights VMEM-RESIDENT across the whole sequence.

Why: the container LSTM (``nn/layers/recurrent.py``) hoists the input
projection out of the scan (one big MXU gemm), but the remaining sequential
chain ``z_t = xw_t + b + h @ RW`` re-streams ``RW [H, 4H]`` from HBM every
timestep: at char-RNN shapes (H=512 → 2 MB bf16) that is T × 2 MB per layer
per direction, and the step is weight-bandwidth-bound at ~1% MFU — exactly
the workload the reference dedicates ``CudnnLSTMHelper.java`` (persistent
RNN) to. These kernels run the whole time loop on a 1-D Pallas grid with
``RW`` (and its transpose, in the backward) loaded into VMEM ONCE
(constant index_map → the DMA is issued for step 0 and skipped after),
h/c carried in VMEM scratch, and only the per-step activations
([b, 4H] / [b, H]) streamed — turning the weight stream from O(T·H·4H)
into O(H·4H).

Backward is the standard LSTM BPTT, hand-written (the cuDNN-helper pattern
the repo already uses for flash attention: custom kernel behind the same
layer math, ``lax.scan`` path as the always-available oracle/fallback):
the forward saves the post-activation gates [T, b, 4H] and the cell
sequence (cuDNN "reserve space"), the reverse kernel carries (dh, dc),
emits per-step pre-activation gradients dz [T, b, 4H] and sums them into
the bias gradient db; everything else batched-over-time (dW, dRW, dx,
h_prev) happens OUTSIDE as single MXU gemms. Supports the Graves peephole
variant (``pi/pf/po``) and per-step [b] sequence masks — both GravesLSTM
semantics from the reference (``GravesLSTM.java``,
``LSTMHelpers.java:206-212``).

Layout and dtypes — the contract with the one call site, the kernel branch
of ``recurrent._BaseLSTMImpl._run``: everything is time-major [T, b, ...]
(the grid walks T), at :func:`lstm_scan` as inside the kernels, so the
caller swaps its NARROW input ([b, T, nIn]) once and no [·, ·, 4H] tensor
is ever transposed. The streamed operands travel in the dtype of the gemms
on their other side and are widened / narrowed in-kernel: ``xw`` (the
projection, bias NOT added) in the dtype its gemm wrote, ``ys`` in the
layer's activation dtype, ``dy`` as AD hands it over (``ys``'s dtype),
``dz`` in ``xw``'s dtype (it is ``xw``'s cotangent and the operand of the
three gradient gemms). Under the bf16 policy that is bf16 for all four,
under f32 compute f32; nobody sets it. The bias rides as a resident f32
row and is added in-kernel; ``db`` is summed in-kernel from the f32 ``dz``.
The RESERVE (gates, cseq) alone follows ``DL4J_TPU_LSTM_STREAM_DTYPE``
(f32 by default). h/c state, gate math and all accumulation are f32
throughout; tanh cell activation and sigmoid gates (the ``supported()``
contract — other activations fall back to the scan).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .flash_attention import _vspec, _scratch, _interpret

__all__ = ["lstm_scan", "supported"]


def _sig(x):
    return jax.nn.sigmoid(x)


def _pad8(*rows):
    """Up to 8 rows [n] → f32 [8, n], zero below them: small per-feature
    vectors (the bias, the peepholes) as one resident tile."""
    return jnp.pad(jnp.stack(rows).astype(jnp.float32),
                   ((0, 8 - len(rows)), (0, 0)))


def _stream_dtype():
    """Dtype of the RESERVE the forward saves for the backward (gates,
    cseq), and of nothing else: ``DL4J_TPU_LSTM_STREAM_DTYPE`` = ``float32``
    (default) or ``bfloat16``. bf16 halves the reserve's HBM traffic (the
    cuDNN reserve-space convention stores the compute dtype) at a small
    recompute-precision cost in the backward, and is what admits
    ``lstm_fused`` at the char-RNN shape; h/c state and all gate math stay
    f32 regardless. The other streams (xw, ys, dy, dz) follow their
    operands' dtypes (module docstring). The VMEM budgets below still count
    every stream at this width: at the f32 default that is an upper bound.
    TRACE-TIME knob, same caveat as ``DL4J_TPU_LSTM_UNROLL``: set it before
    the first step of a config."""
    import os
    v = os.environ.get("DL4J_TPU_LSTM_STREAM_DTYPE", "float32")
    return jnp.bfloat16 if v in ("bfloat16", "bf16") else jnp.float32


def _vmem_fits(b: int, H: int, weight_bytes: int, u: int = 1) -> bool:
    """One budget definition for supported() AND _unroll_factor: resident
    [H, 4H] weights + the u-scaled double-buffered streamed blocks must fit
    a core's VMEM (measured heuristic — see supported()). The stream term
    scales with the stream dtype (30·stream_bytes·u·b·H: 120 coeff at f32,
    60 at bf16 — bf16 streams double the U the budget admits)."""
    sb = jnp.dtype(_stream_dtype()).itemsize
    return 4 * H * H * weight_bytes + 30 * sb * u * b * H <= 12 * 2 ** 20


def _unroll_factor(T: int, b: int, H: int, weight_bytes: int) -> int:
    """Timesteps per grid step. The sequential chain is bound by per-grid-
    step latency (PERF.md round-4 addendum 3), so U > 1 divides it — but
    every streamed block ([U, b, 4H] xp/gates/dz, double-buffered) scales
    with U, so U shrinks until the VMEM budget fits. T must divide evenly.
    ``DL4J_TPU_LSTM_UNROLL`` overrides the default (2); 1 disables.

    TRACE-TIME knob: the env var is read when the enclosing step is traced
    (first call per shape). Once jit has cached a compiled step, changing
    it has NO effect on subsequent steps of the same config — set it before
    the first fit/step, or clear jax caches to re-trace."""
    import os
    try:
        u = int(os.environ.get("DL4J_TPU_LSTM_UNROLL", "2"))
    except ValueError:
        u = 2
    u = max(1, min(u, T))
    while u > 1 and (T % u or not _vmem_fits(b, H, weight_bytes, u)):
        u -= 1
    return u


# ------------------------------------------------------------------ forward
def _fwd_kernel(xw_ref, b_ref, rw_ref, peep_ref, m_ref, h0_ref, c0_ref,
                ys_ref, gates_ref, cseq_ref, hc_ref,
                h_s, c_s, *, nb, H, peep, U):
    """One grid step processes U consecutive timesteps (statically
    unrolled): the measured bound at the char-RNN config is per-grid-step
    latency × the sequential chain length, not FLOPs or HBM bytes
    (PERF.md round-4 addendum 3) — U steps per launch divides that chain
    by U. All block operands carry a leading [U] time dim."""
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        h_s[:] = h0_ref[...].astype(jnp.float32)
        c_s[:] = c0_ref[...].astype(jnp.float32)

    h = h_s[:]
    c = c_s[:]
    # resident [H, 4H] in its SOURCE dtype (bf16 under the mixed-precision
    # policy): the MXU runs a native bf16×bf16→f32 pass instead of the
    # multi-pass f32 algorithm, and the resident footprint halves. h/c stay
    # f32 in scratch (accumulation dtype); only the gemm operand is cast.
    rw = rw_ref[...]
    bias = b_ref[0]                                       # [4H] f32
    if peep:
        pi = peep_ref[0].astype(jnp.float32)              # [H]
        pf = peep_ref[1].astype(jnp.float32)
        po = peep_ref[2].astype(jnp.float32)
    for u in range(U):
        # (xw + b) first, then + h·RW: the order the layer's XLA bias add
        # followed by the kernel's add used to give, so z keeps its bits
        z = (xw_ref[u].astype(jnp.float32) + bias[None, :]) \
            + jax.lax.dot_general(
                h.astype(rw.dtype), rw, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)       # [b, 4H]
        zi, zf, zo, zg = (z[:, :H], z[:, H:2 * H], z[:, 2 * H:3 * H],
                          z[:, 3 * H:])
        if peep:
            zi = zi + c * pi[None, :]
            zf = zf + c * pf[None, :]
        i = _sig(zi)
        f = _sig(zf)
        g = jnp.tanh(zg)
        c_new = f * c + i * g
        if peep:
            zo = zo + c_new * po[None, :]
        o = _sig(zo)
        h_new = o * jnp.tanh(c_new)
        if m_ref is not None:
            m = m_ref[u, :, 0][:, None]                   # [b, 1]
            h_new = m * h_new + (1.0 - m) * h
            c_new = m * c_new + (1.0 - m) * c
        ys_ref[u] = h_new.astype(ys_ref.dtype)
        if gates_ref is not None:  # reserve for BPTT (training fwd only)
            gates_ref[u] = jnp.concatenate([i, f, o, g], axis=-1
                                           ).astype(gates_ref.dtype)
            cseq_ref[u] = c_new.astype(cseq_ref.dtype)
        h, c = h_new, c_new
    h_s[:] = h
    c_s[:] = c

    @pl.when(t == nb - 1)
    def _():
        hc_ref[0] = h.astype(hc_ref.dtype)
        hc_ref[1] = c.astype(hc_ref.dtype)


def _fwd(xw, bias, rw, peep, h0, c0, mask, ys_dtype, save_reserve=True):
    """xw: [T, b, 4H] (input projection, no bias, any float dtype), bias:
    [4H] f32, rw: [H, 4H], peep: [8, H] or None, h0/c0: [b, H], mask:
    [T, b, 8] or None → (ys [T, b, H] in ``ys_dtype``, gates [T, b, 4H],
    cseq [T, b, H] (both in the reserve dtype), hcT [2, b, H] f32);
    ``save_reserve=False`` (inference primal) omits the gates/cseq reserve
    outputs entirely — no dead HBM writes on the non-training path — and
    returns (ys, None, None, hcT)."""
    T, b, H4 = xw.shape
    H = H4 // 4
    U = _unroll_factor(T, b, H, jnp.dtype(rw.dtype).itemsize)
    nb = T // U
    kern = functools.partial(_fwd_kernel, nb=nb, H=H, peep=peep is not None,
                             U=U)
    const3 = lambda t: (0, 0, 0)
    const2 = lambda t: (0, 0)
    specs = [
        _vspec((U, b, H4), lambda t: (t, 0, 0)),          # xw (streamed)
        _vspec((8, H4), const2),                          # bias (row 0)
        _vspec((H, H4), const2),                          # rw (resident)
    ]
    ops = [xw, _pad8(bias), rw]
    if peep is not None:
        specs.append(_vspec((8, H), const2))              # peepholes
        ops.append(peep)
    has_mask = mask is not None
    if has_mask:
        specs.append(_vspec((U, b, 8), lambda t: (t, 0, 0)))
        ops.append(mask)
    specs += [_vspec((b, H), const2), _vspec((b, H), const2)]   # h0, c0
    ops += [h0, c0]

    def shim(*refs):
        n_in = 3 + int(peep is not None) + int(has_mask) + 2
        ins, rest = refs[:n_in], refs[n_in:]
        pos = 3
        peep_ref = ins[pos] if peep is not None else None
        pos += int(peep is not None)
        m_ref = ins[pos] if has_mask else None
        pos += int(has_mask)
        if save_reserve:
            ys_ref, gates_ref, cseq_ref, hc_ref, h_s, c_s = rest
        else:
            (ys_ref, hc_ref, h_s, c_s), gates_ref, cseq_ref = rest, None, \
                None
        return kern(ins[0], ins[1], ins[2], peep_ref, m_ref, ins[pos],
                    ins[pos + 1], ys_ref, gates_ref, cseq_ref, hc_ref,
                    h_s, c_s)

    sd = _stream_dtype()          # reserve dtype (policy knob)
    out_specs = [_vspec((U, b, H), lambda t: (t, 0, 0))]  # ys
    out_shape = [jax.ShapeDtypeStruct((T, b, H), ys_dtype)]
    if save_reserve:
        out_specs += [
            _vspec((U, b, H4), lambda t: (t, 0, 0)),      # gates (reserve)
            _vspec((U, b, H), lambda t: (t, 0, 0)),       # c sequence
        ]
        out_shape += [jax.ShapeDtypeStruct((T, b, H4), sd),
                      jax.ShapeDtypeStruct((T, b, H), sd)]
    out_specs.append(_vspec((2, b, H), const3))           # final (h, c):
    out_shape.append(jax.ShapeDtypeStruct((2, b, H), jnp.float32))
    res = pl.pallas_call(
        shim,
        grid=(nb,),
        in_specs=specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        scratch_shapes=[_scratch((b, H)), _scratch((b, H))],
        interpret=_interpret(),
        name="lstm_cell_fwd",
    )(*ops)
    if save_reserve:
        return res
    ys, hc = res
    return ys, None, None, hc


# ----------------------------------------------------------------- backward
def _bwd_kernel(dy_ref, gates_ref, cseq_ref, cprev_ref, rwt_ref, peep_ref,
                m_ref, c0_ref, dhT_ref, dcT_ref,
                dz_ref, dh0_ref, dc0_ref, dpeep_ref, db_ref,
                dh_s, dc_s, dp_s, db_s, *, nb, H, peep, U):
    """Reverse BPTT, U timesteps per grid step (statically unrolled, walked
    u = U-1 … 0 inside the block). ``cprev_ref`` streams the PREVIOUS
    block of the c sequence — in-block u > 0 takes c_{t-1} from the local
    block, u == 0 takes it from ``cprev_ref[U-1]`` (or c0 at the sequence
    start). ``db_s`` [8, 4H] collects the bias gradient Σ_t Σ_b dz."""
    t = pl.program_id(0)          # walks 0..nb-1; blocks indexed nb-1-t

    @pl.when(t == 0)
    def _():
        dh_s[:] = dhT_ref[...].astype(jnp.float32)
        dc_s[:] = dcT_ref[...].astype(jnp.float32)
        db_s[:] = jnp.zeros_like(db_s)
        if peep:
            dp_s[:] = jnp.zeros_like(dp_s)

    rt_is_first = t == nb - 1     # reverse block at sequence start
    rwt = rwt_ref[...]            # resident [4H, H], source (bf16) dtype
    if peep:
        pi = peep_ref[0].astype(jnp.float32)
        pf = peep_ref[1].astype(jnp.float32)
        po = peep_ref[2].astype(jnp.float32)
    dh_carry = dh_s[:]
    dc_carry = dc_s[:]
    db = db_s[:]
    for u in reversed(range(U)):
        gts = gates_ref[u].astype(jnp.float32)
        i, f, o, g = (gts[:, :H], gts[:, H:2 * H], gts[:, 2 * H:3 * H],
                      gts[:, 3 * H:])
        c_out = cseq_ref[u].astype(jnp.float32)
        if u > 0:
            c_prev = cseq_ref[u - 1].astype(jnp.float32)
        else:
            # first step of the block: c_{t-1} lives in the previous block
            # (clamped stream), or is c0 at the very start of the sequence
            c_prev = jnp.where(rt_is_first,
                               c0_ref[...].astype(jnp.float32),
                               cprev_ref[0].astype(jnp.float32))
        dh_tot = dy_ref[u].astype(jnp.float32) + dh_carry
        dc_tot = dc_carry
        if m_ref is not None:
            m = m_ref[u, :, 0][:, None]
        else:
            m = None
        dh_c = dh_tot if m is None else m * dh_tot
        dc_c = dc_tot if m is None else m * dc_tot
        # cseq stores the POST-mask c_eff (it is the next step's c_prev);
        # the tanh/peephole-o in the forward used the PRE-mask candidate —
        # recompute it from the saved gates so masked-step gradients are
        # exact for any mask value in [0, 1], not just binary
        c_cand = c_out if m is None else f * c_prev + i * g
        tc = jnp.tanh(c_cand)
        do = dh_c * tc
        dzo = do * o * (1.0 - o)
        dc = dc_c + dh_c * o * (1.0 - tc * tc)
        if peep:
            dc = dc + dzo * po[None, :]
        di = dc * g
        df = dc * c_prev
        dg = dc * i
        dzi = di * i * (1.0 - i)
        dzf = df * f * (1.0 - f)
        dzg = dg * (1.0 - g * g)
        dc_prev = dc * f
        if peep:
            dc_prev = dc_prev + dzi * pi[None, :] + dzf * pf[None, :]
            # peephole grads accumulate across steps ([8, H] scratch 0-2)
            dp_s[0] = dp_s[0] + jnp.sum(dzi * c_prev, axis=0)
            dp_s[1] = dp_s[1] + jnp.sum(dzf * c_prev, axis=0)
            dp_s[2] = dp_s[2] + jnp.sum(dzo * c_cand, axis=0)
        dz = jnp.concatenate([dzi, dzf, dzo, dzg], axis=-1)   # [b, 4H]
        dh_prev = jax.lax.dot_general(dz.astype(rwt.dtype), rwt,
                                      (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        if m is not None:
            # dc/dz already carry the m factor (via dh_c/dc_c) — only the
            # straight-through (1-m) residual is added here; an extra m
            # factor would double-scale fractional masks (binary: m² = m)
            dh_prev = dh_prev + (1.0 - m) * dh_tot
            dc_prev = dc_prev + (1.0 - m) * dc_tot
        dz_ref[u] = dz.astype(dz_ref.dtype)
        # bias gradient from the f32 dz, before the stream rounds it: row r
        # of db sums the batch rows ≡ r (mod 8) — whole-tile adds per step,
        # the 8 rows are summed once, outside
        for k in range(0, dz.shape[0], 8):
            db = db + dz[k:k + 8]
        dh_carry, dc_carry = dh_prev, dc_prev
    dh_s[:] = dh_carry
    dc_s[:] = dc_carry
    db_s[:] = db

    @pl.when(t == nb - 1)
    def _():
        dh0_ref[...] = dh_carry.astype(dh0_ref.dtype)
        dc0_ref[...] = dc_carry.astype(dc0_ref.dtype)
        db_ref[...] = db
        if peep:
            dpeep_ref[...] = dp_s[:].astype(dpeep_ref.dtype)
        else:
            dpeep_ref[...] = jnp.zeros(dpeep_ref.shape, dpeep_ref.dtype)


def _bwd_call(dy, gates, cseq, rwt, peep, mask, c0, dhT, dcT, dz_dtype):
    """→ (dz [T, b, 4H] in ``dz_dtype``, dh0, dc0 [b, H], dpeep [8, H],
    db [8, 4H]: partial sums, see the kernel), all but dz in f32."""
    T, b, H = dy.shape
    H4 = 4 * H
    U = _unroll_factor(T, b, H, jnp.dtype(rwt.dtype).itemsize)
    nb = T // U
    kern = functools.partial(_bwd_kernel, nb=nb, H=H, peep=peep is not None,
                             U=U)
    rev = lambda t: (nb - 1 - t, 0, 0)
    # c_prev stream: ONE row — the last element of block rt-1 (block size 1
    # on the time dim ⇒ the index map is an ELEMENT index), clamped at 0
    # and selected against c0 in-kernel at the sequence start
    rev_prev = lambda t: (jnp.maximum((nb - 1 - t) * U - 1, 0), 0, 0)
    const2 = lambda t: (0, 0)
    specs = [
        _vspec((U, b, H), rev),                           # dy
        _vspec((U, b, H4), rev),                          # gates
        _vspec((U, b, H), rev),                           # c sequence
        _vspec((1, b, H), rev_prev),                      # c_{t-1} stream
        _vspec((H4, H), const2),                          # rw^T (resident)
    ]
    ops = [dy, gates, cseq, cseq, rwt]
    if peep is not None:
        specs.append(_vspec((8, H), const2))
        ops.append(peep)
    has_mask = mask is not None
    if has_mask:
        specs.append(_vspec((U, b, 8), rev))
        ops.append(mask)
    specs += [_vspec((b, H), const2)] * 3                 # c0, dhT, dcT
    ops += [c0, dhT, dcT]

    def shim(*refs):
        n_in = 5 + int(peep is not None) + int(has_mask) + 3
        ins, rest = refs[:n_in], refs[n_in:]
        pos = 5
        peep_ref = ins[pos] if peep is not None else None
        pos += int(peep is not None)
        m_ref = ins[pos] if has_mask else None
        pos += int(has_mask)
        return kern(ins[0], ins[1], ins[2], ins[3], ins[4], peep_ref, m_ref,
                    ins[pos], ins[pos + 1], ins[pos + 2], *rest)

    f32 = jnp.float32
    return pl.pallas_call(
        shim,
        grid=(nb,),
        in_specs=specs,
        out_specs=(
            _vspec((U, b, H4), rev),                      # dz per step
            _vspec((b, H), const2),                       # dh0
            _vspec((b, H), const2),                       # dc0
            _vspec((8, H), const2),                       # dpeep
            _vspec((8, H4), const2),                      # db (8 partials)
        ),
        out_shape=(jax.ShapeDtypeStruct((T, b, H4), dz_dtype),
                   jax.ShapeDtypeStruct((b, H), f32),
                   jax.ShapeDtypeStruct((b, H), f32),
                   jax.ShapeDtypeStruct((8, H), f32),
                   jax.ShapeDtypeStruct((8, H4), f32)),
        scratch_shapes=[_scratch((b, H)), _scratch((b, H)),
                        _scratch((8, H)), _scratch((8, H4))],
        interpret=_interpret(),
        name="lstm_cell_bwd",
    )(*ops)


# ------------------------------------------------------------- public entry
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _lstm(ys_dtype, xw, bias, rw, peep, h0, c0, mask):
    # primal (inference) path: no reserve tensors — the BPTT residuals are
    # only materialized by _lstm_fwd under differentiation
    ys, _, _, hc = _fwd(xw, bias, rw, peep, h0, c0, mask, ys_dtype,
                        save_reserve=False)
    return ys, hc[0], hc[1]


def _lstm_fwd(ys_dtype, xw, bias, rw, peep, h0, c0, mask):
    ys, gates, cseq, hc = _fwd(xw, bias, rw, peep, h0, c0, mask, ys_dtype)
    # xw itself is not needed again; its (empty) slice carries its dtype,
    # which is dz's, to the backward
    return (ys, hc[0], hc[1]), (xw[:0], rw, peep, h0, c0, mask, ys, gates,
                                cseq)


def _lstm_bwd(ys_dtype, res, grads):
    xw0, rw, peep, h0, c0, mask, ys, gates, cseq = res
    dy, dhT, dcT = grads
    rwt = jnp.swapaxes(rw, 0, 1)
    dz, dh0, dc0, dpeep, db = _bwd_call(dy, gates, cseq, rwt, peep, mask,
                                        c0.astype(jnp.float32),
                                        dhT.astype(jnp.float32),
                                        dcT.astype(jnp.float32), xw0.dtype)
    # batched-over-time pieces as single MXU gemms (outside the kernel):
    # z_t = xw_t + b + h_{t-1} @ RW  →  dxw = dz,  dRW = Σ_t h_{t-1}ᵀ dz_t.
    # ys and dz already are what the gemm reads (the compute dtype under
    # the bf16 policy), f32 accumulation
    h_prev = jnp.concatenate([h0.astype(ys.dtype)[None], ys[:-1]], axis=0)
    drw = jnp.einsum("tbh,tbg->hg", h_prev, dz,
                     preferred_element_type=jnp.float32).astype(rw.dtype)
    dpeep_out = None if peep is None else dpeep.astype(peep.dtype)
    dmask = None if mask is None else jnp.zeros_like(mask)
    return (dz, db.sum(axis=0), drw, dpeep_out, dh0, dc0, dmask)


_lstm.defvjp(_lstm_fwd, _lstm_bwd)


#: kernel contract: tanh cell activation + sigmoid gates, TPU-tileable dims
def supported(b: int, T: int, H: int, activation: str,
              gate_activation: str, weight_bytes: int = 4) -> bool:
    """Whether the persistent kernel applies: TPU backend (or the tests'
    forced interpret mode), tanh/sigmoid activations (the kernel hard-codes
    them), lane-aligned width and sublane-aligned batch. Everything else
    falls back to the ``lax.scan`` oracle path. Escape hatch:
    ``DL4J_TPU_NO_PERSISTENT_LSTM=1`` forces the scan path (first-hardware
    insurance — the kernel is interpret-verified, and this keeps a
    one-variable rollback if a Mosaic lowering gap surfaces on a new
    jaxlib)."""
    import os
    if os.environ.get("DL4J_TPU_NO_PERSISTENT_LSTM"):
        return False
    from . import flash_attention as _fa
    if not (_fa._FORCE_INTERPRET or _fa._on_tpu()):
        return False
    # VMEM budget: resident [H, 4H] weights (4H² elements × weight_bytes;
    # the bwd kernel holds the transpose) PLUS the batch-dependent per-step
    # blocks — xp/ys/gates/cseq/dz streams (double-buffered by the
    # pipeline), h0/c0/dhT/dcT and the h/c scratch. Worst case (bwd) ≈
    # 4H²·wb + 30·sb·u·b·H bytes where sb is the STREAM dtype's width
    # (DL4J_TPU_LSTM_STREAM_DTYPE: 120·u·b·H at the f32 default, 60·u·b·H
    # at bf16 — see _vmem_fits); cap the SUM under a core's VMEM so
    # oversized configs fall back to the scan instead of failing a Mosaic
    # allocation. bf16-resident weights (weight_bytes=2, the
    # mixed-precision policy) halve the resident term. At f32 streams:
    # f32-weights b=64,H=512 → 7.9 MB ✓; b=256,H=512 → 19.7 MB ✗ → scan;
    # bf16-weights b=64,H=1024 → 16.2 MB ✗ → scan, b=128,H=512 → 10 MB ✓.
    # bf16 streams halve the b-dependent term, roughly doubling each bound.
    if not _vmem_fits(b, H, weight_bytes) or b > 1024:
        return False
    return (activation == "tanh" and gate_activation == "sigmoid"
            and H % 128 == 0 and b % 8 == 0 and T >= 1)


def lstm_scan(xw, bias, rw, peep, h0, c0, mask=None, out_dtype=None):
    """Persistent-LSTM sequence step, TIME-MAJOR. ``xw``: [T, b, 4H] hoisted
    input projection WITHOUT the bias, in the dtype its gemm produced
    (bf16 under the mixed-precision policy, f32 under f32 compute);
    ``bias``: [4H], added in-kernel in f32; ``rw``: [H, 4H]; ``peep``:
    (pi, pf, po) tuple or None; ``h0``/``c0``: [b, H]; ``mask``: [T, b]
    (1 = real step, values in [0, 1]) or None. The mask is
    NON-differentiable (the custom_vjp returns a zero cotangent for it);
    callers differentiating through a soft mask must stop_gradient it on
    their fallback path too (recurrent.py does).

    Returns (ys [T, b, H] in ``out_dtype`` — the layer's activation dtype;
    ``xw.dtype`` when None — and (hT, cT) in f32) — a drop-in for the
    ``lax.scan`` recurrent loop with the weight stream eliminated. Under AD
    ``xw``'s cotangent dz comes back in ``xw.dtype``, ``bias``'s is summed
    in-kernel from the f32 dz. No stream dtype is a setting: ``xw``, ``ys``,
    ``dy`` and ``dz`` follow the operands; only the reserve (gates, cseq)
    follows ``DL4J_TPU_LSTM_STREAM_DTYPE``."""
    T, b, _ = xw.shape
    pk = None if peep is None else _pad8(*peep)
    mk = None
    if mask is not None:
        mk = jnp.broadcast_to(jnp.asarray(mask, jnp.float32)[..., None],
                              (T, b, 8))
    # RW rides in its caller dtype (bf16 under the mixed-precision policy)
    # so the recurrent gemm runs the MXU's native bf16 pass with f32
    # accumulation; h/c state and the gate math are f32 in-kernel whatever
    # the streams' dtypes
    f32 = jnp.float32
    ys, hT, cT = _lstm(jnp.dtype(out_dtype or xw.dtype), xw, bias.astype(f32),
                       rw, pk, h0.astype(f32), c0.astype(f32), mk)
    return ys, (hT, cT)
