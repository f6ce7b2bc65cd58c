"""Numerical gradient checking — the test backbone (SURVEY.md §4 item 1).

TPU-native equivalent of reference ``gradientcheck/GradientCheckUtil.java``
(:112 MLN entry, :268 CG variant): central-difference
``(f(x+eps) - f(x-eps)) / 2eps`` per parameter element vs the analytic gradient.

The reference hard-requires double precision (:122-127); TPU f64 is impractical,
so the rule maps to: run checks on the CPU backend under x64 (conftest pins
JAX_PLATFORMS=cpu; wrap network construction AND the check in
:func:`double_precision`, and build the net with ``dtype="float64"``,
``compute_dtype="float64"``). The reference's "SGD lr=1.0" requirement (:135-142)
does not apply — we differentiate the loss directly rather than inferring the
gradient from a parameter step.
"""
from __future__ import annotations

import contextlib
import logging
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from jax import enable_x64 as _enable_x64
from ..monitor.jitwatch import monitored_jit

log = logging.getLogger(__name__)


@contextlib.contextmanager
def double_precision():
    """Enable f64 for network construction + checking (reference double rule)."""
    with _enable_x64(True):
        yield


def _loss_at(net, params, ds):
    """Full training loss (incl. regularization) at ``params`` for either
    container type; train=True but rng=None so dropout/noise are inactive —
    gradient checks require deterministic nets, as in the reference."""
    from .multilayer import MultiLayerNetwork
    if isinstance(net, MultiLayerNetwork):
        f = net._adapt_input(jnp.asarray(ds.features))
        l = jnp.asarray(ds.labels)
        fm = None if ds.features_mask is None else jnp.asarray(ds.features_mask)
        lm = None if ds.labels_mask is None else jnp.asarray(ds.labels_mask)
        loss, _ = net._loss_fn(params, net.states, f, l, fm, lm, True, None)
        return loss
    mds = net._as_multi(ds)
    inputs = net._adapt_inputs([jnp.asarray(x) for x in mds.features])
    labels = [jnp.asarray(x) for x in mds.labels]
    fms = (None if mds.features_masks is None
           else [None if m is None else jnp.asarray(m) for m in mds.features_masks])
    lms = (None if mds.labels_masks is None
           else [None if m is None else jnp.asarray(m) for m in mds.labels_masks])
    loss, _ = net._loss_fn(params, net.states, inputs, labels, fms, lms, True, None)
    return loss


def check_function_gradients(loss_fn, params, epsilon: float = 1e-6,
                             max_rel_error: float = 1e-3,
                             min_abs_error: float = 1e-8,
                             max_per_param: Optional[int] = None,
                             seed: int = 12345,
                             expect_zero: Optional[set] = None) -> bool:
    """Central-difference check of an arbitrary scalar ``loss_fn(params)``
    against its AD gradient — used for pretrain losses (VAE/AutoEncoder,
    reference ``VaeGradientCheckTests``) and any custom objective.

    ``expect_zero``: leaf-path substrings whose analytic gradient must be
    exactly zero (frozen layers) — those leaves skip the numeric comparison
    and instead assert the zero."""
    loss_fn = monitored_jit(loss_fn, name="gradientcheck/loss")
    analytic = jax.grad(loss_fn)(params)
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    analytic_map = {_key_str(kp): np.asarray(v) for kp, v in
                    jax.tree_util.tree_flatten_with_path(analytic)[0]}
    rng = np.random.default_rng(seed)
    failed = 0
    for keypath, leaf in leaves:
        name = _key_str(keypath)
        grad = analytic_map[name]
        if expect_zero and any(z in name for z in expect_zero):
            if float(np.abs(grad).max(initial=0.0)) != 0.0:
                log.warning("Expected zero gradient for %s, got max %g", name,
                            np.abs(grad).max())
                failed += 1
            continue
        base = np.asarray(leaf, dtype=np.float64)
        flat_idx = np.arange(base.size)
        if max_per_param is not None and base.size > max_per_param:
            flat_idx = rng.choice(base.size, size=max_per_param, replace=False)
        for i in flat_idx:
            plus = base.copy().ravel()
            plus[i] += epsilon
            minus = base.copy().ravel()
            minus[i] -= epsilon
            p_plus = _with_leaf(params, keypath, plus.reshape(base.shape))
            p_minus = _with_leaf(params, keypath, minus.reshape(base.shape))
            num = (float(loss_fn(p_plus)) - float(loss_fn(p_minus))) / (2 * epsilon)
            ana = float(grad.ravel()[i])
            denom = max(abs(num), abs(ana))
            rel = 0.0 if denom == 0 else abs(num - ana) / denom
            if not (rel <= max_rel_error or (abs(num) < min_abs_error
                                             and abs(ana) < min_abs_error)):
                log.warning("Gradient check FAILED %s[%d]: numeric=%.8e "
                            "analytic=%.8e relError=%.4e", name, i, num, ana,
                            rel)
                failed += 1
    return failed == 0


class GradientCheckUtil:
    @staticmethod
    def check_gradients(net, ds, epsilon: float = 1e-6,
                        max_rel_error: float = 1e-3,
                        min_abs_error: float = 1e-8,
                        print_results: bool = False,
                        exit_on_first_error: bool = False,
                        max_per_param: Optional[int] = None,
                        seed: int = 12345,
                        exclude: Optional[set] = None) -> bool:
        """Return True when every checked element's analytic gradient matches the
        central difference within ``max_rel_error`` (elements where both are
        below ``min_abs_error`` pass unconditionally, reference semantics).
        ``max_per_param`` subsamples elements per parameter tensor for large nets.
        """
        leaves = jax.tree_util.tree_flatten_with_path(net.params)[0]
        dtypes = {np.asarray(v).dtype for _, v in leaves}
        if any(d != np.float64 for d in dtypes):
            raise ValueError(
                f"Gradient checks require float64 params (got {dtypes}); build "
                f"the net with dtype='float64', compute_dtype='float64' inside "
                f"gradientcheck.double_precision() (reference "
                f"GradientCheckUtil.java:122-127 double-precision rule)")

        loss_fn = monitored_jit(lambda p: _loss_at(net, p, ds),
                                name="gradientcheck/loss_at")
        analytic = jax.grad(loss_fn)(net.params)
        analytic_leaves = {}
        for keypath, leaf in jax.tree_util.tree_flatten_with_path(analytic)[0]:
            analytic_leaves[_key_str(keypath)] = np.asarray(leaf)

        rng = np.random.default_rng(seed)
        total_checked = 0
        total_failed = 0
        max_err_seen = 0.0
        for keypath, leaf in leaves:
            name = _key_str(keypath)
            if exclude and any(x in name for x in exclude):
                continue  # e.g. frozen layers (AD-zero but numerically active)
            base = np.asarray(leaf, dtype=np.float64)
            grad = analytic_leaves[name]
            flat_idx = np.arange(base.size)
            if max_per_param is not None and base.size > max_per_param:
                flat_idx = rng.choice(base.size, size=max_per_param, replace=False)
            for i in flat_idx:
                plus = base.copy().ravel()
                plus[i] += epsilon
                minus = base.copy().ravel()
                minus[i] -= epsilon
                p_plus = _with_leaf(net.params, keypath, plus.reshape(base.shape))
                p_minus = _with_leaf(net.params, keypath, minus.reshape(base.shape))
                num = (float(loss_fn(p_plus)) - float(loss_fn(p_minus))) / (2 * epsilon)
                ana = float(grad.ravel()[i])
                denom = max(abs(num), abs(ana))
                rel = 0.0 if denom == 0 else abs(num - ana) / denom
                ok = rel <= max_rel_error or (abs(num) < min_abs_error
                                              and abs(ana) < min_abs_error)
                total_checked += 1
                max_err_seen = max(max_err_seen, rel)
                if not ok:
                    total_failed += 1
                    msg = (f"Gradient check FAILED {name}[{i}]: numeric={num:.8e} "
                           f"analytic={ana:.8e} relError={rel:.4e}")
                    if print_results:
                        log.warning(msg)
                    if exit_on_first_error:
                        raise AssertionError(msg)
        if print_results:
            log.info("Gradient check: %d/%d passed (max relError %.3e)",
                     total_checked - total_failed, total_checked, max_err_seen)
        return total_failed == 0

    checkGradients = check_gradients


def _key_str(keypath):
    parts = []
    for k in keypath:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def _with_leaf(tree, keypath, value):
    """Copy of ``tree`` with the leaf at ``keypath`` replaced by ``value``."""
    target = _key_str(keypath)

    def repl(kp, leaf):
        return jnp.asarray(value) if _key_str(kp) == target else leaf

    return jax.tree_util.tree_map_with_path(repl, tree)


check_gradients = GradientCheckUtil.check_gradients
