"""The comparison that decides ``correct``. Each check returns
``(ok, detail)``; the detail goes on a line of its own before the result."""
from __future__ import annotations

import numpy as np

#: a data-parallel run against the one-device run of the same steps, per
#: step: the same bf16 arithmetic compiled as another program (other
#: fusions, other orders of summation) on a loss that falls from ~8 towards
#: 0 within eight steps. Copied from ``chip_smoke.TRAJECTORY_TOL``.
TRAJECTORY_TOL = {"rtol": 0.1, "atol": 0.1}


def state(net, devices, platform, sharded):
    """Loss and parameters finite, state on ``platform``, and for a
    data-parallel run every parameter and updater leaf on all ``devices``."""
    import jax
    import jax.numpy as jnp

    leaves = jax.tree_util.tree_leaves((net.params, net.updater_state))
    finite = bool(jax.jit(lambda t: jnp.all(jnp.stack(
        [jnp.all(jnp.isfinite(x)) for x in jax.tree_util.tree_leaves(t)])))(
            (net.params, net.score_)))
    placed = all(d.platform == platform for leaf in leaves
                 for d in leaf.devices())
    spread = (not sharded) or all(leaf.sharding.device_set == set(devices)
                                  for leaf in leaves)
    detail = (f"finite={finite} on_{platform}={placed}"
              + (f" on_all_{len(devices)}_devices={spread}" if sharded else ""))
    return finite and placed and spread, detail


def against_reference(net, reference, sample, compute_dtype):
    """Loss and gradients of the system (``compute_gradient_and_score``, on
    the network's own seeded weights) against the plain reference, within
    the reference's stated tolerance for ``compute_dtype``. Where the
    reference states no tolerance for gradients (``None``, with its reason),
    the loss alone is held to it, through ``score(sample, training=True)``."""
    import jax
    import jax.numpy as jnp

    tol = reference.TOLERANCE[str(compute_dtype)]
    with_grads = tol["grads"] is not None
    if with_grads:
        grads, loss = net.compute_gradient_and_score(sample)
    else:
        loss = net.score(sample, training=True)
    with jax.default_matmul_precision("highest"):
        fn = jax.value_and_grad(reference.loss) if with_grads \
            else reference.loss
        out = jax.jit(fn)(net.params, jnp.asarray(sample.features),
                          jnp.asarray(sample.labels))
    ref_loss = float(out[0] if with_grads else out)
    loss_err = abs(loss - ref_loss) / abs(ref_loss)
    ok = bool(np.isfinite(loss) and loss_err <= tol["loss"])
    detail = (f"loss {loss:.6f} vs reference {ref_loss:.6f} "
              f"(rel {loss_err:.2e}, allowed {tol['loss']:.0e})")
    if not with_grads:
        return ok, detail + "; gradients not comparable (see the reference)"

    def sq(t):
        return sum(float(jnp.sum(jnp.square(x.astype(jnp.float32))))
                   for x in jax.tree_util.tree_leaves(t))

    diff = jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b, grads, out[1])
    grad_err = (sq(diff) / sq(out[1])) ** 0.5
    return ok and grad_err <= tol["grads"], (
        f"{detail}; gradients rel L2 {grad_err:.2e} "
        f"(allowed {tol['grads']:.0e})")


def holds_collective(texts):
    """Whether a compiled program of this process holds an all-reduce."""
    n = max((t.count("all-reduce") for t in texts), default=0)
    return n > 0, f"{n} all-reduce in the largest compiled step"


def trajectory(losses, one_device_losses):
    ok = (len(losses) == len(one_device_losses) and
          bool(np.allclose(losses, one_device_losses, **TRAJECTORY_TOL)))
    worst = (float(np.max(np.abs(np.asarray(losses)
                                 - np.asarray(one_device_losses))))
             if len(losses) == len(one_device_losses) else float("nan"))
    return ok, (f"losses {[round(v, 4) for v in losses]} vs one device "
                f"{[round(v, 4) for v in one_device_losses]}, "
                f"max |diff| {worst:.4f}")
