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
    the loss alone is held to it, through ``score(sample, training=True)``.

    ``net`` is the check's own and is held at its parameters: its updater
    state, which neither side reads, is deleted first, and one gradient
    tree is on the device at a time (the system's goes to the host as
    float32 before the reference runs). The detail ends with the most live
    device bytes seen at the points between the computations."""
    import jax
    import jax.numpy as jnp

    from benchmark import device

    devices = list(jax.tree_util.tree_leaves(net.params)[0].devices())
    # init() sends Adam's zeros from the host: a buffer still on its way
    # is freed only once it has landed, so wait before counting
    jax.block_until_ready((net.params, net.updater_state))
    held = [("built", device.bytes_in_use(devices))]
    device.delete(net.updater_state)
    held.append(("updater state deleted", device.bytes_in_use(devices)))

    tol = reference.TOLERANCE[str(compute_dtype)]
    with_grads = tol["grads"] is not None
    if with_grads:
        grads, loss = net.compute_gradient_and_score(sample)
        jax.block_until_ready(grads)
        held.append(("the system's gradients alive",
                     device.bytes_in_use(devices)))
        on_host = jax.tree_util.tree_map(
            lambda g: np.asarray(g, dtype=np.float32), grads)
        device.delete(grads)
    else:
        loss = net.score(sample, training=True)
    with jax.default_matmul_precision("highest"):
        fn = jax.value_and_grad(reference.loss) if with_grads \
            else reference.loss
        out = jax.block_until_ready(jax.jit(fn)(
            net.params, jnp.asarray(sample.features),
            jnp.asarray(sample.labels)))
    ref_loss = float(out[0] if with_grads else out)
    loss_err = abs(loss - ref_loss) / abs(ref_loss)
    ok = bool(np.isfinite(loss) and loss_err <= tol["loss"])
    detail = (f"loss {loss:.6f} vs reference {ref_loss:.6f} "
              f"(rel {loss_err:.2e}, allowed {tol['loss']:.0e})")
    if with_grads:
        held.append(("the reference's gradients alive",
                     device.bytes_in_use(devices)))
        grad_err, worst, own = grad_distance(on_host, out[1])
        ok = ok and grad_err <= tol["grads"]
        detail += (f"; gradients rel L2 {grad_err:.2e} "
                   f"(allowed {tol['grads']:.0e})")
        for path, limit in tol.get("leaves", {}).items():
            if path not in own:
                raise SystemExit(f"the reference holds leaf {path} to "
                                 f"{limit}; the gradients have {list(own)}")
            ok = ok and own[path] <= limit
            detail += (f"; leaf {path} rel L2 {own[path]:.2e} "
                       f"(allowed {limit:.0e})")
        detail += (f"; worst leaf {worst[1]} rel L2 {worst[0]:.2e} (against "
                   f"its own or the median leaf's norm; not held)")
    else:
        detail += "; gradients not comparable (see the reference)"
    return bool(ok), detail + "; " + _held(held, net.num_params())


def grad_distance(grads, ref_grads):
    """Relative L2 of all gradients, ``|g - r| / |r|`` over the whole tree,
    summed leaf by leaf on the host: one reference leaf is fetched at a
    time and no tree of differences is built. ``grads``: the system's
    gradients as host arrays, a tree of the reference's structure. Also the
    worst leaf, ``(distance, path)``: its ``|g - r|`` against its ``|r|``
    or the median leaf's, whichever is larger, since a gradient that is
    nought to rounding in the reference has no relative error; and every
    leaf's own ``|g - r| / |r|`` by its path (a reference's ``TOLERANCE``
    may hold some to limits of their own under ``leaves``)."""
    import jax

    paths, diff, norm = [], [], []

    def leaf(path, r, g):
        r = np.asarray(r, dtype=np.float32)
        paths.append(jax.tree_util.keystr(path))
        diff.append(float(np.sum(np.square(g - r), dtype=np.float64)))
        norm.append(float(np.sum(np.square(r), dtype=np.float64)))

    jax.tree_util.tree_map_with_path(leaf, ref_grads, grads)
    diff, norm = np.asarray(diff), np.asarray(norm)
    against = np.maximum(norm, np.median(norm))
    each = np.sqrt(diff / np.where(against > 0, against, 1.0))
    own = np.sqrt(diff / np.where(norm > 0, norm, 1.0))
    worst = int(np.argmax(each))
    return (float((diff.sum() / norm.sum()) ** 0.5),
            (float(each[worst]), paths[worst]),
            dict(zip(paths, own.tolist())))


def _held(held, params):
    most = max(b for _, b in held)
    return (f"held at most {most} bytes on the device, "
            f"{most / params:.2f} a parameter ("
            + ", ".join(f"{where} {b}" for where, b in held) + ")")


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
