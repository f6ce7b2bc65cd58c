"""What the reference check holds on the device, and that holding less
changed none of its numbers: the timed network is gone before the check's
own is built, the check's keeps its parameters only, one gradient tree is
alive at a time, and loss and gradient distance are the parent's. CPU
rehearsals; the bytes are counted here over ``jax.live_arrays()``, apart from
the check's own count."""
import gc
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_benchmark_harness import _own_registry  # noqa: F401 - autouse
from test_benchmark_harness import _PlainTokenLM

from benchmark import cells, correct, device, run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FIXTURES = os.path.join(HERE, "fixtures")
OURO = "ouro_l4_ut4_b2_t4096_resident"


class _LossOnly:
    """A reference that states no tolerance for gradients."""
    TOLERANCE = {"float32": {"loss": 1e-4, "grads": None}}
    loss = _PlainTokenLM.loss


def _plant(monkeypatch, reference, sample):
    """The cell's files as they are, plus a reference and its sample: the
    fixture token LM names none."""
    load, module = cells.load_cell, cells.module

    def load_cell(*args, **kwargs):
        cell = load(*args, **kwargs)
        cell.config.update(reference="planted", correct_sample=sample)
        return cell

    monkeypatch.setattr(cells, "load_cell", load_cell)
    monkeypatch.setattr(
        cells, "module", lambda kind, name: reference
        if (kind, name) == ("reference", "planted") else module(kind, name))


class _Watch:
    """Hooks around the build and the check that note, at each point, the
    bytes of every array that came alive since the test began."""

    def __init__(self, monkeypatch):
        gc.collect()             # other tests' leavings go now or stay
        self.before = {id(a) for a in jax.live_arrays()}
        self.nets, self.seen, self.timed_deleted = [], [], None
        build, check, distance = (cells.build_net, correct.against_reference,
                                  correct.grad_distance)

        def build_net(cell, seed):
            if self.nets:            # the check's build: the timed net's fate
                timed = self.nets[0]
                self.timed_deleted = [leaf.is_deleted() for leaf in
                                      jax.tree_util.tree_leaves(
                                          (timed.params, timed.states,
                                           timed.updater_state))]
                self.note("build starts")
            net = build(cell, seed)
            if self.nets:
                self.note("built")
                grads_and_score = net.compute_gradient_and_score

                def watched(sample):
                    out = grads_and_score(sample)
                    jax.block_until_ready(out[0])
                    self.note("system's gradients")
                    return out
                net.compute_gradient_and_score = watched
            self.nets.append(net)
            return net

        def against_reference(net, *args):
            self.note("check starts")
            out = check(net, *args)
            self.note("check ends")
            return out

        def grad_distance(grads, ref_grads):
            self.note("reference's gradients")
            return distance(grads, ref_grads)

        monkeypatch.setattr(cells, "build_net", build_net)
        monkeypatch.setattr(correct, "against_reference", against_reference)
        monkeypatch.setattr(correct, "grad_distance", grad_distance)

    def note(self, where):
        self.seen.append((where, sum(a.nbytes for a in jax.live_arrays()
                                     if id(a) not in self.before)))


@pytest.mark.parametrize("workload", ["token_lm_resident", OURO])
def test_the_check_holds_one_network_and_one_gradient_tree(
        workload, monkeypatch, tmp_path):
    """(a), (b): one whole rehearsal. When the check's build starts the
    timed network's arrays are deleted and next to nothing is alive; built,
    the check's network is its parameters and the Adam moments ``init()``
    makes (12 bytes a parameter, the one point above 8); from the check's
    first sample on it is parameters plus at most one gradient tree."""
    root = FIXTURES if workload == "token_lm_resident" else ROOT
    manifest = cells.load_manifest(root)
    if workload == "token_lm_resident":
        _plant(monkeypatch, _PlainTokenLM, {"examples": 4, "seq_len": 12})
    spec = cells.load_cell(manifest, root, workload,
                           rehearse=True).config["correct_sample"]
    watch = _Watch(monkeypatch)
    notes = []
    result = run.run_cell(manifest, root, workload, seed=2**31 + 5,
                          seconds=0.2, trace=False, rehearse=True,
                          note=notes.append, trace_root=str(tmp_path))
    assert result["correct"] is True, notes
    timed, fresh = watch.nets
    assert watch.timed_deleted and all(watch.timed_deleted)
    assert all(x.is_deleted() for x in
               jax.tree_util.tree_leaves(fresh.updater_state))
    params = fresh.num_params()
    # the allowance: the sample's ids and labels (int32), twice over, and
    # 4 KiB for losses, keys and counters
    room = 2 * 2 * 4 * spec["examples"] * spec["seq_len"] + 4096
    seen = dict(watch.seen)
    assert [w for w, _ in watch.seen] == [
        "build starts", "built", "check starts", "system's gradients",
        "reference's gradients", "check ends"]
    assert seen["build starts"] <= room               # no second network
    assert 12 * params <= seen["built"] <= 12 * params + room
    assert 8 * params <= seen["system's gradients"] <= 8 * params + room
    assert 8 * params <= seen["reference's gradients"] <= 8 * params + room
    assert seen["check ends"] <= 4 * params + room
    # and the check's own line says the same of itself (its count is of the
    # whole process, other tests' leavings included: differences are exact)
    line = next(n for n in notes if n.startswith("check reference: ok"))
    held = {k: int(v) for k, v in re.findall(
        r"(built|updater state deleted|the system's gradients alive|"
        r"the reference's gradients alive) (\d+)", line)}
    floor = held["updater state deleted"]
    assert floor >= 4 * params and held["built"] - floor == 8 * params
    for point in ("the system's gradients alive",
                  "the reference's gradients alive"):
        assert 4 * params <= held[point] - floor <= 4 * params + room
    assert f"held at most {held['built']} bytes on the device, " in line


def _parents_against_reference(net, reference, sample, compute_dtype):
    """``correct.against_reference`` as the parent commit (b5e304b) had it,
    word for word: both gradient trees and their difference on the device."""
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
        return ok, detail + "; gradients not comparable (see the reference)", \
            None

    def sq(t):
        return sum(float(jnp.sum(jnp.square(x.astype(jnp.float32))))
                   for x in jax.tree_util.tree_leaves(t))

    diff = jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b, grads, out[1])
    grad_err = (sq(diff) / sq(out[1])) ** 0.5
    return ok and grad_err <= tol["grads"], (
        f"{detail}; gradients rel L2 {grad_err:.2e} "
        f"(allowed {tol['grads']:.0e})"), (grads, out[1], grad_err)


def _token_lm(seed=5):
    cell = cells.load_cell(cells.load_manifest(FIXTURES), FIXTURES,
                           "token_lm_resident")
    sample, = cells.make_batches(cell.config, seed + 1, 1, 4, 12)
    return cells.build_net(cell, seed), sample


@pytest.mark.parametrize("reference", [_PlainTokenLM, _LossOnly])
def test_leaf_by_leaf_says_what_the_parents_formula_said(reference):
    """(c): the same verdict and the same printed loss, reference loss and
    distances on the same network and sample, with and without gradients;
    the distance itself to 1e-6 of the parent's."""
    net, sample = _token_lm()
    ok, detail, trees = _parents_against_reference(net, reference, sample,
                                                   "float32")
    new_ok, new_detail = correct.against_reference(net, reference, sample,
                                                   "float32")
    assert new_ok is ok is True
    assert new_detail.startswith(detail), (detail, new_detail)
    if trees is None:
        assert "worst leaf" not in new_detail
        return
    grads, ref_grads, grad_err = trees
    distance, (worst, path), own = correct.grad_distance(
        jax.tree_util.tree_map(np.asarray, grads), ref_grads)
    assert distance == pytest.approx(grad_err, rel=1e-6)
    assert 0 < worst < 1e-4 and path in new_detail
    assert list(own) == [jax.tree_util.keystr(p) for p, _ in
                         jax.tree_util.tree_flatten_with_path(ref_grads)[0]]
    assert worst <= max(own.values()) < 1e-4


def test_the_worst_leaf_named_is_the_one_perturbed():
    """(e): one leaf of the system's gradients moved by a tenth of its own
    size is named, with that distance; a leaf whose reference gradient is
    nought is measured against the median leaf's norm and named only if
    its error is large on that scale."""
    net, sample = _token_lm()
    ref_grads = jax.grad(_PlainTokenLM.loss)(
        net.params, jnp.asarray(sample.features), jnp.asarray(sample.labels))
    sound = jax.tree_util.tree_map(np.asarray, ref_grads)
    distance, (worst, _), own = correct.grad_distance(sound, ref_grads)
    assert distance == worst == 0.0 and not any(own.values())
    moved = dict(sound, **{"b1-ffn": dict(sound["b1-ffn"],
                                          W=sound["b1-ffn"]["W"] * 1.1)})
    distance, (worst, path), own = correct.grad_distance(moved, ref_grads)
    assert path == "['b1-ffn']['W']" and worst == pytest.approx(0.1, rel=1e-4)
    assert own.pop(path) == pytest.approx(0.1, rel=1e-4)
    assert not any(own.values())
    assert 0 < distance < worst            # the whole tree dilutes one leaf
    # a leaf that is nought in the reference: round-off there is no error...
    bias = sound["b0-attn"]["b"]
    nought = dict(ref_grads, **{"b0-attn": dict(ref_grads["b0-attn"],
                                                b=jnp.zeros_like(bias))})

    def with_bias(b):
        return dict(sound, **{"b0-attn": dict(sound["b0-attn"], b=b)})

    _, (worst, path), _ = correct.grad_distance(
        with_bias(np.full_like(bias, 1e-12)), nought)
    assert worst < 1e-6
    # ...and a real gradient where the reference has none is
    _, (worst, path), _ = correct.grad_distance(with_bias(bias + 1.0), nought)
    assert path == "['b0-attn']['b']" and worst > 0.1
    with pytest.raises(ValueError):        # another tree is not compared
        correct.grad_distance({"embed": sound["embed"]}, ref_grads)


def test_a_reference_may_hold_single_leaves_to_limits_of_their_own():
    """``TOLERANCE[dtype]["leaves"]``: each named leaf's own relative L2
    stands beside its limit in the line and decides with the others; a name
    that no gradient has ends the run."""
    def held_to(leaves):
        class Reference:
            TOLERANCE = {"float32": {"loss": 1e-4, "grads": 1e-4,
                                     "leaves": leaves}}
            loss = _PlainTokenLM.loss
        net, sample = _token_lm()
        return correct.against_reference(net, Reference, sample, "float32")

    ok, detail = held_to({"['out']['W']": 1e-4, "['embed']['W']": 1e-4})
    assert ok, detail
    found = re.findall(r"leaf (\S+) rel L2 (\S+) \(allowed 1e-04\)", detail)
    assert [path for path, _ in found] == ["['out']['W']", "['embed']['W']"]
    assert all(0 < float(value) < 1e-4 for _, value in found)
    ok, detail = held_to({"['out']['W']": 1e-9})     # rounding alone is more
    assert not ok and "(allowed 1e-09)" in detail
    with pytest.raises(SystemExit, match="holds leaf"):
        held_to({"['out']['V']": 1e-4})


def test_bytes_in_use_counts_each_devices_share_and_keeps_nothing_alive():
    """Off the chip ``device.bytes_in_use`` adds up ``jax.live_arrays()`` by
    shape: the fullest device's share of sharded and replicated arrays, and
    no array of its own making is left behind (a shard's ``.data`` would
    be one, and would keep a deleted array's buffer alive)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    devices = jax.devices()[:4]
    if len(devices) < 4:
        pytest.skip("needs four (virtual) devices")
    gc.collect()                 # other tests' leavings go now or stay
    base = [device.bytes_in_use([d]) for d in devices]
    mesh = Mesh(np.asarray(devices), ("d",))
    split = jax.device_put(np.zeros((8, 1024), np.float32),
                           NamedSharding(mesh, P("d")))
    whole = jax.device_put(np.zeros((1024,), np.float32),
                           NamedSharding(mesh, P()))
    alone = jax.device_put(np.zeros((512,), np.float32), devices[2])
    jax.block_until_ready((split, whole, alone))
    held = [device.bytes_in_use([d]) - b for d, b in zip(devices, base)]
    assert held == [8192 + 4096, 8192 + 4096, 8192 + 4096 + 2048,
                    8192 + 4096]
    assert device.bytes_in_use(devices) >= base[2] + held[2]   # the fullest
    count = len(jax.live_arrays())
    device.bytes_in_use(devices)
    assert len(jax.live_arrays()) == count
    device.delete({"a": (split, None), "b": [whole, alone, 3.0]})
    assert split.is_deleted() and whole.is_deleted() and alone.is_deleted()
    assert [device.bytes_in_use([d]) for d in devices] == base
    device.delete([split])                      # twice is no error
