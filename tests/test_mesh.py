"""Unified 2-D mesh substrate (parallel/mesh.py — docs/PARALLELISM.md
"Unified mesh substrate"): MeshSpec auto-factorization + validation, the
composed DP×TP step, ZeRO riding the data axis of any mesh (pinned
bit-exact vs replicated), the closed jit-signature set, and the /profile
mesh block. Runs on the conftest 8-device virtual CPU mesh."""
import numpy as np
import pytest
import jax

from deeplearning4j_tpu import (NeuralNetConfiguration, MultiLayerNetwork,
                                Adam, DataSet, ListDataSetIterator)
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.parallel import (ParallelWrapper, TrainingMode,
                                         MeshSpec, make_mesh, mesh_block,
                                         require_axes, zero_update_specs,
                                         tensor_parallel_step,
                                         DATA_AXIS, MODEL_AXIS)
from deeplearning4j_tpu.parallel.mesh import auto_factor, reset_mesh_registry


def _adam_net(seed=7):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).updater(Adam(learning_rate=1e-2)).activation("tanh")
            .list()
            .layer(DenseLayer(n_in=6, n_out=16))
            .layer(DenseLayer(n_in=16, n_out=16))
            .layer(OutputLayer(n_in=16, n_out=4, activation="softmax",
                               loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _batches(n=4, size=32, seed=0):
    rng = np.random.default_rng(seed)
    return [DataSet(rng.normal(size=(size, 6)).astype(np.float32),
                    np.eye(4, dtype=np.float32)[rng.integers(0, 4, size)])
            for _ in range(n)]


def _fit(net, epochs=3, batches=None, **builder_kw):
    b = ParallelWrapper.Builder(net)
    for name, val in builder_kw.items():
        b = getattr(b, name)(*val) if isinstance(val, tuple) \
            else getattr(b, name)(val)
    b.build().fit(ListDataSetIterator(batches or _batches()), epochs=epochs)
    return net


def _assert_params(a, b, bitexact=True, atol=5e-7):
    """Param comparison helper: ``bitexact=True`` pins byte equality;
    otherwise float32-resolution closeness (the TP rules genuinely
    reassociate one contraction's partial sums — see the composed test)."""
    for k in a.params:
        for p in a.params[k]:
            x = np.asarray(a.params[k][p])
            y = np.asarray(b.params[k][p])
            if bitexact:
                np.testing.assert_array_equal(
                    x, y, err_msg=f"param {k}/{p} not bit-identical")
            else:
                np.testing.assert_allclose(x, y, rtol=1e-6, atol=atol,
                                           err_msg=f"param {k}/{p}")


# ----------------------------------------------------------- MeshSpec
def test_auto_factor_balances_extents_deterministically():
    assert auto_factor(8, 1) == (8,)
    assert auto_factor(8, 2) == (4, 2)
    assert auto_factor(8, 3) == (2, 2, 2)
    assert auto_factor(12, 2) == (4, 3)
    assert auto_factor(1, 2) == (1, 1)


def test_meshspec_auto_factorizes_and_respects_fixed_extents():
    # the old degenerate default piled all 8 devices on the first axis
    spec = MeshSpec(axes=(DATA_AXIS, MODEL_AXIS))
    assert spec.resolve_shape(8) == (4, 2)
    m = spec.build()
    assert dict(m.shape) == {"data": 4, "model": 2}
    # a fixed model extent leaves the data extent to auto-factorize
    spec = MeshSpec(axes=(DATA_AXIS, MODEL_AXIS), shape=(None, 2))
    assert spec.resolve_shape(8) == (4, 2)
    # -1 is the same auto spelling
    spec = MeshSpec(axes=(DATA_AXIS, MODEL_AXIS), shape=(-1, 4))
    assert spec.resolve_shape(8) == (2, 4)


def test_meshspec_validation_is_loud_and_actionable():
    with pytest.raises(ValueError, match="duplicate"):
        MeshSpec(axes=(DATA_AXIS, DATA_AXIS))
    with pytest.raises(ValueError, match="at least one axis"):
        MeshSpec(axes=())
    with pytest.raises(ValueError, match="non-positive"):
        MeshSpec(axes=(DATA_AXIS,), shape=(0,))
    with pytest.raises(ValueError, match="2 extents for 1 axes"):
        MeshSpec(axes=(DATA_AXIS,), shape=(4, 2))
    # fixed extents that don't divide the device count name the numbers
    with pytest.raises(ValueError, match="multiple of 3.*8"):
        MeshSpec(axes=(DATA_AXIS, MODEL_AXIS), shape=(None, 3)).build()
    # fully-fixed shapes that under-cover tell the operator what to do
    with pytest.raises(ValueError, match="covers 4.*8 are available"):
        MeshSpec(axes=(DATA_AXIS, MODEL_AXIS), shape=(2, 2)).build()


def test_make_mesh_routes_through_meshspec():
    # multi-axis default auto-factorizes instead of the degenerate [n, 1]
    m = make_mesh(axes=(DATA_AXIS, MODEL_AXIS))
    assert dict(m.shape) == {"data": 4, "model": 2}
    # explicit shapes are preserved; single-axis default takes everything
    m = make_mesh(axes=(DATA_AXIS, MODEL_AXIS), shape=(2, 4))
    assert dict(m.shape) == {"data": 2, "model": 4}
    assert dict(make_mesh().shape) == {"data": 8}
    with pytest.raises(ValueError):
        make_mesh(axes=(DATA_AXIS,), shape=(3,))


def test_require_axes_names_the_missing_axis_and_the_fix():
    m = make_mesh(axes=(DATA_AXIS,))
    with pytest.raises(ValueError, match="model.*MeshSpec"):
        require_axes(m, (MODEL_AXIS,), style="composed step")
    assert require_axes(m, (DATA_AXIS, None)) is m   # None entries skipped


def test_zero_update_specs_compose_with_base_tp_specs():
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_mesh(axes=(DATA_AXIS, MODEL_AXIS), shape=(4, 2))
    tree = {"w1": np.zeros((16, 16)), "w0": np.zeros((6, 16)),
            "b": np.zeros((3,))}
    base = {"w1": NamedSharding(mesh, P(None, MODEL_AXIS)),
            "w0": NamedSharding(mesh, P(None, MODEL_AXIS)),
            "b": NamedSharding(mesh, P())}
    specs = zero_update_specs(tree, mesh, DATA_AXIS, base=base)
    # the data axis takes the largest dim TP left free
    assert specs["w1"].spec == P(DATA_AXIS, MODEL_AXIS)
    # 6 is not divisible by data=4: the base TP sharding is kept as-is
    assert specs["w0"].spec == P(None, MODEL_AXIS)
    # no divisible free dim at all: replicated base stays replicated
    assert specs["b"].spec == P()
    # without a base, behavior matches the classic 1-D rule (later-dim tie)
    solo = zero_update_specs({"w1": np.zeros((16, 16))}, mesh, DATA_AXIS)
    assert solo["w1"].spec == P(None, DATA_AXIS)
    # a base rule that already claims the ZeRO axis keeps its spec as-is
    # instead of building an invalid duplicate-axis PartitionSpec
    # (review finding)
    dup = zero_update_specs(
        {"w": np.zeros((16, 16))}, mesh, DATA_AXIS,
        base={"w": NamedSharding(mesh, P(None, DATA_AXIS))})
    assert dup["w"].spec == P(None, DATA_AXIS)


# ------------------------------------------- composed 2-D fits (tentpole)
def test_2d_mesh_pure_dp_and_zero_bit_identical_to_1d_twin():
    """THE substrate acceptance: moving a DP fit onto a 2-D data × model
    mesh changes NOTHING — bit-identical params to the 1-D twin with the
    same data extent — and ZeRO (ws/fsdp) riding the data axis of that
    2-D mesh stays bit-identical too (arXiv:2004.13336: reduce-scatter
    grads, update the local shard, all-gather weights ≡ replicated DP),
    while params/optimizer state genuinely live 1/N per device."""
    twin = _fit(_adam_net(), workers=4)

    mesh2 = make_mesh(axes=(DATA_AXIS, MODEL_AXIS), shape=(4, 2))
    pure = _fit(_adam_net(), mesh=mesh2)
    _assert_params(twin, pure, bitexact=True)

    ws = _fit(_adam_net(), mesh=mesh2, weight_update_sharding=True)
    _assert_params(twin, ws, bitexact=True)
    upd_specs = {str(l.sharding.spec)
                 for l in jax.tree_util.tree_leaves(ws.updater_state)
                 if hasattr(l, "sharding")}
    assert any(DATA_AXIS in s for s in upd_specs), upd_specs

    f = _fit(_adam_net(), mesh=mesh2, fsdp=True)
    _assert_params(twin, f, bitexact=True)
    w1 = f.params["1"]["W"]
    assert DATA_AXIS in str(w1.sharding.spec)
    # storage genuinely sharded: 1/4 of the bytes per device (data extent)
    assert w1.addressable_shards[0].data.nbytes == w1.nbytes // 4


def test_composed_2d_dp_tp_fit_matches_1d_twin():
    """DP × TP composed in ONE jitted step: the wrapper drives the data
    axis while megatron rules shard the model axis. The model split
    reassociates one contraction's partial sums (row-parallel psum), so
    the pin vs the 1-D DP twin is float32-resolution closeness (observed
    ~6e-8 = 1 ulp); the DP half of the composition is pinned bitwise by
    test_2d_mesh_pure_dp_and_zero_bit_identical_to_1d_twin. The model
    axis sharding must be REAL: half the param bytes per device."""
    twin = _fit(_adam_net(), workers=4)
    comp = _adam_net()
    pw = (ParallelWrapper.Builder(comp).workers(8).tensor_parallel(2)
          .build())
    assert dict(pw.mesh.shape) == {"data": 4, "model": 2}
    assert pw.workers_ == 4            # the wrapper drives the DATA axis
    pw.fit(ListDataSetIterator(_batches()), epochs=3)
    _assert_params(twin, comp, bitexact=False)
    w0 = comp.params["0"]["W"]
    assert MODEL_AXIS in str(w0.sharding.spec)
    assert w0.addressable_shards[0].data.nbytes == w0.nbytes // 2
    # the net still scores transparently after the composed fit
    assert np.isfinite(comp.score(_batches()[0]))


def test_composed_zero_rides_data_axis_of_composed_mesh():
    """ws/fsdp on the composed DP×TP mesh: ZeRO takes the dims TP left
    free, over the data axis — optimizer state leaves carry BOTH axes —
    and the trajectory matches the composed plain fit at float32
    resolution (the TP reassociation is shared; the ZeRO resharding adds
    none of its own — see the bitwise 2-D pin above)."""
    plain = _fit(_adam_net(), tensor_parallel=2, workers=8)
    ws = _fit(_adam_net(), tensor_parallel=2, workers=8,
              weight_update_sharding=True)
    _assert_params(plain, ws, bitexact=False)
    upd_specs = {str(l.sharding.spec)
                 for l in jax.tree_util.tree_leaves(ws.updater_state)
                 if hasattr(l, "sharding")}
    assert any(DATA_AXIS in s and MODEL_AXIS in s for s in upd_specs), \
        upd_specs

    f = _fit(_adam_net(), tensor_parallel=2, workers=8, fsdp=True)
    _assert_params(plain, f, bitexact=False)
    w1 = f.params["1"]["W"]
    # [16,16] W: model splits one dim, data the other → 1/8 per device
    assert {DATA_AXIS, MODEL_AXIS} <= set(
        s for s in w1.sharding.spec if s)
    assert w1.addressable_shards[0].data.nbytes == w1.nbytes // 8


def _state_bytes_on_device0(net):
    """Parameter and updater bytes resident on device 0: a replicated leaf
    costs its full size there, a sharded one its shard."""
    dev0 = jax.devices()[0]
    total = 0
    for leaf in (jax.tree_util.tree_leaves(net.params)
                 + jax.tree_util.tree_leaves(net.updater_state)):
        total += sum(s.data.nbytes for s in leaf.addressable_shards
                     if s.device == dev0)
    return total


@pytest.mark.parametrize("mesh", ["1d", "2d"])
def test_zero_shards_the_optimizer_state_per_device(mesh):
    """ZeRO's memory claim as exact byte counts: the same Adam fit,
    replicated, with the optimizer state sharded (``ws``, ZeRO-1) and with
    the storage sharded (``fsdp``, ZeRO-3), over a 1-D data mesh and a 2-D
    data × model mesh. ZeRO-1 shards Adam's two moments, two thirds of the
    replicated state."""
    def net():
        return MultiLayerNetwork(
            NeuralNetConfiguration.builder().seed(7)
            .updater(Adam(learning_rate=1e-3)).activation("tanh").list()
            .layer(DenseLayer(n_in=64, n_out=128))
            .layer(DenseLayer(n_in=128, n_out=128))
            .layer(OutputLayer(n_in=128, n_out=8, activation="softmax",
                               loss="mcxent"))
            .build()).init()

    rng = np.random.default_rng(0)
    batches = [DataSet(rng.normal(size=(32, 64)).astype(np.float32),
                       np.eye(8, dtype=np.float32)[rng.integers(0, 8, 32)])
               for _ in range(4)]
    tp = {"tensor_parallel": 2} if mesh == "2d" else {}
    held = {style: _state_bytes_on_device0(
                _fit(net(), epochs=1, batches=batches, workers=8, **tp,
                     **zero))
            for style, zero in (("replicated", {}),
                                ("ws", {"weight_update_sharding": True}),
                                ("fsdp", {"fsdp": True}))}
    assert held["fsdp"] < held["ws"] <= held["replicated"], held
    assert held["ws"] < 0.7 * held["replicated"], held


def test_composed_step_keeps_a_closed_jit_set():
    """Size churn on the composed 2-D step: uniform iterator batches merge
    into ONE global-batch signature, so the step compiles exactly once
    across epochs and batch groups — zero retrace storms (the jitwatch
    proof that composition added no signature churn)."""
    from deeplearning4j_tpu.monitor.jitwatch import get_jit_registry
    reg = get_jit_registry()
    before = reg.table().get("sharding/dp_step", {})
    c0 = before.get("compiles", 0)
    s0 = before.get("storms", 0)
    net = _adam_net()
    pw = (ParallelWrapper.Builder(net).workers(8).tensor_parallel(2)
          .weight_update_sharding().build())
    pw.fit(ListDataSetIterator(_batches(8)), epochs=3)
    assert pw.iteration_count == 2 * 3       # 8 batches / 4 data slices
    after = reg.table()["sharding/dp_step"]
    assert after["compiles"] - c0 == 1, after
    assert after["storms"] - s0 == 0, after


def test_wrapper_tp_validation_is_loud():
    # composition is AVERAGING freq=1 only (like ws) — silent fallback
    # would fake the model split
    with pytest.raises(NotImplementedError, match="AVERAGING"):
        (ParallelWrapper.Builder(_adam_net()).workers(8)
         .tensor_parallel(2).averaging_frequency(2).build())
    with pytest.raises(NotImplementedError, match="AVERAGING"):
        (ParallelWrapper.Builder(_adam_net()).workers(8)
         .tensor_parallel(2)
         .training_mode(TrainingMode.SHARED_GRADIENTS).build())
    # an extent that cannot split anything is a config bug, not a no-op
    with pytest.raises(ValueError, match=">= 2"):
        ParallelWrapper(_adam_net(), tensor_parallel=1)
    # a wrapper mesh must carry the data axis it drives
    with pytest.raises(ValueError, match="data"):
        ParallelWrapper(_adam_net(),
                        mesh=make_mesh(jax.devices()[:2],
                                       axes=(MODEL_AXIS,)))
    # tp_rules with nowhere to shard them
    with pytest.raises(ValueError, match="model axis"):
        ParallelWrapper(_adam_net(), tp_rules={"^0/W$": None})
    # an explicit mesh whose model extent disagrees with the requested
    # one must not silently win (review finding)
    with pytest.raises(ValueError, match="model extent 2"):
        ParallelWrapper(_adam_net(), tensor_parallel=4,
                        mesh=make_mesh(axes=(DATA_AXIS, MODEL_AXIS),
                                       shape=(4, 2)))
    # rules naming an axis the mesh lacks fail loudly at the substrate,
    # not as a KeyError deep inside a tree_map (review finding)
    from jax.sharding import PartitionSpec as P
    from deeplearning4j_tpu.parallel import data_parallel_step
    with pytest.raises(ValueError, match="model.*MeshSpec"):
        data_parallel_step(_adam_net(), make_mesh(axes=(DATA_AXIS,)),
                           tp_rules={"^0/W$": P(None, MODEL_AXIS)})


def test_tensor_parallel_step_zero_flags():
    """ZeRO on tensor_parallel_step's own mesh: shard_update/shard_params
    layer the data axis over the TP rules (any-mesh ZeRO, not just the
    wrapper's), and a mesh without a data axis rejects loudly."""
    mesh = make_mesh(axes=(DATA_AXIS, MODEL_AXIS), shape=(4, 2))
    net = _adam_net()
    step, place = tensor_parallel_step(net, mesh, shard_update=True)
    place(net)
    upd_specs = {str(l.sharding.spec)
                 for l in jax.tree_util.tree_leaves(net.updater_state)
                 if hasattr(l, "sharding")}
    assert any(DATA_AXIS in s for s in upd_specs), upd_specs
    ds = _batches(1)[0]
    import jax.numpy as jnp
    itc = jnp.asarray(0, jnp.int32)
    key = net._next_rng()
    net.params, net.states, net.updater_state, loss = step(
        net.params, net.states, net.updater_state, itc, key,
        jnp.asarray(ds.features), jnp.asarray(ds.labels), None, None)
    assert np.isfinite(float(loss))
    with pytest.raises(ValueError, match="data"):
        tensor_parallel_step(_adam_net(),
                             make_mesh(jax.devices()[:2],
                                       axes=(MODEL_AXIS,)),
                             shard_update=True)


# ------------------------------------------------------- /profile block
def test_profile_mesh_block_reports_active_topology():
    from deeplearning4j_tpu.monitor.jitwatch import (profile_report,
                                                     render_profile_text)
    reset_mesh_registry()
    assert mesh_block() == {}
    net = _adam_net()
    pw = (ParallelWrapper.Builder(net).workers(8).tensor_parallel(2)
          .fsdp().build())
    pw.fit(ListDataSetIterator(_batches(4)), epochs=1)
    block = profile_report()["mesh"]
    row = block["sharding/dp_step"]
    assert row["axes"] == {"data": 4, "model": 2}
    assert row["devices"] == 8
    assert row["steps"] >= 1
    assert row["sharded_leaves"] > 0
    assert row["zero"] is True
    # sharded + replicated must cover the params+updater leaf census
    n_leaves = len(jax.tree_util.tree_leaves(net.params)) + \
        len(jax.tree_util.tree_leaves(net.updater_state))
    assert row["sharded_leaves"] + row["replicated_leaves"] == n_leaves
    txt = render_profile_text(profile_report())
    assert "# mesh (active parallel topologies)" in txt
    assert "sharding/dp_step" in txt
    assert "data=4" in txt and "model=2" in txt
