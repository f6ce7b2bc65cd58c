"""Compile-once fleet (ISSUE 12, PERF.md "Compile-once fleet"):
persistent XLA compile cache + AOT warmup artifacts.

THE acceptance lives here: a second process pointed at a warm cache dir
serves its first request with zero full recompiles of warmed signatures
— proven via ``jit_persistent_cache_hits_total`` and a pinned cold→warm
compile-seconds ratio — and an exported AOT artifact round-trips to
bit-identical predictions, while a corrupted/mismatched artifact falls
back loudly (``compile_cache_miss`` flight event), never crashing.
"""
import json
import os
import subprocess
import sys
import zipfile

import numpy as np
import pytest

from deeplearning4j_tpu.compilecache import cache as cc_cache


# --------------------------------------------------------------- helpers
def _mlp(n_in=16, hidden=32, classes=4, seed=7, depth=1):
    from deeplearning4j_tpu import (NeuralNetConfiguration,
                                    MultiLayerNetwork, Sgd)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    b = (NeuralNetConfiguration.builder().seed(seed)
         .updater(Sgd(learning_rate=0.05)).activation("tanh").list()
         .layer(DenseLayer(n_in=n_in, n_out=hidden)))
    for _ in range(depth - 1):
        b = b.layer(DenseLayer(n_in=hidden, n_out=hidden))
    b = b.layer(OutputLayer(n_in=hidden, n_out=classes,
                            activation="softmax", loss="mcxent"))
    return MultiLayerNetwork(b.build()).init()


def _run_child(src, extra_env, timeout=300):
    env = dict(os.environ, **extra_env)
    p = subprocess.run([sys.executable, "-c", src], capture_output=True,
                       text=True, env=env, timeout=timeout)
    assert p.returncode == 0, f"child failed:\n{p.stderr[-3000:]}"
    for line in reversed(p.stdout.splitlines()):
        try:
            return json.loads(line)
        except ValueError:
            continue
    raise AssertionError(f"no JSON record in child stdout: {p.stdout!r}")


def _cc_state():
    """Snapshot/restore seam for the process-global listener counters."""
    return dict(cc_cache._STATE), cc_cache._ENABLED_FAST[0]


def _restore(state):
    snap, fast = state
    cc_cache._STATE.update(snap)
    cc_cache._ENABLED_FAST[0] = fast


# ------------------------------------------------------- claim protocol
def test_claim_protocol_window_and_suppression():
    """claim_persistent_hit: a hit is claimable only when the counter
    grew during the caller's own window AND an unclaimed hit remains;
    suppress_events keeps background (cost-worker) compiles out of the
    attribution pool entirely."""
    state = _cc_state()
    try:
        cc_cache._STATE.update(hits=0, misses=0, claimed=0)
        before = cc_cache.hits_count()
        assert cc_cache.claim_persistent_hit(before) is False   # no growth
        cc_cache._on_event("/jax/compilation_cache/cache_hits")
        cc_cache._on_event("/jax/compilation_cache/cache_misses")
        assert cc_cache.persistent_cache_counts() == {"hits": 1,
                                                      "misses": 1}
        assert cc_cache.claim_persistent_hit(before) is True
        # the one hit is claimed — a second claimant must get False even
        # though its window also saw the growth
        assert cc_cache.claim_persistent_hit(before) is False
        with cc_cache.suppress_events():
            cc_cache._on_event("/jax/compilation_cache/cache_hits")
        assert cc_cache.persistent_cache_counts()["hits"] == 1
        # suppression is scoped: events count again after the block
        cc_cache._on_event("/jax/compilation_cache/cache_hits")
        assert cc_cache.persistent_cache_counts()["hits"] == 2
    finally:
        _restore(state)


def test_maybe_enable_is_noop_without_the_dial(monkeypatch):
    monkeypatch.delenv(cc_cache.ENV_DIR, raising=False)
    monkeypatch.delenv(cc_cache.JAX_ENV_DIR, raising=False)
    if cc_cache.enabled():
        pytest.skip("cache already enabled in this process")
    assert cc_cache.maybe_enable() is None
    assert cc_cache.cache_dir() is None


def test_cache_directory_resolution_order(monkeypatch, tmp_path):
    """Where the cache lives: JAX_COMPILATION_CACHE_DIR, then the
    directory the caller passes (chip_smoke.py: the fixed
    <checkout>/.jax_cache), then the DL4J_TPU_COMPILE_CACHE_DIR dial. With
    jax's own variable set the cache is placed from outside: no code
    updates ``jax_compilation_cache_dir`` at all. jax's config and latch
    are stubbed — the process-global cache state is not touched."""
    import jax
    from jax._src import compilation_cache as jcc
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setattr(jcc, "reset_cache", lambda: None)
    monkeypatch.setattr(cc_cache, "_install_listener", lambda: None)
    ext, fixed, dial = (str(tmp_path / n) for n in ("ext", "fixed", "dial"))
    state = _cc_state()

    def resolve(arg):
        cc_cache._STATE["dir"] = None
        updates.clear()
        got = cc_cache.enable(arg)
        return got, [v for k, v in updates
                     if k == "jax_compilation_cache_dir"]

    try:
        monkeypatch.setenv(cc_cache.ENV_DIR, dial)
        monkeypatch.setenv(cc_cache.JAX_ENV_DIR, ext)
        assert resolve(fixed) == (ext, [])
        assert ("jax_persistent_cache_min_compile_time_secs", 0) in updates
        cc_cache._STATE["dir"] = None
        assert cc_cache.maybe_enable() == ext       # the seam honours it too
        monkeypatch.delenv(cc_cache.JAX_ENV_DIR)
        assert resolve(fixed) == (fixed, [fixed])
        assert resolve(None) == (dial, [dial])
        monkeypatch.delenv(cc_cache.ENV_DIR)
        assert resolve(None) == (None, [])
        assert not os.path.exists(dial) or os.listdir(dial) == []
    finally:
        _restore(state)


def test_jitwatch_splits_persistent_hits(monkeypatch):
    """A compile whose call window saw a disk hit lands in
    persistent_cache_hits / jit_persistent_cache_hits_total{fn=}; one
    without stays a true compile. Driven in-process by firing the
    listener from inside the traced function (trace time IS the call
    window), so no global jax config is touched."""
    from deeplearning4j_tpu.monitor import get_registry
    from deeplearning4j_tpu.monitor.jitwatch import (get_jit_registry,
                                                     monitored_jit)
    state = _cc_state()
    fire = {"on": True}

    def fn(x):
        if fire["on"]:
            cc_cache._on_event("/jax/compilation_cache/cache_hits")
        return x + 1

    try:
        cc_cache._STATE.update(hits=0, misses=0, claimed=0)
        cc_cache._ENABLED_FAST[0] = True
        f = monitored_jit(fn, name="cc/probe_split")
        f(np.ones((3,), np.float32))            # compile 1: disk hit
        fire["on"] = False
        f(np.ones((2, 2), np.float32))          # compile 2: true compile
        row = get_jit_registry().table()["cc/probe_split"]
        assert row["compiles"] == 2
        assert row["persistent_cache_hits"] == 1
        assert row["true_compiles"] == 1
        snap = get_registry().snapshot()
        hits = [r for r in snap.get("jit_persistent_cache_hits_total", [])
                if r["labels"].get("fn") == "cc/probe_split"]
        assert hits and hits[0]["value"] == 1.0
        # the split reaches the text render (the `disk` column)
        from deeplearning4j_tpu.monitor.jitwatch import (profile_report,
                                                         render_profile_text)
        text = render_profile_text(profile_report())
        assert "disk" in text and "cc/probe_split" in text
    finally:
        _restore(state)


# ------------------------------------------ THE shared-cache acceptance
_ACCEPT_SRC = """
import json
import numpy as np
from deeplearning4j_tpu import (NeuralNetConfiguration, MultiLayerNetwork,
                                Sgd, ModelRegistry)
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
b = (NeuralNetConfiguration.builder().seed(7)
     .updater(Sgd(learning_rate=0.05)).activation('tanh').list())
for _ in range(16):
    b = b.layer(DenseLayer(n_in=96, n_out=96))
b = b.layer(OutputLayer(n_in=96, n_out=10, activation='softmax',
                        loss='mcxent'))
net = MultiLayerNetwork(b.build()).init()
reg = ModelRegistry()
served = reg.register('accept', net, batch_buckets=(1, 2, 4),
                      input_shape=(96,), warmup=True)
from deeplearning4j_tpu.monitor.jitwatch import get_jit_registry
warmed = dict(get_jit_registry().table().get('mln/output', {}))
out = served.predict(np.ones((1, 96), np.float32))   # first request
row = get_jit_registry().table().get('mln/output', {})
from deeplearning4j_tpu.monitor import get_registry
snap = get_registry().snapshot()
series = sum(r['value'] for r in
             snap.get('jit_persistent_cache_hits_total', []))
reg.close_all(drain=False)
print(json.dumps({'compile_s': warmed['compile_seconds'],
                  'compiles': warmed['compiles'],
                  'persistent_cache_hits': warmed['persistent_cache_hits'],
                  'true_compiles': warmed['true_compiles'],
                  'series_hits': series,
                  'request_compiles': row['compiles'] - warmed['compiles'],
                  'out_ok': bool(np.isfinite(np.asarray(out)).all())}))
"""


def test_second_process_warms_from_shared_cache_dir(tmp_path):
    """THE acceptance: two processes share DL4J_TPU_COMPILE_CACHE_DIR.
    The first (cold) pays true XLA compiles and populates the dir; the
    second (warm) performs the SAME warmup with every compile a
    persistent-cache hit — ``jit_persistent_cache_hits_total >= 1``
    (in fact == compiles: zero full recompiles of warmed signatures), and
    its first request served with zero additional compiles. What a warm
    start saves in seconds is the chip's to say (``setup_s`` of a cell's
    first run against its later ones): two CPU children's wall clocks on a
    shared host do not order reliably."""
    env = {"DL4J_TPU_COMPILE_CACHE_DIR": str(tmp_path / "cc")}
    cold = _run_child(_ACCEPT_SRC, env)
    warm = _run_child(_ACCEPT_SRC, env)

    assert cold["compiles"] == 3                 # one per batch bucket
    assert cold["persistent_cache_hits"] == 0    # nothing to hit yet
    assert cold["out_ok"] and warm["out_ok"]

    # the warm twin: every warmup compile was a disk read
    assert warm["series_hits"] >= 1
    assert warm["persistent_cache_hits"] == warm["compiles"] == 3
    assert warm["true_compiles"] == 0
    # ...and the first request after warmup compiles NOTHING
    assert warm["request_compiles"] == 0
    assert cold["compile_s"] > 0 and warm["compile_s"] > 0


# ------------------------------------------------------- AOT artifacts
def test_artifact_roundtrip_bit_identical(tmp_path):
    """export → warm(artifact=) → predict is byte-for-byte the live
    twin's answer, with zero forward compiles on the loading side and a
    compile_cache_artifact_loaded flight event."""
    from deeplearning4j_tpu.monitor import get_flight_recorder
    from deeplearning4j_tpu.monitor.jitwatch import get_jit_registry
    from deeplearning4j_tpu.serving.registry import ServedModel

    x = np.random.default_rng(3).normal(size=(1, 16)).astype(np.float32)
    served = ServedModel("aot_src", _mlp(), batch_buckets=(1, 2),
                         input_shape=(16,), warmup=True)
    path = served.export_warmup(str(tmp_path))
    assert path.endswith(".dl4jaot") and os.path.exists(path)
    ref = np.asarray(served.predict(x))
    served.close()

    before = dict(get_jit_registry().table().get("mln/output", {}))
    twin = ServedModel("aot_dst", _mlp(), batch_buckets=(1, 2),
                       input_shape=(16,))
    twin.warm(artifact=path)
    assert twin.stats()["aot_signatures"] == 2
    out = np.asarray(twin.predict(x))
    after = get_jit_registry().table().get("mln/output", {})
    twin.close()

    assert (out == ref).all(), "artifact-served predict must be " \
                               "bit-identical to the live twin"
    # zero forward compiles on the loading side (AOT bypasses the jit)
    assert after.get("compiles", 0) == before.get("compiles", 0)
    events = [e for e in get_flight_recorder().events()
              if e["event"] == "compile_cache_artifact_loaded"
              and e.get("model") == "aot_dst"]
    assert events and events[-1]["signatures"] == 2

    # an artifact-warmed model can RE-EXPORT (toolchain-refresh
    # workflow): the exporter forces the live warm path past the AOT
    # table, and the fresh artifact installs like the original
    reexp = ServedModel("aot_dst2", _mlp(), batch_buckets=(1, 2),
                        input_shape=(16,), warmup_artifact=path)
    assert reexp.stats()["aot_signatures"] == 2
    path2 = reexp.export_warmup(str(tmp_path / "re"))
    assert reexp.stats()["aot_signatures"] == 2      # table restored
    reexp.close()
    third = ServedModel("aot_dst3", _mlp(), batch_buckets=(1, 2),
                        input_shape=(16,), warmup_artifact=path2)
    assert third.stats()["aot_signatures"] == 2
    assert (np.asarray(third.predict(x)) == ref).all()
    third.close()


def test_corrupted_artifact_falls_back_loudly(tmp_path):
    """Garbage bytes and a tampered fingerprint both fall back to live
    compile with a compile_cache_miss flight event — never a crash,
    never a silently-installed executable."""
    from deeplearning4j_tpu.monitor import get_flight_recorder
    from deeplearning4j_tpu.serving.registry import ServedModel

    x = np.ones((1, 16), np.float32)
    served = ServedModel("aot_exp", _mlp(), batch_buckets=(1, 2),
                         input_shape=(16,), warmup=True)
    good = served.export_warmup(str(tmp_path))
    served.close()

    garbage = str(tmp_path / "garbage.dl4jaot")
    with open(garbage, "wb") as fh:
        fh.write(b"not a zip at all")
    tampered = str(tmp_path / "tampered.dl4jaot")
    with zipfile.ZipFile(good) as zin, \
            zipfile.ZipFile(tampered, "w") as zout:
        for name in zin.namelist():
            data = zin.read(name)
            if name == "manifest.json":
                man = json.loads(data)
                man["fingerprint"]["jax"] = "0.0.0-elsewhere"
                data = json.dumps(man).encode()
            zout.writestr(name, data)

    for name, bad, reason_frag in (("aot_garb", garbage, ""),
                                   ("aot_tamp", tampered, "fingerprint")):
        m = ServedModel(name, _mlp(), batch_buckets=(1, 2),
                        input_shape=(16,))
        m.warm(artifact=bad)                     # must not raise
        assert m._aot == {}                      # nothing installed
        out = np.asarray(m.predict(x))           # live path serves
        assert out.shape == (1, 4)
        m.close()
        misses = [e for e in get_flight_recorder().events()
                  if e["event"] == "compile_cache_miss"
                  and e.get("model") == name]
        assert misses, f"no compile_cache_miss event for {name}"
        assert reason_frag in misses[-1]["reason"]


def test_loader_only_replica_rejected_artifact_starts_cold(tmp_path):
    """A replica configured with ONLY warmup_artifact (no input_shape —
    the artifact was going to supply it) whose artifact is rejected must
    START anyway, cold: the never-a-crash fallback contract covers the
    no-input-shape case too — first requests pay the compiles."""
    from deeplearning4j_tpu.monitor import get_flight_recorder
    from deeplearning4j_tpu.serving.registry import ServedModel

    garbage = str(tmp_path / "garbage.dl4jaot")
    with open(garbage, "wb") as fh:
        fh.write(b"junk")
    m = ServedModel("aot_cold", _mlp(), batch_buckets=(1, 2),
                    warmup_artifact=garbage)     # no input_shape, no raise
    assert m._aot == {} and m.input_shape is None
    out = np.asarray(m.predict(np.ones((1, 16), np.float32)))
    assert out.shape == (1, 4)                   # serves, compiling live
    m.close()
    assert any(e["event"] == "compile_cache_miss"
               and e.get("model") == "aot_cold"
               for e in get_flight_recorder().events())


def test_mismatched_topology_and_buckets_rejected(tmp_path):
    """An artifact from a DIFFERENT architecture (or bucket set) must
    not install — its executables compute the wrong function."""
    from deeplearning4j_tpu.monitor import get_flight_recorder
    from deeplearning4j_tpu.serving.registry import ServedModel

    served = ServedModel("aot_a", _mlp(hidden=32), batch_buckets=(1, 2),
                         input_shape=(16,), warmup=True)
    path = served.export_warmup(str(tmp_path))
    served.close()

    other = ServedModel("aot_topo", _mlp(hidden=48),   # different net
                        batch_buckets=(1, 2), input_shape=(16,))
    other.warm(artifact=path)
    assert other._aot == {}
    other.close()
    ev = [e for e in get_flight_recorder().events()
          if e["event"] == "compile_cache_miss"
          and e.get("model") == "aot_topo"]
    assert ev and "topology" in ev[-1]["reason"]

    rebucketed = ServedModel("aot_bkt", _mlp(hidden=32),
                             batch_buckets=(1, 2, 4), input_shape=(16,))
    rebucketed.warm(artifact=path)               # bucket set differs
    assert rebucketed._aot == {}
    rebucketed.close()
    ev = [e for e in get_flight_recorder().events()
          if e["event"] == "compile_cache_miss"
          and e.get("model") == "aot_bkt"]
    assert ev and "bucket" in ev[-1]["reason"]


def test_compile_signatures_is_the_closed_set():
    """The batcher's enumeration (shared by warm() and the exporter):
    one signature per batch bucket, × time buckets (masked) for
    sequence models, in the serving dtype."""
    from deeplearning4j_tpu.serving.batcher import ContinuousBatcher

    with ContinuousBatcher(lambda xs: xs, batch_buckets=(1, 2)) as b:
        assert b.compile_signatures((7,)) == [
            ((1, 7), "float32", False), ((2, 7), "float32", False)]
    with ContinuousBatcher(lambda xs, mask=None: xs,
                           batch_buckets=(2, 4),
                           time_buckets=(8, 16)) as b:
        assert b.compile_signatures((5, 3)) == [
            ((2, 8, 3), "float32", True), ((2, 16, 3), "float32", True),
            ((4, 8, 3), "float32", True), ((4, 16, 3), "float32", True)]
    with ContinuousBatcher(lambda xs: xs, batch_buckets=(1,),
                           precision="bf16") as b:
        assert b.compile_signatures((4,)) == [((1, 4), "bfloat16", False)]


def test_artifact_manifest_matches_enumeration(tmp_path):
    """The exported manifest's signature list IS compile_signatures —
    an artifact can never silently cover a different set than warm()."""
    from deeplearning4j_tpu.compilecache import read_manifest
    from deeplearning4j_tpu.serving.registry import ServedModel

    served = ServedModel("aot_man", _mlp(), batch_buckets=(1, 2),
                         input_shape=(16,), warmup=True)
    path = served.export_warmup(str(tmp_path))
    man = read_manifest(path)
    sigs = served.batcher.compile_signatures(served.input_shape)
    served.close()
    assert [(tuple(s["shape"]), s["dtype"], s["masked"])
            for s in man["signatures"]] == sigs
    assert man["precision"] == "f32"
    assert man["batch_buckets"] == [1, 2]
    for key in ("jax", "backend", "backend_version"):
        assert key in man["fingerprint"]


# ---------------------------------------------------------------- GC
def test_gc_evicts_only_mismatched_fingerprints(tmp_path):
    from deeplearning4j_tpu.compilecache import gc_cache
    from deeplearning4j_tpu.serving.registry import ServedModel

    served = ServedModel("aot_gc", _mlp(), batch_buckets=(1,),
                         input_shape=(16,), warmup=True)
    good = served.export_warmup(str(tmp_path))
    served.close()
    stale = str(tmp_path / "stale.dl4jaot")
    with zipfile.ZipFile(good) as zin, zipfile.ZipFile(stale, "w") as zout:
        for name in zin.namelist():
            data = zin.read(name)
            if name == "manifest.json":
                man = json.loads(data)
                man["fingerprint"]["backend_version"] = "ancient"
                data = json.dumps(man).encode()
            zout.writestr(name, data)

    orphan = str(tmp_path / "half.dl4jaot.tmp")  # a killed export
    with open(orphan, "wb") as fh:
        fh.write(b"half-written")

    from deeplearning4j_tpu.compilecache import cache_stats
    census = cache_stats(str(tmp_path))
    assert census["artifacts"] == 2              # good + stale
    assert census["entries"] == 0                # the orphan is NOT a
                                                 # jax cache entry

    report = gc_cache(str(tmp_path))             # dry-run default
    assert report["dry_run"] is True
    assert report["scanned"] == 3 and report["kept"] == 1
    assert sorted(os.path.basename(e["path"])
                  for e in report["evicted"]) == \
        ["half.dl4jaot.tmp", "stale.dl4jaot"]
    assert os.path.exists(stale)                 # dry-run deletes nothing
    assert os.path.exists(orphan)

    report = gc_cache(str(tmp_path), dry_run=False)
    assert all(e["removed"] for e in report["evicted"])
    assert not os.path.exists(stale) and not os.path.exists(orphan)
    assert os.path.exists(good)


# ------------------------------------------------- step_cost satellite
def test_step_cost_reuses_cached_lowering():
    """ISSUE 12 satellite: repeated step_cost over the same shapes must
    not re-trace (nor re-compile) — the jitwatch wrapper's cached
    lowering is reused; only a NEW shape pays a lowering."""
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.utils import profiling

    net = _mlp()
    rng = np.random.default_rng(0)

    def ds(batch):
        return DataSet(rng.normal(size=(batch, 16)).astype(np.float32),
                       np.eye(4, dtype=np.float32)[
                           rng.integers(0, 4, batch)])

    first = profiling.step_cost(net, ds(8))
    assert first["flops"] > 0 and first["batch"] == 8
    state = getattr(net, profiling._STEP_COST_ATTR)
    assert len(state["wrapper"]._lowerings) == 1

    class _NoLower:
        def lower(self, *a, **k):
            raise AssertionError("step_cost re-lowered a cached shape")

    real_jit = state["wrapper"]._jit
    state["wrapper"]._jit = _NoLower()
    try:
        again = profiling.step_cost(net, ds(8))   # same shapes: cached
        assert again["flops"] == first["flops"]
        with pytest.raises(AssertionError):
            profiling.step_cost(net, ds(4))       # new shape MUST lower
    finally:
        state["wrapper"]._jit = real_jit
    other = profiling.step_cost(net, ds(4))       # ...and now it can
    assert other["batch"] == 4 and other["flops"] > 0
