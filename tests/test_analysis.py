"""tpulint (deeplearning4j_tpu/analysis — docs/STATIC_ANALYSIS.md).

Per-rule positive/negative fixtures (deleting any rule's implementation
makes its fixture test fail), pragma suppression, baseline round-trip,
JSON output schema, and the self-hosting tier-1 run: the whole package
must lint clean against the shipped ``analysis/baseline.json``, which is
ratchet-only — new violations fail here, fixed ones must be deleted from
the baseline.
"""
import json
import textwrap

import pytest

from deeplearning4j_tpu.analysis import (Linter, load_baseline,
                                         save_baseline,
                                         DEFAULT_BASELINE_PATH,
                                         PACKAGE_ROOT, all_rules, get_rule)

#: the full registry, pinned at 14 — EXC001 included (it was silently
#: missing from an earlier revision of this set) and THR005 with it;
#: a rule added without extending this pin fails the registry test
RULE_IDS = {"JAX001", "JAX002", "JAX003", "JAX004", "THR001", "THR002",
            "THR003", "THR004", "THR005", "RES001", "EXC001", "MON001",
            "PERF001", "CTL001"}
assert len(RULE_IDS) == 14


# default fixture path lives under tests/ so the JAX003 bare-jit rule
# (tests-exempt by design) does not fire on every jax.jit fixture the
# OTHER rules legitimately use; JAX003 tests pass package-like paths
def lint_src(src, rules=None, path="tests/fixture.py"):
    return Linter(rules=rules).lint_source(textwrap.dedent(src), path)


def rule_ids(findings):
    return [f.rule for f in findings]


# ------------------------------------------------------------- registry
def test_rule_registry_is_complete_and_documented():
    rules = all_rules()
    assert set(rules) == RULE_IDS
    for rid, cls in rules.items():
        assert cls.id == rid
        assert cls.title, rid
        assert len(cls.rationale) > 40, f"{rid} needs a real rationale"
    assert get_rule("thr001").id == "THR001"            # case-insensitive
    with pytest.raises(KeyError):
        get_rule("NOPE999")


def test_syntax_error_reports_not_raises():
    fs = lint_src("def f(:\n    pass\n")
    assert rule_ids(fs) == ["SYN000"]


# ------------------------------------------------- JAX001 host-sync in jit
def test_jax001_flags_host_sync_in_decorated_jit():
    fs = lint_src("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x):
            y = jnp.sum(x)
            return float(y)
        """)
    assert rule_ids(fs) == ["JAX001"]
    assert "float()" in fs[0].message


def test_jax001_flags_jit_wrapped_local_def():
    # the repo's dominant idiom: jax.jit(step, donate_argnums=...) around
    # a local def (nn/multilayer.py, nn/graph.py)
    fs = lint_src("""
        import jax
        import numpy as np

        def make(net):
            def step(p, x):
                x.block_until_ready()
                q = np.asarray(p)
                return q
            return jax.jit(step, donate_argnums=(0,))
        """)
    assert rule_ids(fs) == ["JAX001", "JAX001"]
    assert "block_until_ready" in fs[0].message
    assert "np.asarray" in fs[1].message


def test_jax001_ignores_host_sync_outside_jit_and_jnp_inside():
    fs = lint_src("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x):
            return jnp.asarray(x) + float(1.0)   # jnp + constant: fine

        def fit_loop(step, x):
            loss = step(x)
            return float(loss)                   # the sanctioned fetch
        """)
    assert fs == []


# ------------------------------------------------- JAX002 PRNG key reuse
def test_jax002_flags_straight_line_key_reuse():
    fs = lint_src("""
        import jax

        def f(key):
            a = jax.random.normal(key, (2,))
            b = jax.random.uniform(key, (2,))
            return a + b
        """)
    assert rule_ids(fs) == ["JAX002"]
    assert "'key'" in fs[0].message


def test_jax002_split_consumes_too():
    # feeding key to split and then to normal correlates the draws
    fs = lint_src("""
        import jax

        def f(key):
            k1, k2 = jax.random.split(key)
            return jax.random.normal(key, (2,))
        """)
    assert rule_ids(fs) == ["JAX002"]


def test_jax002_accepts_split_and_fold_in_flows():
    fs = lint_src("""
        import jax

        def f(key):
            k1, k2 = jax.random.split(key)
            a = jax.random.normal(k1, (2,))
            b = jax.random.uniform(k2, (2,))
            return a + b

        def g(key, n):
            out = []
            for i in range(n):
                out.append(jax.random.normal(
                    jax.random.fold_in(key, i), (2,)))
            return out
        """)
    assert fs == []


def test_jax002_branches_do_not_conflict():
    # the RBM sampler shape (nn/layers/feedforward.py): mutually-exclusive
    # guard-ifs each consuming the key once
    fs = lint_src("""
        import jax

        def sample(kind, key, z):
            if kind == "binary":
                return jax.random.bernoulli(key, z)
            if kind == "gaussian":
                return z + jax.random.normal(key, z.shape)
            return z
        """)
    assert fs == []


def test_jax002_use_after_branch_use_conflicts():
    fs = lint_src("""
        import jax

        def f(cond, key):
            if cond:
                a = jax.random.normal(key, (2,))
            return jax.random.uniform(key, (2,))
        """)
    assert rule_ids(fs) == ["JAX002"]


def test_jax002_loop_reuse_without_rebinding():
    fs = lint_src("""
        import jax

        def f(key, n):
            out = []
            for i in range(n):
                out.append(jax.random.normal(key, (2,)))
            return out
        """)
    assert rule_ids(fs) == ["JAX002"]
    assert "loop" in fs[0].message


# -------------------------------------------- THR001 blocking under lock
def test_thr001_flags_sleep_socket_and_bare_queue_get_under_lock():
    fs = lint_src("""
        import threading
        import time

        class Server:
            def __init__(self, sock, q):
                self._lock = threading.Lock()
                self.sock, self.q = sock, q

            def bad(self):
                with self._lock:
                    time.sleep(0.1)
                    self.sock.sendall(b"x")
                    item = self.q.get()
                    also = self.q.get(timeout=None)   # still unbounded
                return item, also
        """)
    assert rule_ids(fs) == ["THR001"] * 4


def test_thr001_accepts_snapshot_then_block_outside():
    fs = lint_src("""
        import threading
        import time

        class Server:
            def __init__(self, sock, q):
                self._lock = threading.Lock()
                self.sock, self.q = sock, q

            def good(self):
                with self._lock:
                    data = dict(self.pending)       # snapshot under lock
                    cached = self.q.get("k")        # dict.get: not a queue
                    bounded = self.q.get(timeout=1)
                self.sock.sendall(b"x")             # block outside
                time.sleep(0.1)
                return data, cached, bounded

            def closure_defined_under_lock_runs_later(self):
                with self._lock:
                    def send():
                        self.sock.sendall(b"x")
                    t = ",".join(["a", "b"])        # str.join: not thread
                return send, t
        """)
    assert fs == []


def test_thr001_flags_repo_wire_helpers_and_join():
    fs = lint_src("""
        import threading
        from parallel.transport import send_frame

        class Peer:
            def __init__(self, lock, t):
                self._send_locks = {1: lock}
                self.t = t

            def bad(self, s, frame):
                with self._send_locks[1]:
                    send_frame(s, frame)
                    self.t.join()
        """)
    assert rule_ids(fs) == ["THR001", "THR001"]


# ------------------------------------------------ THR002 leaked threads
def test_thr002_flags_non_daemon_never_joined():
    fs = lint_src("""
        import threading

        def serve(fn):
            t = threading.Thread(target=fn)
            t.start()
            threading.Thread(target=fn).start()     # unbound: unjoinable
        """)
    assert rule_ids(fs) == ["THR002", "THR002"]


def test_thr002_accepts_daemon_or_joined_threads():
    fs = lint_src("""
        import threading
        from threading import Thread

        class Svc:
            def start(self, fn):
                self._t = threading.Thread(target=fn, daemon=True)
                self._t.start()
                self._w = Thread(target=fn)
                self._w.daemon = True
                self._w.start()
                self._j = threading.Thread(target=fn)
                self._j.start()

            def stop(self):
                self._j.join()
        """)
    assert fs == []


# ------------------------------------------------ EXC001 silent swallows
def test_exc001_flags_silent_broad_handlers():
    fs = lint_src("""
        def f(x):
            try:
                return x()
            except Exception:
                pass

        def g(x):
            try:
                return x()
            except:
                return None
        """)
    assert rule_ids(fs) == ["EXC001", "EXC001"]


def test_exc001_accepts_narrow_logged_reraised_or_routed():
    fs = lint_src("""
        import logging

        log = logging.getLogger(__name__)

        def narrow(x):
            try:
                return x()
            except (OSError, ValueError):
                return None

        def logged(x):
            try:
                return x()
            except Exception:
                log.debug("swallowed", exc_info=True)

        def reraised(x):
            try:
                return x()
            except Exception:
                raise RuntimeError("wrapped")

        def routed(x, fut):
            try:
                return x()
            except Exception as e:
                fut.set_exception(e)     # kept, not swallowed
        """)
    assert fs == []


# ------------------------------------- PERF001 blocking d2h in hot loop
_PERF_LOOP = """
    import jax
    import numpy as np

    def fit(step, net, iterator):
        for ds in iterator:
            update, loss = step(net.params, ds)
            update = jax.tree_util.tree_map(np.asarray, update)
            net.apply(update)
    """


def test_perf001_flags_blocking_tree_map_fetch_in_hot_loop():
    fs = lint_src(_PERF_LOOP, path="pkg/paramserver/training.py")
    assert rule_ids(fs) == ["PERF001"]
    assert "async_device_get" in fs[0].message
    # parallel/ is a hot package too; device_get is the other fetch shape
    fs = lint_src(_PERF_LOOP.replace("np.asarray", "jax.device_get"),
                  path="pkg/parallel/distributed.py")
    assert rule_ids(fs) == ["PERF001"]


def test_perf001_only_fires_in_hot_packages_and_loops():
    # same shape outside paramserver//parallel/: silent
    assert lint_src(_PERF_LOOP, path="pkg/serving/engine.py") == []
    # the fetch OUTSIDE a loop: one-shot d2h is fine
    assert lint_src("""
        import jax
        import numpy as np

        def snapshot(update):
            return jax.tree_util.tree_map(np.asarray, update)
        """, path="pkg/paramserver/training.py") == []
    # jnp.asarray keeps the tree device-resident — not a fetch
    assert lint_src(_PERF_LOOP.replace("np.asarray", "jnp.asarray"),
                    path="pkg/paramserver/training.py") == []
    # a closure DEFINED in the loop does not run per iteration
    assert lint_src("""
        import jax
        import numpy as np

        def fit(iterator):
            for ds in iterator:
                def later(u):
                    return jax.tree_util.tree_map(np.asarray, u)
                yield later
        """, path="pkg/paramserver/training.py") == []


def test_perf001_pragma_suppresses():
    src = _PERF_LOOP.replace(
        "tree_map(np.asarray, update)",
        "tree_map(np.asarray, update)  # tpulint: disable=PERF001")
    assert lint_src(src, path="pkg/paramserver/training.py") == []


# ------------------------------- CTL001 actuator outside the control plane
_CTL_SRC = """
    def autoscale(group, master):
        addrs = group.scale_to(4)
        master.remap(addrs)

    def heal(group, shard):
        group.restart(shard)

    def shed(registry, model):
        registry.get(model).set_admission(max_queue_examples=8)
    """


def test_ctl001_flags_actuator_calls_outside_control_plane():
    fs = lint_src(_CTL_SRC, path="pkg/serving/engine.py")
    assert rule_ids(fs) == ["CTL001"] * 4
    assert "ControlPolicy" in fs[0].message
    hit = {f.message.split("actuator call ")[1].split("(")[0]
           for f in fs}
    assert hit == {".scale_to", ".remap", ".restart", ".set_admission"}


def test_ctl001_exempts_sanctioned_packages_and_self_forwards():
    # the control plane, the paramserver package (manual runbook paths),
    # tests, and bench harnesses all legitimately actuate
    for path in ("pkg/control/policies.py", "pkg/paramserver/training.py",
                 "tests/test_x.py", "bench.py"):
        assert lint_src(_CTL_SRC, path=path) == []
    # self.* forward: the definition pattern (ServedModel.set_admission
    # delegating to its own batcher), not an automated action
    assert lint_src("""
        class Served:
            def set_admission(self, **kw):
                return self.batcher.set_admission(**kw)
        """, path="pkg/serving/registry.py") == []
    # unrelated methods that merely share a name fragment stay silent
    assert lint_src("""
        def f(video):
            video.restart_playback()
        """, path="pkg/serving/engine.py") == []


def test_ctl001_pragma_suppresses():
    src = _CTL_SRC.replace("group.scale_to(4)",
                           "group.scale_to(4)  # tpulint: disable=CTL001")
    fs = lint_src(src, path="pkg/serving/engine.py")
    assert rule_ids(fs) == ["CTL001"] * 3


# --------------------------------------------------------------- pragmas
def test_line_pragma_suppresses_named_rule_only():
    src = """
        def f(x):
            try:
                return x()
            except Exception:  # tpulint: disable=EXC001
                pass
        """
    assert lint_src(src) == []
    # a pragma naming a DIFFERENT rule does not suppress
    assert rule_ids(lint_src(src.replace("EXC001", "THR001"))) == ["EXC001"]
    # bare disable suppresses every rule on the line
    bare = src.replace(" disable=EXC001", " disable")
    assert lint_src(bare) == []


# ----------------------------------------------------- baseline mechanics
_VIOLATION = textwrap.dedent("""
    def f(x):
        try:
            return x()
        except Exception:
            pass
    """)


def test_baseline_round_trip_and_ratchet(tmp_path):
    mod = tmp_path / "mod.py"
    mod.write_text(_VIOLATION)
    bl_path = tmp_path / "baseline.json"
    linter = Linter(root=str(tmp_path))

    first = linter.run([str(mod)])
    assert len(first.new) == 1 and first.exit_code == 1
    save_baseline(str(bl_path), first.new)

    # round-trip: same code, baselined, exits 0
    bl = load_baseline(str(bl_path))
    again = linter.run([str(mod)], baseline=bl)
    assert again.new == [] and len(again.baselined) == 1
    assert again.exit_code == 0

    # ratchet: a SECOND identical violation exceeds the baselined count
    mod.write_text(_VIOLATION + _VIOLATION.replace("def f", "def g"))
    worse = linter.run([str(mod)], baseline=bl)
    assert len(worse.new) == 1 and len(worse.baselined) == 1
    assert worse.exit_code == 1

    # fixing the code leaves a stale entry — reported, never fatal
    mod.write_text("def f(x):\n    return x()\n")
    fixed = linter.run([str(mod)], baseline=bl)
    assert fixed.new == [] and fixed.exit_code == 0
    assert len(fixed.stale_baseline) == 1

    # staleness is scoped: a run that never visited the entry's file (or
    # never ran its rule) must not advise deleting it
    other = tmp_path / "other.py"
    other.write_text("x = 1\n")
    subset = linter.run([str(other)], baseline=bl)
    assert subset.stale_baseline == []
    mod.write_text(_VIOLATION)
    ruled = Linter(rules=["THR001"], root=str(tmp_path))
    assert ruled.run([str(mod)], baseline=bl).stale_baseline == []


def test_baseline_fingerprint_survives_line_shift(tmp_path):
    mod = tmp_path / "mod.py"
    mod.write_text(_VIOLATION)
    linter = Linter(root=str(tmp_path))
    bl_path = tmp_path / "baseline.json"
    save_baseline(str(bl_path), linter.run([str(mod)]).new)
    # prepend 20 lines: line numbers move, fingerprints don't
    mod.write_text("# padding\n" * 20 + _VIOLATION)
    res = linter.run([str(mod)], baseline=load_baseline(str(bl_path)))
    assert res.new == [] and len(res.baselined) == 1


# ------------------------------------------------------------ JSON output
def test_json_output_schema_and_determinism(tmp_path):
    mod = tmp_path / "mod.py"
    mod.write_text(_VIOLATION)
    linter = Linter(root=str(tmp_path))
    d1 = linter.run([str(mod)]).to_dict()
    d2 = linter.run([str(mod)]).to_dict()
    assert d1 == d2                                    # deterministic
    assert d1["version"] == 1 and d1["tool"] == "tpulint"
    assert d1["files_checked"] == 1
    assert d1["new_count"] == 1 and d1["baselined_count"] == 0
    (f,) = d1["findings"]
    assert set(f) == {"rule", "path", "line", "col", "message", "snippet",
                      "baselined"}
    assert f["rule"] == "EXC001" and f["baselined"] is False
    assert f["path"] == "mod.py" and f["line"] >= 1
    json.dumps(d1)                                     # serializable


# ------------------------------------------------ pre-commit fast path
def test_lint_changed_empty_diff_exits_zero_without_linting(monkeypatch,
                                                            capsys):
    """Pre-commit wiring pin (docs/STATIC_ANALYSIS.md runbook): `lint
    --changed` on an empty diff exits 0 FAST — it must return before
    constructing a Linter (no rule imports, no file parses), so the
    hook costs nothing when there is nothing to check."""
    from deeplearning4j_tpu import main as main_mod
    from deeplearning4j_tpu import analysis as analysis_mod
    monkeypatch.setattr(main_mod, "_changed_files", lambda root: [])

    def _boom(*a, **k):
        raise AssertionError(
            "an empty --changed diff must not construct a Linter")

    monkeypatch.setattr(analysis_mod, "Linter", _boom)
    assert main_mod.main(["lint", "--changed"]) == 0
    assert "no changed python files" in capsys.readouterr().out


# -------------------------------------------------- self-hosting (tier-1)
def test_package_lints_clean_against_shipped_baseline():
    """THE tier-1 guard: any new JAX001/JAX002/THR001/THR002/EXC001
    violation anywhere in deeplearning4j_tpu/ fails here. Fix the code,
    pragma the line with a reason, or (exceptionally) extend
    analysis/baseline.json in the same PR with a written reason."""
    res = Linter().run([PACKAGE_ROOT],
                       baseline=load_baseline(DEFAULT_BASELINE_PATH))
    assert res.files_checked > 100
    assert res.new == [], "new tpulint findings:\n" + "\n".join(
        f.render() for f in res.new)
    # the baseline is ratchet-only: entries for fixed code must be removed
    assert res.stale_baseline == [], (
        "stale baseline entries (delete them from analysis/baseline.json):"
        f" {res.stale_baseline}")


def test_shipped_baseline_entries_are_documented():
    with open(DEFAULT_BASELINE_PATH, encoding="utf-8") as fh:
        data = json.load(fh)
    assert data["findings"], "baseline exists to demonstrate the workflow"
    for e in data["findings"]:
        assert e["rule"] in RULE_IDS, e
        assert len(e.get("reason", "")) > 20, \
            f"baseline entry needs a written reason: {e}"


def test_write_baseline_preserves_reasons(tmp_path):
    """A ratchet reset (`lint --write-baseline`) must keep the surviving
    entries' written reasons — they are the documentation the tier-1
    documented-reason test enforces."""
    from deeplearning4j_tpu.analysis import load_baseline_reasons
    from deeplearning4j_tpu.main import main as cli_main
    mod = tmp_path / "mod.py"
    mod.write_text(_VIOLATION)
    bl = tmp_path / "bl.json"
    bl.write_text(json.dumps({"version": 1, "findings": [{
        "rule": "EXC001", "path": "mod.py",
        "snippet": "except Exception:",
        "reason": "a deliberately-grandfathered fixture entry"}]}))
    # NB: the CLI relativizes against the repo root, so drive the rewrite
    # through save_baseline the way cmd_lint does
    from deeplearning4j_tpu.analysis import Linter, save_baseline
    linter = Linter(root=str(tmp_path))
    res = linter.run([str(mod)], baseline=load_baseline(str(bl)))
    assert res.baselined and not res.new
    save_baseline(str(bl), res.new + res.baselined,
                  reasons=load_baseline_reasons(str(bl)))
    data = json.loads(bl.read_text())
    (entry,) = data["findings"]
    assert entry["reason"] == "a deliberately-grandfathered fixture entry"
    assert cli_main is not None


def test_jax001_same_name_in_other_scope_not_dragged_in():
    """Scope-aware wrap resolution: `jax.jit(step)` marks the `step` it
    can lexically see, not every same-named eager def in the module."""
    fs = lint_src("""
        import jax

        def jitted_factory():
            def step(p):
                return p.item()          # traced: flagged
            return jax.jit(step)

        def eager_factory():
            def step(x):
                return float(x)          # eager helper: NOT flagged
            return step
        """)
    assert rule_ids(fs) == ["JAX001"]
    assert "item" in fs[0].message


def test_thr001_nested_locks_report_once():
    fs = lint_src("""
        import threading
        import time

        class A:
            def f(self):
                with self._lock:
                    with self._send_lock:
                        time.sleep(0.1)
        """)
    assert rule_ids(fs) == ["THR001"]


def test_cli_select_trailing_comma_and_unknown_rule(tmp_path, capsys):
    from deeplearning4j_tpu.main import main as cli_main
    ok = tmp_path / "ok.py"
    ok.write_text("x = 1\n")
    assert cli_main(["lint", str(ok), "--select", "THR001,"]) == 0
    capsys.readouterr()
    with pytest.raises(SystemExit) as ei:
        cli_main(["lint", str(ok), "--select", "NOPE999"])
    assert "unknown rule" in str(ei.value)


def test_cli_write_baseline_refuses_subset_runs(tmp_path):
    from deeplearning4j_tpu.main import main as cli_main
    mod = tmp_path / "mod.py"
    mod.write_text("x = 1\n")
    for argv in (["lint", str(mod), "--write-baseline"],
                 ["lint", "--select", "THR001", "--write-baseline"]):
        with pytest.raises(SystemExit) as ei:
            cli_main(argv)
        assert "full default run" in str(ei.value)


def test_unreadable_file_reports_finding_not_crash(tmp_path, capsys):
    from deeplearning4j_tpu.main import main as cli_main
    ok = tmp_path / "ok.py"
    ok.write_text("x = 1\n")
    bad = tmp_path / "bad.py"
    bad.write_bytes(b"\xff\xfe\x00garbage")     # not UTF-8
    assert cli_main(["lint", str(tmp_path), "--no-baseline"]) == 1
    out = capsys.readouterr().out
    assert "cannot read file" in out
    assert "2 files" in out                      # ok.py still got linted
    assert cli_main(["lint", str(tmp_path / "nope.py")]) == 1
    assert "cannot read file" in capsys.readouterr().out


# --------------------------------------------- JAX003 bare jax.jit sites
def test_jax003_flags_call_decorator_and_partial_forms():
    src = """
        import jax
        from functools import partial

        def build(step):
            return jax.jit(step, donate_argnums=(0,))

        @jax.jit
        def f(x):
            return x

        @partial(jax.jit, donate_argnums=(0,))
        def g(x):
            return x
        """
    fs = lint_src(src, path="deeplearning4j_tpu/somemod.py")
    assert rule_ids(fs) == ["JAX003"] * 3
    assert "monitored_jit" in fs[0].message


def test_jax003_flags_bare_jit_import():
    fs = lint_src("""
        from jax import jit

        def build(step):
            return jit(step)
        """, path="deeplearning4j_tpu/somemod.py")
    assert rule_ids(fs) == ["JAX003"]


def test_jax003_follows_module_aliases():
    # `import jax as j; j.jit(...)` must not evade the guard
    fs = lint_src("""
        import jax as j

        def build(step):
            return j.jit(step)
        """, path="deeplearning4j_tpu/somemod.py")
    assert rule_ids(fs) == ["JAX003"]


def test_jax003_accepts_monitored_jit_and_exempt_paths():
    src = """
        from deeplearning4j_tpu.monitor.jitwatch import monitored_jit

        def build(step):
            return monitored_jit(step, name="area/step", donate_argnums=(0,))
        """
    assert lint_src(src, path="deeplearning4j_tpu/somemod.py") == []
    bare = """
        import jax

        def build(step):
            return jax.jit(step)
        """
    # tests/ and jitwatch.py itself are exempt by design
    assert lint_src(bare, path="tests/test_x.py") == []
    assert lint_src(bare,
                    path="deeplearning4j_tpu/monitor/jitwatch.py") == []
    assert lint_src(bare, path="deeplearning4j_tpu/x.py") != []


def test_jax001_follows_monitored_jit_wrapped_defs():
    # the migration must not blind JAX001: a monitored_jit-wrapped def is
    # just as traced as a jax.jit-wrapped one
    fs = lint_src("""
        from ..monitor.jitwatch import monitored_jit

        def build(self):
            def step(x):
                return float(x.sum())
            return monitored_jit(step, name="mln/step")
        """, rules=["JAX001"])
    assert rule_ids(fs) == ["JAX001"]


# ---------------------------------- JAX004 raw Mesh/shard_map construction
def test_jax004_flags_raw_mesh_and_shard_map_calls():
    src = """
        import jax
        from jax.sharding import Mesh
        from jax import shard_map
        import numpy as np

        def build(devices, fn, mesh):
            m1 = Mesh(np.asarray(devices).reshape(4, 2), ("data", "model"))
            m2 = jax.sharding.Mesh(np.asarray(devices), ("data",))
            stepped = shard_map(fn, mesh=mesh, in_specs=(), out_specs=())
            return m1, m2, stepped
        """
    fs = lint_src(src, path="deeplearning4j_tpu/serving/somemod.py")
    assert rule_ids(fs) == ["JAX004"] * 3
    assert "MeshSpec" in fs[0].message


def test_jax004_flags_jax_shard_map_and_module_form():
    fs = lint_src("""
        import jax
        from jax.experimental import shard_map as smod

        def build(fn, mesh):
            a = jax.shard_map(fn, mesh=mesh, in_specs=(), out_specs=())
            b = smod.shard_map(fn, mesh=mesh, in_specs=(), out_specs=())
            return a, b
        """, path="deeplearning4j_tpu/somemod.py")
    assert rule_ids(fs) == ["JAX004"] * 2


def test_jax004_follows_sharding_module_aliases():
    # `import jax.sharding as jsh` / `from jax import sharding` must not
    # evade the guard (review finding)
    fs = lint_src("""
        import numpy as np
        import jax.sharding as jsh
        from jax import sharding

        def build(devices):
            a = jsh.Mesh(np.asarray(devices), ("data",))
            b = sharding.Mesh(np.asarray(devices), ("data",))
            return a, b
        """, path="deeplearning4j_tpu/somemod.py")
    assert rule_ids(fs) == ["JAX004"] * 2


def test_jax004_exempts_substrate_tests_and_annotations():
    raw = """
        import numpy as np
        from jax.sharding import Mesh
        from jax import shard_map

        def build(devices, fn, mesh):
            m = Mesh(np.asarray(devices), ("data",))
            return m, shard_map(fn, mesh=mesh, in_specs=(), out_specs=())
        """
    # the substrate package and tests are exempt by design
    assert lint_src(raw, path="deeplearning4j_tpu/parallel/mesh.py") == []
    assert lint_src(raw, path="deeplearning4j_tpu/parallel/wrapper.py") == []
    assert lint_src(raw, path="tests/test_x.py") == []
    assert lint_src(raw, path="deeplearning4j_tpu/x.py") != []
    # a Mesh type ANNOTATION is not a construction — only calls flag
    ann = """
        from jax.sharding import Mesh

        def use(mesh: Mesh) -> Mesh:
            return mesh
        """
    assert lint_src(ann, path="deeplearning4j_tpu/x.py") == []
    # an unrelated object's own .shard_map method must not flag — only
    # jax module roots are constructors (review finding)
    own = """
        class Router:
            def shard_map(self, fn):
                return fn

            def go(self, fn):
                return self.shard_map(fn)
        """
    assert lint_src(own, path="deeplearning4j_tpu/x.py") == []
    # routed through the substrate: clean
    good = """
        from .parallel.mesh import MeshSpec, make_mesh

        def build(devices):
            return MeshSpec(axes=("data", "model"),
                            devices=devices).build()
        """
    assert lint_src(good, path="deeplearning4j_tpu/x.py") == []


def test_jax004_pragma_suppression():
    src = """
        import numpy as np
        from jax.sharding import Mesh

        def build(devices):
            return Mesh(np.asarray(devices), ("data",))  # tpulint: disable=JAX004
        """
    assert lint_src(src, path="deeplearning4j_tpu/x.py") == []


# ---------------------------------------------------------------- MON001
def test_mon001_metric_name_unit_suffix_convention():
    """ISSUE 10: counters end _total, gauges must not, histograms carry a
    unit suffix, _seconds histograms pass unit="s", and unit tokens sit
    at the END of the name (or right before a counter's _total)."""
    bad = lint_src("""
        reg.counter("requests")
        reg.gauge("stuff_total")
        reg.histogram("lat")
        reg.histogram("wait_seconds")
        reg.gauge("device_memory_bytes_in_use")
        """, rules=["MON001"])
    assert rule_ids(bad) == ["MON001"] * 5

    clean = lint_src("""
        reg.counter("requests_total")
        reg.counter("wire_bytes_total")
        reg.counter(f"paramserver_{k}_total")
        reg.gauge("queue_depth")
        reg.histogram("lat_ms", op="push")
        reg.histogram("wait_seconds", unit="s")
        reg.histogram("frame_bytes")
        reg.histogram("batch_examples")
        reg.histogram(f"shard_lat_{suffix}")
        """, rules=["MON001"])
    assert clean == []

    # dynamic names and non-registry callees are out of scope
    assert lint_src("""
        reg.counter(name)
        counter("oops")
        somedict.histogram()
        """, rules=["MON001"]) == []

    # pragma suppression rides the shared machinery
    assert lint_src("""
        reg.counter("legacy")  # tpulint: disable=MON001
        """, rules=["MON001"]) == []


def test_mon001_serving_cache_series_must_be_counters():
    """ISSUE 11 satellite: the response-cache hit/miss series are
    monotonic events — any non-counter spelling (or a counter without
    _total) breaks the hit-rate math /profile and the bench derive from
    counter deltas."""
    bad = lint_src("""
        reg.gauge("serving_cache_hits")
        reg.histogram("serving_cache_misses_ms")
        reg.counter("serving_cache_hits")
        """, rules=["MON001"])
    assert rule_ids(bad) == ["MON001"] * 3
    assert all("serving_cache" in f.message for f in bad)

    assert lint_src("""
        reg.counter("serving_cache_hits_total", model=name)
        reg.counter("serving_cache_misses_total", model=name)
        reg.gauge("serving_cache_examples")
        reg.counter(f"shard_cache_hits_{suffix}")
        """, rules=["MON001"]) == []
