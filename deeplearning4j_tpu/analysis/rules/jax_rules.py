"""JAX hazards: host-sync barriers under trace, PRNG key reuse.

Calibrated for this repo's idioms: jit shows up both as a decorator
(``@jax.jit``) and — dominantly — as ``jax.jit(step, donate_argnums=...)``
wrapping a locally-defined function (``nn/multilayer.py``, ``nn/graph.py``,
``paramserver/training.py``), so JAX001 resolves first-argument names back
to ``def``\\ s in the same module. PRNG flows through ``rng``/``key``
threading with ``jax.random.split``/``fold_in`` (``nn/layers/*``), so
JAX002 treats ``split`` as a *consuming* use (feeding a key to ``split``
and then to ``normal`` correlates the draws) but exempts ``fold_in``
(reuse with distinct fold data is the sanctioned pattern,
``nn/layers/base.py``).
"""
from __future__ import annotations

import ast
from typing import Iterator, List, Optional, Sequence, Set, Tuple

from . import Rule, register, terminal_name, assigned_names

# attrs that CONSUME a key's entropy; same key into two of these (without a
# rebinding split in between) repeats the stream
_KEY_EXEMPT = {"PRNGKey", "key", "fold_in", "key_data", "wrap_key_data",
               "key_impl", "clone"}
# host-sync method calls: each forces the device queue to drain
_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
_NUMPY_NAMES = {"np", "numpy", "onp"}


def _walk_pruned(root: ast.AST):
    """ast.walk minus nested function/lambda/class subtrees — those are
    separate execution scopes."""
    stack = [root]
    while stack:
        node = stack.pop()
        yield node
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda, ast.ClassDef)):
                continue
            stack.append(child)


def _is_jit_expr(node: ast.AST) -> bool:
    """``jit`` / ``jax.jit`` (any attribute chain ending in .jit), plus the
    package's own ``monitored_jit`` wrapper (``monitor/jitwatch.py``) — a
    function routed through jitwatch is every bit as traced as a bare-jit
    one, so JAX001's barrier analysis must follow it."""
    return terminal_name(node) in ("jit", "monitored_jit")


def _jit_decorated(fn: ast.AST) -> bool:
    for dec in getattr(fn, "decorator_list", ()):
        if _is_jit_expr(dec):
            return True
        if isinstance(dec, ast.Call):
            if _is_jit_expr(dec.func):          # @jax.jit(static_argnums=…)
                return True
            if terminal_name(dec.func) == "partial" and any(
                    _is_jit_expr(a) for a in dec.args):
                return True
    return False


@register
class HostSyncInJit(Rule):
    id = "JAX001"
    title = "host-sync barrier inside a jit-traced function"
    rationale = (
        "float()/.item()/.tolist()/.block_until_ready()/np.asarray on a "
        "traced value either crashes at trace time (ConcretizationTypeError)"
        " or, via a constant-folded escape hatch, silently pins a host "
        "round-trip into the hot step. The repo's contract (docs/"
        "OBSERVABILITY.md) is that the ONE sanctioned device→host fetch per "
        "step is the fit loop's float(loss), placed inside the step span — "
        "traced code must stay barrier-free.")

    def check(self, tree, lines, path) -> Iterator:
        traced: List[ast.AST] = []
        # scope-aware wrap resolution: `jax.jit(step, ...)` marks the
        # `def step` of the SAME scope as traced (the repo idiom is both
        # inside one factory function) — a same-named eager def in another
        # factory must not be dragged in
        self._collect_scope(tree, traced)
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and _jit_decorated(node):
                traced.append(node)
        seen: Set[tuple] = set()
        for fn in traced:
            for f in self._scan(fn, lines, path):
                key = (f.line, f.col)
                if key not in seen:          # nested traced defs overlap
                    seen.add(key)
                    yield f

    def _collect_scope(self, scope: ast.AST, traced: List[ast.AST],
                       inherited: Optional[dict] = None):
        """One execution scope: a jit call here marks the def it can SEE
        (defined here or in a lexically enclosing scope — closure
        capture) as traced, plus lambdas passed to jit directly. Nested
        defs/classes are their own scopes (recursed into) — so an eager
        helper that merely shares a jitted def's name in some unrelated
        scope is never dragged in."""
        visible = dict(inherited or {})
        wrapped: Set[str] = set()
        child_scopes: List[ast.AST] = []
        stack = list(ast.iter_child_nodes(scope))
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                if not isinstance(node, ast.ClassDef):
                    visible[node.name] = node       # local shadows outer
                child_scopes.append(node)
                continue               # its body is a separate scope
            if isinstance(node, ast.Lambda):
                continue               # bare lambda body: separate scope
            if isinstance(node, ast.Call) and _is_jit_expr(node.func) \
                    and node.args:
                target = node.args[0]
                if isinstance(target, ast.Name):
                    wrapped.add(target.id)
                elif isinstance(target, ast.Lambda):
                    traced.append(target)
            stack.extend(ast.iter_child_nodes(node))
        for name in wrapped:
            if name in visible:
                traced.append(visible[name])
        for child in child_scopes:
            # class bodies are not closure scopes: methods see what the
            # CLASS saw, not their sibling methods
            self._collect_scope(
                child, traced,
                inherited if isinstance(scope, ast.ClassDef) else visible)

    def _scan(self, fn: ast.AST, lines, path):
        body = fn.body if isinstance(fn.body, list) else [fn.body]
        for stmt in body:
            for node in ast.walk(stmt):
                if not isinstance(node, ast.Call):
                    continue
                callee = terminal_name(node.func)
                if callee == "float" and isinstance(node.func, ast.Name):
                    if node.args and not isinstance(node.args[0],
                                                    ast.Constant):
                        yield self.finding(
                            node, lines, path,
                            "float() inside a jit-traced function is a "
                            "device→host sync barrier (or a trace-time "
                            "crash); compute on-device and fetch once, "
                            "outside the traced step")
                elif isinstance(node.func, ast.Attribute) \
                        and callee in _SYNC_METHODS and not node.args:
                    yield self.finding(
                        node, lines, path,
                        f".{callee}() inside a jit-traced function forces "
                        f"a host round-trip; keep traced code barrier-free")
                elif isinstance(node.func, ast.Attribute) \
                        and callee in {"asarray", "array", "frombuffer"}:
                    base = node.func.value
                    if isinstance(base, ast.Name) \
                            and base.id in _NUMPY_NAMES:
                        yield self.finding(
                            node, lines, path,
                            f"np.{callee}() inside a jit-traced function "
                            f"materializes on host; use jnp.{callee} (or "
                            f"move the conversion outside the trace)")
                elif callee == "device_get":
                    yield self.finding(
                        node, lines, path,
                        "jax.device_get inside a jit-traced function is a "
                        "host transfer; fetch outside the traced step")


@register
class PRNGKeyReuse(Rule):
    id = "JAX002"
    title = "PRNG key fed to two jax.random consumers without a split"
    rationale = (
        "jax.random is splittable, not stateful: the same key yields the "
        "SAME draw from every consumer, so dropout masks repeat, VAE "
        "samples collapse, and init correlates across layers — silently. "
        "The sanctioned flow (nn/layers/*) is split/fold_in per consumer: "
        "`k1, k2 = jax.random.split(rng)`, never rng twice.")

    def check(self, tree, lines, path) -> Iterator:
        consumer_bare = self._bare_imports(tree)
        scopes: List[ast.AST] = [tree]
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scopes.append(node)
        for scope in scopes:
            yield from self._scan_scope(scope, consumer_bare, lines, path)

    @staticmethod
    def _bare_imports(tree) -> Set[str]:
        """Names imported with `from jax.random import X` count as
        consumers when called bare."""
        out: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) \
                    and node.module == "jax.random":
                for a in node.names:
                    name = a.asname or a.name
                    if a.name not in _KEY_EXEMPT:
                        out.add(name)
        return out

    @staticmethod
    def _is_consumer(call: ast.Call, bare: Set[str]) -> bool:
        f = call.func
        if isinstance(f, ast.Name):
            return f.id in bare
        if not isinstance(f, ast.Attribute) or f.attr in _KEY_EXEMPT:
            return False
        base = f.value
        if isinstance(base, ast.Attribute) and base.attr == "random" \
                and terminal_name(base.value) == "jax":
            return True              # jax.random.X / xxx.jax.random.X
        if isinstance(base, ast.Name) and base.id in {"jrandom", "jr"}:
            return True              # import jax.random as jrandom
        return False

    def _scan_scope(self, scope, bare, lines, path):
        """Branch-aware linear scan. State maps key name → line of the use
        that consumed it (cleared on rebinding). `if`/`try` arms run on
        COPIES of the incoming state and merge by union afterwards, so
        mutually-exclusive consumers (the RBM sampler's if/elif arms in
        nn/layers/feedforward.py) never conflict, while a use AFTER the
        branch still conflicts with a use on either arm."""
        findings: List = []
        # cross-iteration pass bookkeeping
        loop_uses: List[Tuple[str, ast.AST, frozenset]] = []
        loop_stack: List[ast.AST] = []
        bound_in_loop: dict = {}   # name -> {id(loop) where it's rebound}

        def consumed_key(call: ast.Call):
            key = call.args[0] if call.args else None
            if key is None:
                for kw in call.keywords:
                    if kw.arg == "key":
                        key = kw.value
            return key.id if isinstance(key, ast.Name) else None

        def apply_expr(expr, state):
            """Uses (in walk order) then walrus-assigns for one
            expression tree; nested scopes excluded."""
            if expr is None:
                return
            for node in _walk_pruned(expr):
                if isinstance(node, ast.Call) \
                        and self._is_consumer(node, bare):
                    name = consumed_key(node)
                    if name is None:
                        continue
                    if name in state:
                        findings.append(self.finding(
                            node, lines, path,
                            f"PRNG key {name!r} already consumed at line "
                            f"{state[name]}; split it first (`k1, k2 = "
                            f"jax.random.split({name})`) — reusing a key "
                            f"repeats the exact same draw"))
                    else:
                        state[name] = node.lineno
                    if loop_stack:
                        loop_uses.append(
                            (name, node,
                             frozenset(id(lp) for lp in loop_stack)))
                elif isinstance(node, ast.NamedExpr):
                    note_assign(assigned_names(node), state)

        def note_assign(names, state):
            for n in names:
                state.pop(n, None)
                for lp in loop_stack:
                    bound_in_loop.setdefault(n, set()).add(id(lp))

        def merge(into, *branches):
            # union of consumed keys: reuse after the join conflicts with
            # a consumer on ANY arm
            for st in branches:
                for n, line in st.items():
                    into[n] = max(line, into.get(n, 0))
            return into

        def analyze_block(stmts, state):
            """Returns True when the block always leaves the enclosing
            flow (return/raise/break/continue) — a terminated arm's state
            must not merge into the join, so guard-style sequential
            ``if …: return consume(key)`` arms never conflict."""
            for stmt in stmts:
                if analyze_stmt(stmt, state):
                    return True
            return False

        def analyze_stmt(stmt, state):
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                return False                        # separate scope
            if isinstance(stmt, ast.If):
                apply_expr(stmt.test, state)
                s1, s2 = dict(state), dict(state)
                t1 = analyze_block(stmt.body, s1)
                t2 = analyze_block(stmt.orelse, s2)
                live = [s for s, t in ((s1, t1), (s2, t2)) if not t]
                state.clear()
                merge(state, *live)
                return not live
            if isinstance(stmt, (ast.For, ast.AsyncFor)):
                apply_expr(stmt.iter, state)
                loop_stack.append(stmt)
                body_state = dict(state)
                # the for target rebinds every iteration, inside the loop
                tgt = [terminal_name(t) for t in ast.walk(stmt.target)
                       if isinstance(t, (ast.Name, ast.Attribute))]
                note_assign([t for t in tgt if t], body_state)
                t1 = analyze_block(stmt.body, body_state)
                loop_stack.pop()
                analyze_block(stmt.orelse, state)
                if not t1:            # zero-iteration path keeps `state`
                    merge(state, body_state)
                return False
            if isinstance(stmt, ast.While):
                apply_expr(stmt.test, state)
                loop_stack.append(stmt)
                body_state = dict(state)
                t1 = analyze_block(stmt.body, body_state)
                loop_stack.pop()
                analyze_block(stmt.orelse, state)
                if not t1:
                    merge(state, body_state)
                return False
            if isinstance(stmt, ast.Try):
                s1 = dict(state)
                t1 = analyze_block(stmt.body, s1)
                arms = [(s1, t1)]
                for h in stmt.handlers:
                    sh = dict(state)
                    arms.append((sh, analyze_block(h.body, sh)))
                so = dict(s1)
                to = t1 or analyze_block(stmt.orelse, so)
                arms.append((so, to))
                live = [s for s, t in arms if not t]
                state.clear()
                merge(state, *live)
                tfin = analyze_block(stmt.finalbody, state)
                return tfin or not live
            if isinstance(stmt, (ast.With, ast.AsyncWith)):
                for item in stmt.items:
                    apply_expr(item.context_expr, state)
                    if item.optional_vars is not None:
                        n = terminal_name(item.optional_vars)
                        if n:
                            note_assign([n], state)
                return analyze_block(stmt.body, state)
            # simple statement: uses from the expression parts, then the
            # statement-level bindings
            apply_expr(stmt, state)
            note_assign(assigned_names(stmt), state)
            return isinstance(stmt, (ast.Return, ast.Raise, ast.Break,
                                     ast.Continue))

        analyze_block(scope.body, {})
        yield from findings
        # loop reuse: a consumer inside a loop whose key is never rebound
        # within ANY enclosing loop draws the SAME value every iteration
        for name, node, loops in loop_uses:
            if not (bound_in_loop.get(name, set()) & loops):
                yield self.finding(
                    node, lines, path,
                    f"PRNG key {name!r} consumed inside a loop but never "
                    f"rebound there — every iteration repeats the same "
                    f"draw; split or fold_in per iteration")


@register
class BareJit(Rule):
    id = "JAX003"
    title = "bare jax.jit not routed through monitored_jit"
    rationale = (
        "A bare jax.jit compiles invisibly: no compile counter, no "
        "compile-time histogram, no compile/<fn> span on /trace, no "
        "cost_analysis capture, and — critically — no retrace-storm "
        "detection, so shape/dtype churn silently re-traces the step and "
        "training gets 10x slower with nothing on /metrics to say why. "
        "monitor.jitwatch.monitored_jit(name=...) is a drop-in wrapper "
        "that records all of the above (docs/OBSERVABILITY.md "
        "'Compilation & memory'). Exempt: tests/ and jitwatch.py itself "
        "(the one sanctioned jax.jit call). Ratchet-only via "
        "analysis/baseline.json for sites that genuinely cannot migrate.")

    def check(self, tree, lines, path) -> Iterator:
        p = path.replace("\\", "/")
        if "tests" in p.split("/") or p.endswith("monitor/jitwatch.py"):
            return
        # `from jax import jit [as alias]` makes the bare name a jit
        # ref; `import jax as j` makes `j.jit` one (evading the guard
        # through a module alias must not lint clean)
        bare: Set[str] = set()
        jax_mods: Set[str] = {"jax"}
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.module == "jax":
                for a in node.names:
                    if a.name == "jit":
                        bare.add(a.asname or "jit")
            elif isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "jax" and a.asname:
                        jax_mods.add(a.asname)
        # flagging the REFERENCE (Attribute/Name), not just calls, covers
        # every spelling in one pass: jax.jit(f, ...), @jax.jit,
        # @jax.jit(static_argnums=...), functools.partial(jax.jit, ...)
        seen: Set[tuple] = set()
        for node in ast.walk(tree):
            hit = None
            if isinstance(node, ast.Attribute) and node.attr == "jit" \
                    and terminal_name(node.value) in jax_mods:
                hit = node
            elif isinstance(node, ast.Name) and node.id in bare \
                    and isinstance(node.ctx, ast.Load):
                hit = node
            if hit is None:
                continue
            key = (hit.lineno, hit.col_offset)
            if key in seen:
                continue
            seen.add(key)
            yield self.finding(
                hit, lines, path,
                "bare jax.jit — route it through monitor.jitwatch."
                "monitored_jit(name=\"area/fn\") so compiles are counted, "
                "timed, traced, cost-profiled, and retrace-storm-watched")


@register
class RawMeshConstruction(Rule):
    id = "JAX004"
    title = "raw Mesh/shard_map construction outside the parallel substrate"
    rationale = (
        "parallel/mesh.py is the ONE sanctioned mesh construction site: a "
        "MeshSpec validates axis names, auto-factorizes extents over the "
        "available devices (a raw Mesh(...) reshape silently builds the "
        "degenerate [n, 1, ...] topology or crashes on a non-dividing "
        "shape), stays multi-process consistent, and registers the "
        "topology on GET /profile's mesh block. A raw "
        "jax.sharding.Mesh(...) or shard_map(...) call outside "
        "parallel/ bypasses all of that — the fit runs on a topology no "
        "operator can see and no validation ever checked. Route meshes "
        "through parallel.mesh (MeshSpec/make_mesh) and shard_map-style "
        "steps through the parallel/ step factories. Exempt: tests/ and "
        "the parallel/ substrate package itself. "
        "Ratchet-only via analysis/baseline.json for sites that "
        "genuinely cannot migrate.")

    def check(self, tree, lines, path) -> Iterator:
        p = path.replace("\\", "/")
        parts = p.split("/")
        if "tests" in parts or "parallel" in parts:
            return
        # names bound to the constructors by import: `from jax.sharding
        # import Mesh [as m]`, `from jax.experimental.shard_map import
        # shard_map`, `from jax import shard_map`
        mesh_names: Set[str] = set()
        sm_names: Set[str] = set()
        jax_mods: Set[str] = {"jax"}
        # module aliases whose .shard_map attribute IS the constructor
        # (`from jax.experimental import shard_map as smod`,
        # `import jax.experimental.shard_map as sm`) — an unrelated
        # object's own .shard_map method must NOT flag
        sm_mods: Set[str] = set()
        # aliases of the jax.sharding MODULE itself (`import jax.sharding
        # as jsh`, `from jax import sharding [as x]`) — jsh.Mesh(...) is
        # just as raw as jax.sharding.Mesh(...)
        sharding_mods: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.module:
                mod = node.module
                for a in node.names:
                    bound = a.asname or a.name
                    if a.name == "Mesh" and mod.startswith("jax"):
                        mesh_names.add(bound)
                    elif a.name == "shard_map":
                        if mod.startswith("jax"):
                            sm_names.add(bound)
                        if mod == "jax.experimental":
                            sm_mods.add(bound)   # module, not function
                    elif a.name == "sharding" and mod == "jax":
                        sharding_mods.add(bound)
            elif isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "jax" and a.asname:
                        jax_mods.add(a.asname)
                    elif a.name == "jax.experimental.shard_map":
                        sm_mods.add(a.asname or "shard_map")
                    elif a.name == "jax.sharding" and a.asname:
                        sharding_mods.add(a.asname)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            hit = None
            if isinstance(f, ast.Name):
                if f.id in mesh_names:
                    hit = "Mesh"
                elif f.id in sm_names:
                    hit = "shard_map"
            elif isinstance(f, ast.Attribute):
                root = terminal_name(f.value)
                if f.attr == "Mesh" and (
                        root in jax_mods
                        or (isinstance(f.value, ast.Name)
                            and f.value.id in sharding_mods)
                        or (isinstance(f.value, ast.Attribute)
                            and f.value.attr == "sharding")):
                    hit = "Mesh"          # jax.sharding.Mesh / jsh.Mesh
                elif f.attr == "shard_map" and (
                        root in jax_mods or root in sm_mods):
                    hit = "shard_map"     # jax.shard_map / smod.shard_map
            if hit is None:
                continue
            yield self.finding(
                node, lines, path,
                f"raw {hit}(...) outside the parallel/ substrate — build "
                f"meshes with parallel.mesh.MeshSpec/make_mesh (validated, "
                f"auto-factorized, visible on /profile) and mapped steps "
                f"through the parallel/ step factories")
