"""G001 host-sync-in-round-path and G007 blocking-call-on-dispatch-thread.

Both enforce the async runner's core promise (runner/loop.py): the dispatch
path never hides a host synchronization, and the only sanctioned sync points
are the declared drain points — functions carrying `# graftlint:
drain-point` above their `def` (the batched-metrics drain, commit, eval, the
one-shot RTT probe, the serving queue's quorum wait). Everything else that
forces a device round-trip or blocks the thread must either move behind a
drain boundary or carry an explicit, justified suppression.

G007's reachability is PACKAGE-level: from the dispatch-path roots it
follows same-module calls AND import bindings (`from .helper import fn`,
`mod.fn()` through `from . import mod`) into other modules of the package,
depth-bounded — a `time.sleep` smuggled behind a helper import is the same
stall as an inline one. Drain-point declarations and explicit G007 disables
in the HELPER module stop the traversal (that is how serve/transport.py
declares its sanctioned blocking points in code).
"""

from __future__ import annotations

import ast

from . import dataflow
from .core import PACKAGE, Rule, SourceFile, Violation

# modules where ANY value may be a traced array, so float()/bool() on a
# non-literal is a host sync (compiled-code scope); in the host-side halves
# (api.py, loop.py) those conversions are ordinary host arithmetic and only
# the unambiguous sync primitives are flagged
_COMPILED_SCOPE = (
    f"{PACKAGE}/modes/",
    f"{PACKAGE}/sketch/",
    f"{PACKAGE}/federated/engine.py",
)

_SYNC_CALLS = ("jax.device_get", "jax.block_until_ready")
_NUMPY_SYNC_ATTRS = ("asarray", "array")


class HostSyncInRoundPath(Rule):
    code = "G001"
    name = "host-sync-in-round-path"
    fixit = ("defer the sync to a drain boundary (runner drain/commit), or "
             "mark the enclosing function `# graftlint: drain-point` if it "
             "IS the sanctioned boundary")

    SCOPE = (
        f"{PACKAGE}/federated/",
        f"{PACKAGE}/modes/",
        f"{PACKAGE}/sketch/",
    )
    # the always-on pipeline seams joined the round path in PR 11: the
    # two-open-rounds ingest buffer sits on the admission hot path, and
    # the pipeline worker runs the serve cycle that feeds every dispatch —
    # a hidden host sync in either stalls the always-on promise exactly
    # like one in the loop would
    EXACT = (
        f"{PACKAGE}/runner/loop.py",
        f"{PACKAGE}/serve/ingest.py",
        f"{PACKAGE}/serve/pipeline.py",
    )

    # the interprocedural taint pass (PR 20): `float(x)` smuggled behind a
    # helper call fires too. Subclassable off so the regression test can
    # demonstrate exactly what the pre-taint syntactic rule missed.
    taint_pass = True

    # hops of helper-call indirection the taint pass follows before giving
    # up (a coercion buried deeper is beyond honest static reach)
    _MAX_TAINT_DEPTH = 3

    def applies(self, rel: str) -> bool:
        return rel.startswith(self.SCOPE) or rel in self.EXACT

    def check(self, src: SourceFile) -> list[Violation]:
        out: list[Violation] = []
        compiled = src.rel.startswith(_COMPILED_SCOPE)
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            if src.in_drain_point(node.lineno):
                continue
            hit = self._classify(src, node, compiled)
            if hit:
                out.append(self.violation(src, node, hit))
        if compiled and self.taint_pass:
            out.extend(self._taint_findings(src))
        return out

    # -- interprocedural taint -------------------------------------------------

    def _taint_findings(self, src: SourceFile) -> list[Violation]:
        """float()/bool()/int() on a traced value HIDDEN BEHIND a helper
        call: every parameter of a compiled-scope function is a potential
        tracer, so an argument derived from one that flows into an
        out-of-scope helper which coerces it is the same hidden sync as an
        inline float() — reported at the call site that smuggles it."""
        imports = dataflow.import_bindings(src)
        if not imports:
            return []
        out: list[Violation] = []
        for fnode in ast.walk(src.tree):
            if not isinstance(fnode, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            seeds = set(dataflow.param_names(fnode))
            if not seeds:
                continue
            tainted = dataflow.tainted_names(fnode, seeds)
            for call in dataflow.walk_in_function(fnode):
                if not isinstance(call, ast.Call):
                    continue
                if src.in_drain_point(call.lineno):
                    continue
                target = dataflow.import_call_target(src, call, imports)
                if target is None:
                    continue
                passed = self._tainted_params(src, call, tainted,
                                              target[0], target[1])
                if not passed:
                    continue
                hit = self._coerced_in_helper(target[0], target[1],
                                              passed, depth=0, seen=set())
                if hit is not None:
                    coercer, where = hit
                    out.append(self.violation(
                        src, call,
                        f"{coercer} on a value tainted from a traced "
                        f"parameter, hidden inside helper {target[1]}() "
                        f"({where}) — a host sync the syntactic scan "
                        "cannot see"))
        return out

    def _tainted_params(self, src: SourceFile, call: ast.Call,
                        tainted: set[str], path: str,
                        fname: str) -> frozenset[str]:
        """Callee parameter names that receive a tainted argument."""
        helper = dataflow.LOADER.load(path)
        if helper is None:
            return frozenset()
        fdef = _find_def(helper, fname)
        if fdef is None:
            return frozenset()
        params = dataflow.param_names(fdef)
        hit: set[str] = set()
        for i, arg in enumerate(call.args):
            if i < len(params) and dataflow.expr_tainted(arg, tainted):
                hit.add(params[i])
        for kw in call.keywords:
            if (kw.arg is not None and kw.arg in params
                    and dataflow.expr_tainted(kw.value, tainted)):
                hit.add(kw.arg)
        return frozenset(hit)

    def _coerced_in_helper(self, path: str, fname: str,
                           seeds: frozenset[str], depth: int,
                           seen: set) -> tuple[str, str] | None:
        """Does `fname` at `path` coerce a value derived from `seeds` with
        float()/bool()/int()? Returns (coercer, 'rel:lineno') or None.
        Compiled-scope helpers are skipped (the syntactic rule already
        patrols them); drain points and explicit G001 disables in the
        helper stop the traversal, same contract as G007."""
        key = (path, fname, seeds)
        if depth > self._MAX_TAINT_DEPTH or key in seen:
            return None
        seen.add(key)
        helper = dataflow.LOADER.load(path)
        if helper is None or helper.rel.startswith(_COMPILED_SCOPE):
            return None
        fdef = _find_def(helper, fname)
        if fdef is None:
            return None
        if any(f.qualname == fname and f.drain_point
               for f in helper.functions):
            return None  # a declared sanctioned sync boundary
        tainted = dataflow.tainted_names(fdef, set(seeds))
        imports = None
        for call in dataflow.walk_in_function(fdef):
            if not isinstance(call, ast.Call):
                continue
            if helper.directives.disabled(self.code, call.lineno):
                continue
            if helper.in_drain_point(call.lineno):
                continue
            if (isinstance(call.func, ast.Name)
                    and call.func.id in ("float", "bool", "int")
                    and len(call.args) == 1
                    and dataflow.expr_tainted(call.args[0], tainted)):
                return (f"{call.func.id}()",
                        f"{helper.rel}:{call.lineno}")
            # taint flowing one helper deeper: same-module Name call or a
            # further import binding
            nxt: tuple[str, str] | None = None
            if isinstance(call.func, ast.Name) and any(
                    f.qualname == call.func.id for f in helper.functions):
                nxt = (path, call.func.id)
            else:
                if imports is None:
                    imports = dataflow.import_bindings(helper)
                nxt = dataflow.import_call_target(helper, call, imports)
            if nxt is None:
                continue
            passed = self._tainted_params(helper, call, tainted,
                                          nxt[0], nxt[1])
            if passed:
                hit = self._coerced_in_helper(nxt[0], nxt[1], passed,
                                              depth + 1, seen)
                if hit is not None:
                    return hit
        return None

    def _classify(self, src: SourceFile, node: ast.Call,
                  compiled: bool) -> str | None:
        dotted = src.resolve_dotted(node.func)
        if dotted in _SYNC_CALLS:
            return (f"{dotted}() is a host-device synchronization on the "
                    "round path, outside any declared drain point")
        # <expr>.item() / <expr>.block_until_ready()
        if (isinstance(node.func, ast.Attribute) and not node.args
                and not node.keywords
                and node.func.attr in ("item", "block_until_ready")):
            return (f".{node.func.attr}() forces a device round-trip on the "
                    "round path, outside any declared drain point")
        # numpy conversions materialize traced/device values on host
        if dotted is not None:
            head, _, attr = dotted.rpartition(".")
            if head == "numpy" and attr in _NUMPY_SYNC_ATTRS:
                return (f"np.{attr}() on the round path copies its argument "
                        "to host (a hidden sync when the value is a device "
                        "array)")
        # float()/bool() on a non-literal in compiled-code modules
        if (compiled and isinstance(node.func, ast.Name)
                and node.func.id in ("float", "bool") and len(node.args) == 1
                and isinstance(node.args[0],
                               (ast.Name, ast.Attribute, ast.Subscript))):
            return (f"{node.func.id}() on a value in compiled-scope code "
                    "forces concretization — a host sync under jit tracing")
        return None


_BLOCKING_CALLS = {
    "time.sleep": "time.sleep() stalls the dispatch/prefetch thread",
    "os.system": "os.system() is blocking sync IO on the dispatch path",
    "open": "synchronous file IO on the dispatch path",
    "socket.create_connection": "socket.create_connection() is a blocking "
                                "network round-trip on the dispatch path",
}

# entry points of the dispatch/prefetch path; reachability is computed over
# the package-level call graph from these roots (serve_round/submit are the
# serving layer's dispatch-path entries)
_ROOT_NAMES = {"run_loop", "next", "prepare_round", "dispatch_round",
               "serve_round", "submit"}

# cross-module traversal bound: hops of `from .helper import fn` / `mod.fn()`
# indirection followed before giving up (a sleep buried deeper than this
# behind imports is beyond honest static reach — raise it if one ever is)
_MAX_IMPORT_DEPTH = 4


class BlockingCallOnDispatchThread(Rule):
    code = "G007"
    name = "blocking-call-on-dispatch-thread"
    fixit = ("move the blocking work to the writer/watchdog thread or an "
             "exit path; drain points and fault-injection sites carry "
             "`# graftlint: drain-point` / an explicit disable")

    SCOPE = (f"{PACKAGE}/runner/", f"{PACKAGE}/serve/")
    # the async writer runs on its own dedicated thread by design
    EXEMPT = (f"{PACKAGE}/runner/writer.py",)
    # overridable per subclass: G015 (rules_reactor.py) reuses this whole
    # reachability machine with the event loop's own roots
    ROOTS = _ROOT_NAMES

    def applies(self, rel: str) -> bool:
        return rel.startswith(self.SCOPE) and rel not in self.EXEMPT

    def check(self, src: SourceFile) -> list[Violation]:
        reachable = self._reachable(src)
        imports = _import_bindings(src)
        out: list[Violation] = []
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            sym = src.enclosing_symbol(node.lineno)
            if sym not in reachable:
                continue
            if src.in_drain_point(node.lineno):
                continue
            msg = self._blocking(src, node)
            if msg:
                out.append(self.violation(
                    src, node,
                    f"{msg} (reachable from the dispatch path via {sym})"))
                continue
            # package-level: a call into an IMPORTED helper whose body (or
            # transitive same-package callees) blocks — the "sleep smuggled
            # behind a helper import" a module-local graph cannot see
            imported = self._imported_blocking(src, node, imports)
            if imported:
                out.append(self.violation(
                    src, node,
                    f"{imported} (reachable from the dispatch path via "
                    f"{sym}, through a helper import)"))
        return out

    def _blocking(self, src: SourceFile, node: ast.Call) -> str | None:
        dotted = src.resolve_dotted(node.func)
        if dotted in _BLOCKING_CALLS:
            return _BLOCKING_CALLS[dotted]
        if dotted and dotted.startswith("subprocess."):
            return f"{dotted}() launches a blocking subprocess on the " \
                   "dispatch path"
        return None

    # -- package-level traversal ---------------------------------------------

    def _imported_blocking(self, src: SourceFile, node: ast.Call,
                           imports: dict) -> str | None:
        """Resolve `fn()` / `mod.fn()` through the file's import bindings to
        a function in another module of this package (or the fixture's
        directory) and report the first blocking call reachable from it."""
        target: tuple[str, str] | None = None
        if isinstance(node.func, ast.Name):
            target = imports.get(node.func.id)
        elif (isinstance(node.func, ast.Attribute)
              and isinstance(node.func.value, ast.Name)):
            mod = imports.get(node.func.value.id)
            if mod is not None and mod[1] == "*module*":
                target = (mod[0], node.func.attr)
        if target is None:
            return None
        path, func = target
        return self._func_blocks(path, func, depth=0, seen=set())

    def _func_blocks(self, path: str, func: str, depth: int,
                     seen: set) -> str | None:
        """Does module-level function `func` in the module at `path` reach a
        blocking call (its own body, same-module callees, or further
        imports, depth-bounded)? Declared drain points — the sanctioned
        blocking boundaries — and explicit G007 disables stop the
        traversal."""
        if depth > _MAX_IMPORT_DEPTH or (path, func) in seen:
            return None
        seen.add((path, func))
        helper = self._load_helper(path)
        if helper is None:
            return None
        fns = [f for f in helper.functions if f.qualname == func]
        if not fns or any(f.drain_point for f in fns):
            return None  # undefined here, or a declared sanctioned boundary
        spans = [(f.start, f.end) for f in fns]
        imports = _import_bindings(helper)
        for node in ast.walk(helper.tree):
            if not isinstance(node, ast.Call):
                continue
            if not any(s <= node.lineno <= e for s, e in spans):
                continue
            if helper.enclosing_symbol(node.lineno) != func:
                # a nested def inside the helper is its own (possibly
                # thread-targeted) context — don't charge it to the caller
                continue
            if helper.in_drain_point(node.lineno):
                continue
            if helper.directives.disabled(self.code, node.lineno):
                continue
            msg = self._blocking(helper, node)
            if msg:
                return (f"{msg} — in {helper.rel}:{node.lineno} "
                        f"({func})")
            # same-module callee
            if isinstance(node.func, ast.Name):
                callee = node.func.id
                if any(f.qualname == callee for f in helper.functions):
                    hit = self._func_blocks(path, callee, depth + 1, seen)
                    if hit:
                        return hit
            # further imports
            target = None
            if isinstance(node.func, ast.Name):
                target = imports.get(node.func.id)
            elif (isinstance(node.func, ast.Attribute)
                  and isinstance(node.func.value, ast.Name)):
                mod = imports.get(node.func.value.id)
                if mod is not None and mod[1] == "*module*":
                    target = (mod[0], node.func.attr)
            if target is not None:
                hit = self._func_blocks(target[0], target[1], depth + 1, seen)
                if hit:
                    return hit
        return None

    def _load_helper(self, path: str) -> SourceFile | None:
        # the shared parse cache: one SourceFile per helper per process,
        # whichever interprocedural rule asked first
        return dataflow.LOADER.load(path)

    def _reachable(self, src: SourceFile) -> set[str]:
        """Qualnames reachable from the dispatch-path roots over same-module
        calls (Name calls resolve innermost-nested-first, then module level;
        self.X calls resolve to any same-module method named X)."""
        by_last: dict[str, set[str]] = {}
        for f in src.functions:
            by_last.setdefault(f.qualname.rsplit(".", 1)[-1], set()).add(
                f.qualname)
        edges: dict[str, set[str]] = {f.qualname: set()
                                      for f in src.functions}
        # one walk: attribute calls and name calls per enclosing function
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            caller = src.enclosing_symbol(node.lineno)
            if caller == "<module>":
                continue
            callee: str | None = None
            if isinstance(node.func, ast.Name):
                callee = node.func.id
            elif (isinstance(node.func, ast.Attribute)
                  and isinstance(node.func.value, ast.Name)
                  and node.func.value.id in ("self", "cls")):
                callee = node.func.attr
            if callee and callee in by_last:
                # prefer a nested function of the caller, else any match
                nested = {q for q in by_last[callee]
                          if q.startswith(f"{caller}.")}
                edges.setdefault(caller, set()).update(
                    nested or by_last[callee])
        roots = {f.qualname for f in src.functions
                 if f.qualname.rsplit(".", 1)[-1] in self.ROOTS}
        seen = set(roots)
        frontier = list(roots)
        while frontier:
            cur = frontier.pop()
            for nxt in edges.get(cur, ()):
                if nxt not in seen:
                    seen.add(nxt)
                    frontier.append(nxt)
        # a nested function belongs to its parent's thread context
        for f in src.functions:
            if any(f.qualname.startswith(f"{r}.") for r in list(seen)):
                seen.add(f.qualname)
        return seen


def _find_def(helper: SourceFile,
              fname: str) -> ast.FunctionDef | ast.AsyncFunctionDef | None:
    """First def named `fname` in the helper (module-level functions is
    the shape import bindings hand us; a shadowing nested def would have
    the same body anyway for taint purposes)."""
    for node in ast.walk(helper.tree):
        if (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and node.name == fname):
            return node
    return None


# import resolution lives in dataflow.py since the concurrency rules joined
# (G018/G019/G020 resolve imports identically); re-exported names keep the
# G015 subclass and the tests importing from here working
_package_root = dataflow.package_root
_import_bindings = dataflow.import_bindings
