#!/usr/bin/env python3
"""graftlint — AST-level trace-hygiene lint for this repo's own sources.

jax traces Python ONCE and replays the result: host-side effects inside
a traced scope silently freeze (an ``os.environ`` read becomes a baked
constant, ``time.time()`` a stale timestamp, ``np.random`` one sample
forever) or tear (a ``MetricRegistry`` mutation fires at trace time, not
step time).  The analysis subsystem (geomx_tpu/analysis/) audits traced
*programs*; graftlint audits the *source* that produces them — no jax
import, pure ``ast``, fast enough for a pre-commit hook.

Rules (docs/analysis.md has the catalog with examples):

- GXL001  wall-clock read (``time.time``/``perf_counter``/
          ``datetime.now``) inside a jitted/traced-scope function
- GXL002  host RNG (``np.random.*`` / stdlib ``random.*``) inside a
          traced scope (freezes to one sample per trace)
- GXL003  environment read (``os.environ``/``os.getenv``) inside a
          traced scope (bakes the trace-time value into the program)
- GXL004  MetricRegistry mutation (``get_registry``/``log_event``/
          ``.inc``/``.observe``/``.labels``) inside a traced scope
          (fires per trace, not per step — use
          ``telemetry.probes.record_inline``)
- GXL005  mutable default argument in a public geomx_tpu API
- GXL006  ``os.environ``/``os.getenv`` read in geomx_tpu/ outside
          config.py (knobs route through GeoConfig/_env so launch
          scripts and docs stay the single source of truth)
- GX-WIRE-001  pickle use (``dumps``/``loads``/``dump``/``load``/
          ``Unpickler``) anywhere in geomx_tpu/service/ or
          geomx_tpu/serve/ — the host plane's wire hot path speaks
          the fixed-layout v0x02 binary codec (the serving plane's
          registry refresh rides the same frames); pickling there
          reintroduces the per-frame serializer cost the native
          fast path removed (and, for loads, an attack surface).
          The ONLY sanctioned waivers are the legacy-compat v0x01
          codec paths in protocol.py.

Traced-scope detection (documented heuristics, module-local):

1. decorated with ``jax.jit``/``jit``/``pjit``/``functools.partial(
   jax.jit, ...)``/``shard_map``/``checkpoint``;
2. passed by name to a trace entry point anywhere in the module
   (``jax.jit(f)``, ``shard_map_compat(f, ...)``, ``lax.scan(body,``,
   ``make_jaxpr(f)``, ``value_and_grad``, ``pallas_call``, ...);
3. named like a known traced hook of this codebase (``compress``,
   ``allreduce_leaf``, ``sync_grads``, ... — the Compressor/
   SyncAlgorithm surfaces the train step calls while tracing);
4. anything such a function calls (module-local call graph, including
   ``self.method()`` edges and local class instantiation -> __init__),
   and anything nested inside it.

Waivers: append ``# graftlint: disable=GXL003`` (comma list, or
``disable=all``) to the offending line or the line above, ideally with
a reason.  The committed zero-findings baseline
(tools/graftlint_baseline.json) records finding AND waiver counts, so
waiver creep shows up in review; CI runs ``--check-baseline``.

Usage:
    python tools/graftlint.py                      # lint default roots
    python tools/graftlint.py path [path ...]      # lint specific paths
    python tools/graftlint.py --json               # one-line JSON out
    python tools/graftlint.py --check-baseline     # gate (CI)
    python tools/graftlint.py --write-baseline     # refresh the file
"""

from __future__ import annotations

import ast
import json
import os
import re
import sys
from typing import Dict, List, Optional, Set, Tuple

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_ROOTS = ("geomx_tpu", "tools", "tests", "examples", "scripts",
                 "__graft_entry__.py")
BASELINE_PATH = os.path.join(REPO_ROOT, "tools", "graftlint_baseline.json")

# entry points whose function-valued arguments are traced
TRACE_ENTRYPOINTS = {
    "jit", "pjit", "shard_map", "shard_map_compat", "make_jaxpr",
    "eval_shape", "value_and_grad", "grad", "vmap", "pmap", "scan",
    "cond", "while_loop", "fori_loop", "switch", "map", "checkpoint",
    "remat", "custom_jvp", "custom_vjp", "pallas_call", "named_scope",
    "associative_scan", "export",
}

# decorators that make the decorated function a traced scope
TRACE_DECORATORS = {"jit", "pjit", "shard_map", "checkpoint", "remat",
                    "custom_jvp", "custom_vjp"}

# methods this codebase calls from inside the traced train step
# (Compressor / SyncAlgorithm / bucketer surfaces)
TRACED_METHOD_NAMES = {
    "compress", "decompress", "allreduce", "allreduce_leaf",
    "allreduce_buckets", "flatten", "unflatten", "sync_grads",
    "sync_params", "sync_model_state", "forward_params", "drain_grads",
    "drain_model_state", "telemetry_scalars", "scatter_grad_leaf",
    "shard_param_leaf", "unshard_param_leaf",
}

# resolved (import-alias-expanded) call paths that read the wall clock;
# `datetime.datetime.now` covers `import datetime`, `datetime.now` the
# `from datetime import datetime` spelling
_WALL_CLOCK_PATHS = {
    "time.time", "time.perf_counter", "time.monotonic",
    "time.process_time", "datetime.now", "datetime.utcnow",
    "datetime.datetime.now", "datetime.datetime.utcnow"}
_REGISTRY_CALLS = {"get_registry", "log_event"}
_REGISTRY_METHODS = {"inc", "observe", "labels"}

_WAIVER_RE = re.compile(r"#\s*graftlint:\s*disable=([A-Za-z0-9_,\s-]+|all)")


class LintFinding:
    def __init__(self, rule: str, path: str, line: int, message: str):
        self.rule = rule
        self.path = path
        self.line = line
        self.message = message

    def format(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.message}"

    def as_dict(self) -> dict:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "message": self.message}


def _dotted(node: ast.AST) -> str:
    """Best-effort dotted-name string for a Name/Attribute chain."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    elif isinstance(node, ast.Call):
        # e.g. datetime.datetime.now() spelled via a call chain root
        parts.append("()")
    return ".".join(reversed(parts))


def _collect_waivers(source: str) -> Dict[int, Set[str]]:
    waivers: Dict[int, Set[str]] = {}
    for i, line in enumerate(source.splitlines(), start=1):
        m = _WAIVER_RE.search(line)
        if not m:
            continue
        rules = {r.strip().upper() for r in m.group(1).split(",")
                 if r.strip()}
        waivers[i] = rules
    return waivers


def _waived(waivers: Dict[int, Set[str]], line: int, rule: str) -> bool:
    for ln in (line, line - 1):
        rules = waivers.get(ln)
        if rules and ("ALL" in rules or rule in rules):
            return True
    return False


class _FnInfo:
    __slots__ = ("name", "qual", "node", "cls", "nested_in", "traced")

    def __init__(self, name, qual, node, cls, nested_in):
        self.name = name
        self.qual = qual
        self.node = node
        self.cls = cls            # enclosing class name or None
        self.nested_in = nested_in  # enclosing function qual or None
        self.traced = False


def _decorator_is_trace(dec: ast.AST) -> bool:
    """``@jax.jit``, ``@jit``, ``@functools.partial(jax.jit, ...)``."""
    if isinstance(dec, ast.Call):
        name = _dotted(dec.func)
        if name.rsplit(".", 1)[-1] == "partial" and dec.args:
            return _decorator_is_trace(dec.args[0])
        return name.rsplit(".", 1)[-1] in TRACE_DECORATORS
    return _dotted(dec).rsplit(".", 1)[-1] in TRACE_DECORATORS


class ModuleLinter:
    """One file's lint run: trace-scope inference + rule checks."""

    def __init__(self, path: str, source: str, in_package: bool):
        self.path = path
        self.source = source
        self.in_package = in_package  # under geomx_tpu/
        self.tree = ast.parse(source, filename=path)
        self.waivers = _collect_waivers(source)
        self.findings: List[LintFinding] = []
        self.fns: Dict[str, _FnInfo] = {}
        self.classes: Dict[str, Set[str]] = {}  # class -> method quals
        self.calls: Dict[str, Set[str]] = {}    # fn qual -> callee quals
        # local import aliases, so `from jax import random` is never
        # confused with numpy/stdlib random: name -> full module path
        self.imports: Dict[str, str] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.imports[a.asname or a.name.split(".")[0]] = \
                        a.name if a.asname else a.name.split(".")[0]
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    self.imports[a.asname or a.name] = \
                        f"{node.module}.{a.name}"

    def _resolve(self, dotted: str) -> str:
        """Expand the root of a dotted chain through the module's import
        aliases (``np.random.rand`` -> ``numpy.random.rand``)."""
        if not dotted:
            return dotted
        root, _, rest = dotted.partition(".")
        full = self.imports.get(root, root)
        return f"{full}.{rest}" if rest else full

    # -- collection ---------------------------------------------------------

    def _collect_functions(self):
        def visit(node, cls, fn_qual, prefix):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    qual = f"{prefix}{child.name}"
                    info = _FnInfo(child.name, qual, child, cls, fn_qual)
                    self.fns[qual] = info
                    if cls is not None:
                        self.classes.setdefault(cls, set()).add(qual)
                    visit(child, cls, qual, qual + ".")
                elif isinstance(child, ast.ClassDef):
                    visit(child, child.name, fn_qual,
                          f"{prefix}{child.name}.")
                else:
                    visit(child, cls, fn_qual, prefix)

        visit(self.tree, None, None, "")

    def _fn_by_name(self, name: str, near: Optional[_FnInfo]) -> List[str]:
        """Resolve a bare name to candidate function quals (same class
        first, then module level / any)."""
        out = [q for q, f in self.fns.items() if f.name == name]
        if near is not None and near.cls is not None:
            same = [q for q in out
                    if self.fns[q].cls in (near.cls, None)]
            if same:
                return same
        return out

    def _collect_roots_and_calls(self):
        # roots by decorator / known traced method name
        for info in self.fns.values():
            if any(_decorator_is_trace(d)
                   for d in info.node.decorator_list):
                info.traced = True
            if info.cls is not None and info.name in TRACED_METHOD_NAMES:
                info.traced = True

        # roots by being passed to a trace entry point; call edges
        class V(ast.NodeVisitor):
            def __init__(v, outer):
                v.outer = outer
                v.stack: List[_FnInfo] = []

            def visit_FunctionDef(v, node):
                qual = v._qual_for(node)
                info = v.outer.fns.get(qual)
                if info is not None:
                    v.stack.append(info)
                    v.generic_visit(node)
                    v.stack.pop()
                else:
                    v.generic_visit(node)

            visit_AsyncFunctionDef = visit_FunctionDef

            def _qual_for(v, node):
                # reconstruct qual by matching the node object
                for q, f in v.outer.fns.items():
                    if f.node is node:
                        return q
                return node.name

            def visit_Call(v, node):
                outer = v.outer
                fname = _dotted(node.func).rsplit(".", 1)[-1]
                cur = v.stack[-1] if v.stack else None
                if fname in TRACE_ENTRYPOINTS:
                    for arg in list(node.args) + [kw.value for kw in
                                                  node.keywords]:
                        target = None
                        if isinstance(arg, ast.Name):
                            target = arg.id
                        elif isinstance(arg, ast.Attribute) and \
                                isinstance(arg.value, ast.Name) and \
                                arg.value.id == "self":
                            target = arg.attr
                        if target:
                            for q in outer._fn_by_name(target, cur):
                                outer.fns[q].traced = True
                # call edges from the enclosing function
                if cur is not None:
                    callee = None
                    if isinstance(node.func, ast.Name):
                        callee = node.func.id
                    elif isinstance(node.func, ast.Attribute) and \
                            isinstance(node.func.value, ast.Name) and \
                            node.func.value.id == "self":
                        callee = node.func.attr
                    if callee:
                        edges = outer.calls.setdefault(cur.qual, set())
                        for q in outer._fn_by_name(callee, cur):
                            edges.add(q)
                        # local class instantiation -> __init__
                        init = f"{callee}.__init__"
                        if init in outer.fns:
                            edges.add(init)
                v.generic_visit(node)

        V(self).visit(self.tree)

    def _propagate(self):
        # nested-in-traced functions are traced; then close over calls
        changed = True
        while changed:
            changed = False
            for info in self.fns.values():
                if info.traced:
                    continue
                parent = info.nested_in
                if parent and self.fns.get(parent) is not None \
                        and self.fns[parent].traced:
                    info.traced = True
                    changed = True
            for qual, callees in self.calls.items():
                caller = self.fns.get(qual)
                if caller is None or not caller.traced:
                    continue
                for c in callees:
                    callee = self.fns.get(c)
                    if callee is not None and not callee.traced:
                        callee.traced = True
                        changed = True

    # -- rules --------------------------------------------------------------

    def _emit(self, rule: str, node: ast.AST, message: str):
        line = getattr(node, "lineno", 0)
        if _waived(self.waivers, line, rule):
            return
        self.findings.append(
            LintFinding(rule, os.path.relpath(self.path, REPO_ROOT),
                        line, message))

    def _check_traced_body(self, info: _FnInfo):
        # walk the body WITHOUT descending into nested defs (each is
        # checked as its own function, so effects inside would double-
        # report under the outer qual)
        def iter_own(root):
            stack = list(ast.iter_child_nodes(root))
            while stack:
                node = stack.pop()
                yield node
                if not isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                    stack.extend(ast.iter_child_nodes(node))

        for node in iter_own(info.node):
            if isinstance(node, ast.Call):
                dotted = _dotted(node.func)
                leaf = dotted.rsplit(".", 1)[-1]
                resolved = self._resolve(dotted)
                if resolved in _WALL_CLOCK_PATHS:
                    self._emit("GXL001", node,
                               f"wall-clock read `{dotted}()` inside "
                               f"traced scope `{info.qual}` freezes to "
                               "the trace-time value")
                if (resolved.startswith("numpy.random.")
                        or resolved.startswith("random.")):
                    self._emit("GXL002", node,
                               f"host RNG `{dotted}()` inside traced "
                               f"scope `{info.qual}` yields ONE sample "
                               "per trace — thread a jax PRNG key")
                if dotted.endswith("os.getenv") or dotted == "getenv" \
                        or dotted.endswith("environ.get"):
                    self._emit("GXL003", node,
                               f"environment read `{dotted}` inside "
                               f"traced scope `{info.qual}` bakes the "
                               "trace-time value into the program")
                if leaf in _REGISTRY_CALLS or \
                        (isinstance(node.func, ast.Attribute)
                         and leaf in _REGISTRY_METHODS):
                    self._emit("GXL004", node,
                               f"metric-registry mutation `{dotted}` "
                               f"inside traced scope `{info.qual}` "
                               "fires per TRACE, not per step — use "
                               "telemetry.probes.record_inline")
            elif isinstance(node, ast.Subscript) and \
                    isinstance(node.ctx, ast.Load) and \
                    _dotted(node.value).endswith("os.environ"):
                self._emit("GXL003", node,
                           "os.environ[...] read inside traced scope "
                           f"`{info.qual}` bakes the trace-time value "
                           "into the program")

    def _check_mutable_defaults(self):
        if not self.in_package:
            return
        for info in self.fns.values():
            if info.name.startswith("_") or info.nested_in:
                continue
            if info.cls is not None and info.cls.startswith("_"):
                continue
            a = info.node.args
            for default in list(a.defaults) + [d for d in a.kw_defaults
                                               if d is not None]:
                bad = isinstance(default, (ast.List, ast.Dict, ast.Set))
                if isinstance(default, ast.Call) and \
                        _dotted(default.func) in ("list", "dict", "set"):
                    bad = True
                if bad:
                    self._emit("GXL005", default,
                               f"mutable default argument in public API "
                               f"`{info.qual}` is shared across calls — "
                               "default to None and build inside")

    def _check_env_outside_config(self):
        if not self.in_package or \
                os.path.basename(self.path) == "config.py":
            return
        for node in ast.walk(self.tree):
            dotted = ""
            if isinstance(node, ast.Call):
                dotted = _dotted(node.func)
                if not (dotted.endswith("os.getenv")
                        or dotted.endswith("environ.get")):
                    continue
            elif isinstance(node, ast.Subscript) and \
                    isinstance(node.ctx, ast.Load):
                dotted = _dotted(node.value)
                if not dotted.endswith("os.environ"):
                    continue
            elif isinstance(node, ast.Compare) and any(
                    _dotted(c).endswith("os.environ")
                    for c in node.comparators):
                dotted = "in os.environ"
            else:
                continue
            self._emit("GXL006", node,
                       f"environment read (`{dotted}`) outside "
                       "config.py: route the knob through "
                       "GeoConfig/_env (or waive with a reason)")

    def _check_service_pickle(self):
        # GX-WIRE-001: geomx_tpu/service/ is the wire hot path — every
        # frame a worker pushes crosses this code — and geomx_tpu/serve/
        # rides the same frames for its registry refresh stream.  The
        # v0x02 binary codec exists precisely so no pickle runs per
        # frame; any new pickle use here silently reintroduces that
        # serializer cost (and for loads, an arbitrary-object decode
        # surface).  Only the legacy-compat v0x01 encode/decode in
        # protocol.py carries a sanctioned waiver.
        ap = os.path.abspath(self.path)
        gated = any(
            os.sep + os.path.join("geomx_tpu", d) + os.sep in ap
            for d in ("service", "serve"))
        if not gated:
            return
        names = ("dumps", "loads", "dump", "load", "Unpickler")
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Attribute):
                dotted = self._resolve(_dotted(node))
            elif isinstance(node, ast.Name):
                dotted = self.imports.get(node.id, "")
            else:
                continue
            if not any(dotted == f"pickle.{n}"
                       or dotted.endswith(f".pickle.{n}")
                       for n in names):
                continue
            self._emit("GX-WIRE-001", node,
                       f"pickle on the service wire path (`{dotted}`): "
                       "the host plane ships the v0x02 binary codec — "
                       "extend protocol's TLV/compact forms instead "
                       "(waivers are reserved for the legacy-compat "
                       "v0x01 codec)")

    def run(self) -> List[LintFinding]:
        self._collect_functions()
        self._collect_roots_and_calls()
        self._propagate()
        for info in self.fns.values():
            if info.traced:
                self._check_traced_body(info)
        self._check_mutable_defaults()
        self._check_env_outside_config()
        self._check_service_pickle()
        return self.findings

    @property
    def waiver_count(self) -> int:
        return len(self.waivers)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def iter_py_files(paths):
    for p in paths:
        ap = p if os.path.isabs(p) else os.path.join(REPO_ROOT, p)
        if os.path.isfile(ap) and ap.endswith(".py"):
            yield ap
        elif os.path.isdir(ap):
            for root, dirs, files in os.walk(ap):
                dirs[:] = [d for d in dirs
                           if d not in ("__pycache__", ".git",
                                        ".geomx_compile_cache")]
                for f in sorted(files):
                    if f.endswith(".py"):
                        yield os.path.join(root, f)


def lint_paths(paths) -> Tuple[List[LintFinding], int]:
    findings: List[LintFinding] = []
    waivers = 0
    pkg_root = os.path.join(REPO_ROOT, "geomx_tpu") + os.sep
    self_path = os.path.abspath(__file__)
    for path in iter_py_files(paths):
        if os.path.abspath(path) == self_path:
            # the linter documents its own waiver syntax and rule text;
            # scanning itself would count docstring examples as waivers
            continue
        with open(path, encoding="utf-8") as f:
            source = f.read()
        linter = ModuleLinter(path, source,
                              in_package=path.startswith(pkg_root))
        findings.extend(linter.run())
        waivers += linter.waiver_count
    return findings, waivers


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    as_json = "--json" in argv
    check_baseline = "--check-baseline" in argv
    write_baseline = "--write-baseline" in argv
    paths = [a for a in argv if not a.startswith("--")] or \
        list(DEFAULT_ROOTS)

    findings, waivers = lint_paths(paths)
    counts: Dict[str, int] = {}
    for f in findings:
        counts[f.rule] = counts.get(f.rule, 0) + 1

    if as_json:
        print(json.dumps({
            "mode": "graftlint", "findings": len(findings),
            "waivers": waivers, "rules": counts,
            "items": [f.as_dict() for f in findings]}))
    else:
        for f in findings:
            print(f.format())
        print(f"graftlint: {len(findings)} finding(s), "
              f"{waivers} waiver(s)")

    if write_baseline:
        with open(BASELINE_PATH, "w") as f:
            json.dump({"findings": len(findings), "waivers": waivers,
                       "rules": counts}, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"graftlint: baseline written to {BASELINE_PATH}")

    if check_baseline:
        with open(BASELINE_PATH) as f:
            base = json.load(f)
        if len(findings) != base["findings"] or \
                waivers != base["waivers"]:
            print("graftlint: BASELINE MISMATCH — expected "
                  f"{base['findings']} finding(s) / {base['waivers']} "
                  f"waiver(s), got {len(findings)} / {waivers}. Fix the "
                  "findings (preferred), waive with a reason, or "
                  "refresh via --write-baseline and justify in review.")
            return 1
        return 0
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
