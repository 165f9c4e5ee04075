"""Layer 1: AST lints over ``src/repro``.

Pure-syntax rules that catch precision/kernel contract violations before
anything is traced:

  host-sync-in-jit     .item()/.tolist()/.block_until_ready()/
                       jax.device_get/np.asarray — and float()/int()/bool()
                       around a jnp/jax call — inside a traced scope (a
                       function passed to jit/scan/pallas_call/... or
                       decorated with one). Each is a device->host sync
                       that serializes the step it hides in.
  stale-interpret-flag hard-coded ``interpret=True`` (def default or call
                       keyword). Kernels must auto-resolve via
                       ``kernels.ref.default_interpret`` so the same call
                       compiles for real on TPU.
  force-backend-leak   ``force_backend(...)`` outside its def site — a
                       test hook; production code must not pin a backend.
  traced-truthiness    Python ``if``/``while``/``assert`` on a jnp/jax
                       expression in a traced scope (TracerBoolConversion
                       at runtime, or a silent trace-time specialization).
  container-name       container-name string literals in registry calls /
                       known keywords / argparse defaults that the codec
                       registry cannot resolve (with did-you-mean).
  policy-name          same for precision-policy names ('+'-composition
                       validated without construction).
  float64              jnp.float64 / astype("float64") / jax_enable_x64 —
                       this codebase's containers assume <= 32-bit floats.
  obs-no-hot-path-sync telemetry mutation (obs/tracer/timeline .inc/
                       .observe/.emit/...) inside a traced scope. The
                       repro.obs API is host-side Python: calling it from
                       jitted code either burns a trace-time constant or
                       forces a host callback. Record at the host
                       boundary after the step returns.

Two passes per module: collect the names of functions that enter a traced
context (arguments to jit-like wrappers, including through
``functools.partial`` and bound-method references; jit-decorated defs),
then visit with a scope stack so nested defs inherit tracedness.
"""
from __future__ import annotations

import ast
import pathlib
from typing import Iterable, List, Optional, Set

from repro.analysis.findings import Finding

# Wrappers whose function-valued arguments run traced.
_TRACE_WRAPPERS = {
    "jit", "pmap", "vmap", "grad", "value_and_grad", "checkpoint", "remat",
    "scan", "while_loop", "fori_loop", "cond", "switch", "associated_scan",
    "pallas_call", "custom_vjp", "custom_jvp", "shard_map", "eval_shape",
    "make_jaxpr",
}

# jnp/jax attributes that are static (shape-level) despite the module root.
_STATIC_ATTRS = {"ndim", "shape", "size", "issubdtype", "dtype",
                 "result_type", "isdtype", "iinfo", "finfo"}

_HOST_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
_NUMPY_ROOTS = {"np", "numpy", "onp"}
_JAX_ROOTS = {"jax", "jnp", "lax", "pl", "pltpu"}

_CONTAINER_KWARGS = {"container", "kv_container", "degraded_container",
                     "grad_codec", "stash_container", "ckpt_container"}
_CONTAINER_RE = r"(sfp|gecko|bit_?exact)[\w+-]*"

# Telemetry surface (repro.obs). Any of these methods invoked on a
# receiver whose attribute chain passes through an obs handle is a
# host-side mutation — illegal inside a traced scope.
_OBS_MUTATORS = {"inc", "dec", "set", "observe", "emit", "event",
                 "instant", "begin", "end", "complete", "record_train",
                 "record_serve", "write", "span"}
_OBS_RECEIVERS = {"obs", "tracer", "timeline", "registry", "events"}


def _dotted(node) -> str:
    """Best-effort dotted name of an expression ('jax.lax.scan', 'f')."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _last(dotted: str) -> str:
    return dotted.rsplit(".", 1)[-1]


def _root(dotted: str) -> str:
    return dotted.split(".", 1)[0]


def _chain_parts(node) -> Set[str]:
    """Every identifier on a receiver chain, walking through attribute
    access, calls, and subscripts: ``self.obs.tracer``,
    ``obs.registry.counter(...).labels(...)``, ``handles["ttft"]`` all
    surface their intermediate names."""
    parts: Set[str] = set()
    while True:
        if isinstance(node, ast.Attribute):
            parts.add(node.attr)
            node = node.value
        elif isinstance(node, ast.Call):
            node = node.func
        elif isinstance(node, ast.Subscript):
            node = node.value
        elif isinstance(node, ast.Name):
            parts.add(node.id)
            return parts
        else:
            return parts


def _callable_names(node) -> Iterable[str]:
    """Function identifiers an argument expression refers to: a bare name,
    a bound-method attr (self._step_fn -> _step_fn), or either wrapped in
    functools.partial(f, ...)."""
    if isinstance(node, ast.Name):
        yield node.id
    elif isinstance(node, ast.Attribute):
        yield node.attr
    elif isinstance(node, ast.Call) and _last(_dotted(node.func)) == \
            "partial" and node.args:
        yield from _callable_names(node.args[0])


def _is_jit_decorator(dec) -> bool:
    if isinstance(dec, ast.Call):
        if _last(_dotted(dec.func)) == "partial" and dec.args:
            return _last(_dotted(dec.args[0])) in _TRACE_WRAPPERS
        return _last(_dotted(dec.func)) in _TRACE_WRAPPERS
    return _last(_dotted(dec)) in _TRACE_WRAPPERS


class _TracedCollector(ast.NodeVisitor):
    """Pass 1: names of functions handed to a traced context anywhere in
    the module (scope-insensitive on purpose — conservative)."""

    def __init__(self):
        self.traced: Set[str] = set()

    def visit_Call(self, node):
        if _last(_dotted(node.func)) in _TRACE_WRAPPERS:
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                self.traced.update(_callable_names(arg))
        self.generic_visit(node)


def _docstring_linenos(tree) -> Set[int]:
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.FunctionDef,
                             ast.AsyncFunctionDef, ast.ClassDef)):
            body = getattr(node, "body", [])
            if body and isinstance(body[0], ast.Expr) and isinstance(
                    body[0].value, ast.Constant) and isinstance(
                        body[0].value.value, str):
                c = body[0].value
                out.update(range(c.lineno, c.end_lineno + 1))
    return out


def _contains_jax_call(expr, *, skip_static=True) -> Optional[str]:
    """Dotted name of the first jnp/jax-rooted call inside ``expr``."""
    for node in ast.walk(expr):
        if isinstance(node, ast.Call):
            d = _dotted(node.func)
            if _root(d) in _JAX_ROOTS and "." in d:
                if skip_static and _last(d) in _STATIC_ATTRS:
                    continue
                return d
    return None


class _Lint(ast.NodeVisitor):
    def __init__(self, path: str, traced: Set[str], docstrings: Set[int],
                 findings: List[Finding]):
        self.path = path
        self.traced_names = traced
        self.docstrings = docstrings
        self.findings = findings
        self.scopes: List[tuple] = []  # (name, traced)

    # -- helpers -----------------------------------------------------------

    def _emit(self, rule: str, node, message: str, scope: str = ""):
        scope = scope or (self.scopes[-1][0] if self.scopes else "<module>")
        self.findings.append(Finding(rule=rule, path=self.path,
                                     line=node.lineno, scope=scope,
                                     message=message))

    def _in_traced(self) -> bool:
        return any(traced for _, traced in self.scopes)

    # -- scope tracking ----------------------------------------------------

    def visit_FunctionDef(self, node):
        traced = (node.name in self.traced_names
                  or any(_is_jit_decorator(d) for d in node.decorator_list)
                  or self._in_traced())
        for arg, default in zip(reversed(node.args.args + node.args
                                         .kwonlyargs),
                                reversed((node.args.defaults or [])
                                         + (node.args.kw_defaults or []))):
            if (arg.arg == "interpret" and isinstance(default, ast.Constant)
                    and default.value is True):
                self._emit("stale-interpret-flag", default,
                           f"def {node.name} defaults interpret=True; "
                           "default to None and resolve via "
                           "kernels.ref.default_interpret", scope=node.name)
        self.scopes.append((node.name, traced))
        self.generic_visit(node)
        self.scopes.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    # -- rules -------------------------------------------------------------

    def visit_Call(self, node):
        d = _dotted(node.func)
        last = _last(d)

        if last == "force_backend" and not self.path.endswith(
                "kernels/ops.py"):
            self._emit("force-backend-leak", node,
                       "force_backend() is a test hook; production code "
                       "must not pin a kernel backend")

        for kw in node.keywords:
            if (kw.arg == "interpret" and isinstance(kw.value, ast.Constant)
                    and kw.value.value is True):
                self._emit("stale-interpret-flag", node,
                           f"call {d or '<lambda>'}(..., interpret=True) "
                           "hard-codes interpret mode; pass the resolved "
                           "backend or leave the default")

        if self._in_traced():
            if last in _HOST_SYNC_METHODS and isinstance(node.func,
                                                         ast.Attribute):
                self._emit("host-sync-in-jit", node,
                           f".{last}() forces a device->host sync inside a "
                           "traced function")
            elif last == "device_get" and _root(d) == "jax":
                self._emit("host-sync-in-jit", node,
                           "jax.device_get inside a traced function")
            elif (_root(d) in _NUMPY_ROOTS and last in ("asarray", "array")
                  and node.args
                  and not isinstance(node.args[0], ast.Constant)):
                self._emit("host-sync-in-jit", node,
                           f"{d}() materializes on host inside a traced "
                           "function (use jnp)")
            elif d in ("float", "int", "bool") and node.args:
                inner = _contains_jax_call(node.args[0])
                if inner:
                    self._emit("host-sync-in-jit", node,
                               f"{d}({inner}(...)) concretizes a traced "
                               "value (device->host sync)")
            if (last in _OBS_MUTATORS
                    and isinstance(node.func, ast.Attribute)
                    and _chain_parts(node.func.value) & _OBS_RECEIVERS):
                self._emit("obs-no-hot-path-sync", node,
                           f"telemetry mutation .{last}() inside a traced "
                           "function records a trace-time constant (or "
                           "forces a host callback); record at the host "
                           "boundary after the step returns")

        self._check_names_in_call(node, d, last)
        self.generic_visit(node)

    def _check_names_in_call(self, node, d: str, last: str):
        from repro.analysis import names as _names

        root = _root(d)
        # registry calls: codecs.get("..."), policies.get("...")
        if last in ("get", "validate_name") and node.args and isinstance(
                node.args[0], ast.Constant) and isinstance(
                    node.args[0].value, str):
            if root == "codecs":
                self._name_finding("container-name", node.args[0],
                                   _names.check_container(
                                       node.args[0].value))
            elif root == "policies":
                self._name_finding("policy-name", node.args[0],
                                   _names.check_policy(node.args[0].value))
        # known keywords anywhere: container=..., policy=...
        for kw in node.keywords:
            if not (isinstance(kw.value, ast.Constant)
                    and isinstance(kw.value.value, str)):
                continue
            if kw.arg in _CONTAINER_KWARGS:
                self._name_finding("container-name", kw.value,
                                   _names.check_container(kw.value.value))
            elif kw.arg == "policy":
                self._name_finding("policy-name", kw.value,
                                   _names.check_policy(kw.value.value))
        # argparse: add_argument("--kv-container", default="...")
        if last == "add_argument":
            flags = [a.value for a in node.args
                     if isinstance(a, ast.Constant)
                     and isinstance(a.value, str)]
            is_container = any("container" in f or f.endswith("-codec")
                               for f in flags)
            is_policy = any("policy" in f for f in flags)
            for kw in node.keywords:
                if kw.arg not in ("default", "const"):
                    continue
                if not (isinstance(kw.value, ast.Constant)
                        and isinstance(kw.value.value, str)):
                    continue
                if is_container:
                    self._name_finding(
                        "container-name", kw.value,
                        _names.check_container(kw.value.value))
                elif is_policy:
                    self._name_finding(
                        "policy-name", kw.value,
                        _names.check_policy(kw.value.value))

    def _name_finding(self, rule: str, node, error: Optional[str]):
        if error:
            self._emit(rule, node, error)

    def visit_Assign(self, node):
        targets = [t.id for t in node.targets if isinstance(t, ast.Name)]
        self._check_name_assign(targets, node.value)
        self.generic_visit(node)

    def visit_AnnAssign(self, node):
        if isinstance(node.target, ast.Name) and node.value is not None:
            self._check_name_assign([node.target.id], node.value)
        self.generic_visit(node)

    def _check_name_assign(self, targets: List[str], value):
        if not (isinstance(value, ast.Constant)
                and isinstance(value.value, str)):
            return
        from repro.analysis import names as _names
        for t in targets:
            tl = t.lower()
            if tl in _CONTAINER_KWARGS or tl.endswith("_container"):
                self._name_finding("container-name", value,
                                   _names.check_container(value.value))
            elif tl == "policy" or tl.endswith("_policy"):
                self._name_finding("policy-name", value,
                                   _names.check_policy(value.value))

    def _check_truthiness(self, test, kind: str):
        if not self._in_traced():
            return
        inner = _contains_jax_call(test)
        if inner:
            self._emit("traced-truthiness", test,
                       f"Python {kind} on traced expression {inner}(...) — "
                       "use lax.cond/jnp.where (or checkify for asserts)")

    def visit_If(self, node):
        self._check_truthiness(node.test, "if")
        self.generic_visit(node)

    def visit_While(self, node):
        self._check_truthiness(node.test, "while")
        self.generic_visit(node)

    def visit_Assert(self, node):
        self._check_truthiness(node.test, "assert")
        self.generic_visit(node)

    def visit_Attribute(self, node):
        if node.attr == "float64" and _root(_dotted(node)) in (
                _JAX_ROOTS | _NUMPY_ROOTS) - {"np", "numpy", "onp"}:
            self._emit("float64", node,
                       f"{_dotted(node)}: 64-bit floats are outside every "
                       "container geometry here (and silently downcast "
                       "without x64)")
        self.generic_visit(node)

    def visit_Constant(self, node):
        if node.value == "jax_enable_x64" and node.lineno not in \
                self.docstrings:
            self._emit("float64", node,
                       "enabling x64 flips global dtype semantics; "
                       "containers assume <= 32-bit floats")
        self.generic_visit(node)


def lint_source(source: str, path: str) -> List[Finding]:
    """Run every AST rule over one module's source."""
    tree = ast.parse(source, filename=path)
    collector = _TracedCollector()
    collector.visit(tree)
    findings: List[Finding] = []
    _Lint(path, collector.traced, _docstring_linenos(tree),
          findings).visit(tree)
    # astype("float64") / dtype="float64" string form.
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            d = _dotted(node.func)
            args = list(node.args) + [kw.value for kw in node.keywords
                                      if kw.arg in ("dtype", None)]
            if _last(d) in ("astype", "asarray", "zeros", "ones", "full",
                            "array", "dtype", "convert_element_type"):
                for a in args:
                    if isinstance(a, ast.Constant) and a.value == "float64":
                        findings.append(Finding(
                            rule="float64", path=path, line=a.lineno,
                            scope=_last(d),
                            message=f'{d}(..., "float64") introduces '
                                    "64-bit floats"))
    return findings


def run_lints(roots: List[pathlib.Path],
              repo_root: pathlib.Path) -> List[Finding]:
    findings: List[Finding] = []
    for root in roots:
        files = [root] if root.is_file() else sorted(root.rglob("*.py"))
        for py in files:
            rel = py.relative_to(repo_root).as_posix()
            # The analyzer necessarily embeds the very patterns it hunts
            # (rule-trigger strings, force_backend sweeps) — never self-lint.
            if rel.startswith("src/repro/analysis/"):
                continue
            findings.extend(lint_source(py.read_text(), rel))
    return findings
