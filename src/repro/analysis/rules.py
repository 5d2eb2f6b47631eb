"""AST lint rules for JAX/Pallas-specific footguns.

Every rule has a stable ID, a severity, and a one-line rationale; the
runner (:mod:`repro.analysis.runner`) applies them to parsed modules,
honours ``# lint: disable=ID`` suppressions, and subtracts a committed
baseline. The rules are deliberately narrow: each one encodes a failure
mode that silently destroys the paper's complexity story (host syncs in
solver loops, f64 promotion, PRNG key reuse) without ever failing a
functional test.

Rule catalogue
--------------
RA101  prng-key-reuse        error    two PRNG keys built from the same
                                      seed expression share randomness
RA102  traced-python-branch  error    Python ``if``/``while`` on a traced
                                      value inside a jitted function
RA103  host-sync-in-loop     warning  ``float()`` / ``.item()`` /
                                      ``np.asarray`` / ``block_until_ready``
                                      inside a Python loop (device sync per
                                      iteration)
RA104  implicit-promotion    warning  identity arithmetic with bare Python
                                      scalars (``* 1.0``, ``+ 0.0``) or the
                                      builtin ``float`` used as a dtype —
                                      silent f64 promotion under x64
RA105  mutable-default       error    mutable default argument (shared
                                      across calls; breaks pytree configs)
RA106  banned-import         error    ``scipy`` / ``torch`` imports under
                                      ``src/repro`` (``jax.scipy`` is fine)
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Iterator

__all__ = ["Finding", "ModuleContext", "Rule", "ALL_RULES", "RULES_BY_ID",
           "BANNED_IMPORT_ROOTS"]

BANNED_IMPORT_ROOTS = ("scipy", "torch")


@dataclass
class Finding:
    """One analyzer finding; ``fingerprint`` is filled in by the runner."""
    rule: str
    severity: str            # "error" | "warning"
    path: str
    line: int
    col: int
    message: str
    fingerprint: str = ""

    def format(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: "
                f"{self.rule} [{self.severity}] {self.message}")

    def to_json(self) -> dict:
        return {"rule": self.rule, "severity": self.severity,
                "path": self.path, "line": self.line, "col": self.col,
                "message": self.message, "fingerprint": self.fingerprint}


@dataclass
class ModuleContext:
    """Parsed module handed to every rule."""
    path: str
    source: str
    tree: ast.Module
    lines: list[str] = field(default_factory=list)

    @classmethod
    def from_source(cls, source: str, path: str) -> "ModuleContext":
        return cls(path=path, source=source, tree=ast.parse(source),
                   lines=source.splitlines())


class Rule:
    """Base class: subclasses set ``id``/``severity`` and implement check."""

    id: str = ""
    name: str = ""
    severity: str = "error"
    rationale: str = ""

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        raise NotImplementedError

    def finding(self, ctx: ModuleContext, node: ast.AST,
                message: str) -> Finding:
        return Finding(rule=self.id, severity=self.severity, path=ctx.path,
                       line=getattr(node, "lineno", 1),
                       col=getattr(node, "col_offset", 0), message=message)


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------
def _attr_tail(node: ast.AST) -> str:
    """Final attribute / name of a dotted expression (``a.b.c`` -> ``c``)."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return ""


def _dotted(node: ast.AST) -> str:
    """Full dotted name of an expression, or "" if not a plain chain."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _seed_signature(node: ast.AST):
    """Structural signature of a seed expression, base names erased.

    ``cfg.seed + 1`` and ``state.config.seed + 1`` normalise to the same
    signature (both read a ``.seed`` attribute and add 1), which is exactly
    the aliasing that makes key reuse hard to spot in review.
    """
    if isinstance(node, ast.Constant):
        return ("const", repr(node.value))
    if isinstance(node, ast.Name):
        return ("name",)
    if isinstance(node, ast.Attribute):
        return ("attr", node.attr)
    if isinstance(node, ast.BinOp):
        return ("binop", type(node.op).__name__,
                _seed_signature(node.left), _seed_signature(node.right))
    if isinstance(node, ast.UnaryOp):
        return ("unary", type(node.op).__name__,
                _seed_signature(node.operand))
    if isinstance(node, ast.Call):
        return ("call", _dotted(node.func) or _attr_tail(node.func),
                tuple(_seed_signature(a) for a in node.args))
    return ("other", ast.dump(node))


# --------------------------------------------------------------------------
# RA101: PRNG key reuse
# --------------------------------------------------------------------------
class PrngKeyReuseRule(Rule):
    id = "RA101"
    name = "prng-key-reuse"
    severity = "error"
    rationale = ("Two jax.random.PRNGKey calls built from the same seed "
                 "expression produce identical keys: the code paths silently "
                 "share randomness. Derive sub-keys with jax.random.fold_in "
                 "or jax.random.split instead.")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        # PRNGKey(expr) fed straight into fold_in(key, tag) is the
        # sanctioned way to derive distinct streams from one base seed —
        # the tag differentiates them, so same-seed matches are fine.
        folded: set[int] = set()
        for node in ast.walk(ctx.tree):
            if (isinstance(node, ast.Call)
                    and _attr_tail(node.func) in ("fold_in", "split")
                    and node.args
                    and isinstance(node.args[0], ast.Call)):
                folded.add(id(node.args[0]))
        seen: dict = {}
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if _attr_tail(node.func) != "PRNGKey" or len(node.args) != 1:
                continue
            if id(node) in folded:
                continue
            sig = _seed_signature(node.args[0])
            first = seen.get(sig)
            if first is None:
                seen[sig] = node
                continue
            expr = ast.unparse(node.args[0])
            yield self.finding(
                ctx, node,
                f"PRNGKey seed expression {expr!r} matches the key built at "
                f"line {first.lineno}: the two keys are identical and the "
                "paths share randomness; derive distinct streams with "
                "jax.random.fold_in")


# --------------------------------------------------------------------------
# RA102: Python branch on traced values inside jit
# --------------------------------------------------------------------------
def _jit_static_names(dec: ast.AST, func: ast.FunctionDef) -> list[str] | None:
    """Param names made static by a jit decorator, or None if not a jit.

    Recognises ``@jax.jit``, ``@jit``, and
    ``@functools.partial(jax.jit, static_argnames=..., static_argnums=...)``.
    """
    def is_jit(expr: ast.AST) -> bool:
        return _dotted(expr) in ("jit", "jax.jit")

    if is_jit(dec):
        return []
    if (isinstance(dec, ast.Call)
            and _attr_tail(dec.func) == "partial"
            and dec.args and is_jit(dec.args[0])):
        static: list[str] = []
        args = ([a.arg for a in func.args.posonlyargs]
                + [a.arg for a in func.args.args]
                + [a.arg for a in func.args.kwonlyargs])
        for kw in dec.keywords:
            if kw.arg == "static_argnames":
                for el in ast.walk(kw.value):
                    if isinstance(el, ast.Constant) and isinstance(el.value, str):
                        static.append(el.value)
            if kw.arg == "static_argnums":
                for el in ast.walk(kw.value):
                    if isinstance(el, ast.Constant) and isinstance(el.value, int):
                        if 0 <= el.value < len(args):
                            static.append(args[el.value])
        return static
    return None


def _is_static_test(test: ast.AST) -> bool:
    """Tests that are legal on tracers: ``x is None``, isinstance checks."""
    if isinstance(test, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops):
        return True
    if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
        return _is_static_test(test.operand)
    if isinstance(test, ast.Call) and _attr_tail(test.func) == "isinstance":
        return True
    return False


class TracedBranchRule(Rule):
    id = "RA102"
    name = "traced-python-branch"
    severity = "error"
    rationale = ("A Python if/while on a traced value inside a jitted "
                 "function either raises a ConcretizationTypeError or — "
                 "when the value is silently concretised — forces a host "
                 "sync and a retrace per distinct value. Use jnp.where / "
                 "lax.cond / lax.while_loop.")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for func in ast.walk(ctx.tree):
            if not isinstance(func, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            static: list[str] | None = None
            for dec in func.decorator_list:
                names = _jit_static_names(dec, func)
                if names is not None:
                    static = names
                    break
            if static is None:
                continue
            params = {a.arg for a in (func.args.posonlyargs + func.args.args
                                      + func.args.kwonlyargs)}
            traced = params - set(static) - {"self", "cls"}
            for node in ast.walk(func):
                if not isinstance(node, (ast.If, ast.While)):
                    continue
                if _is_static_test(node.test):
                    continue
                used = {n.id for n in ast.walk(node.test)
                        if isinstance(n, ast.Name)}
                hits = sorted(used & traced)
                if hits:
                    kind = "if" if isinstance(node, ast.If) else "while"
                    yield self.finding(
                        ctx, node,
                        f"Python `{kind}` on potentially traced value(s) "
                        f"{', '.join(hits)} inside jitted function "
                        f"{func.name!r}; use jnp.where / lax.cond / "
                        "lax.while_loop (or mark the argument static)")


# --------------------------------------------------------------------------
# RA103: host syncs inside Python loops
# --------------------------------------------------------------------------
_SYNC_BUILTINS = {"float", "int", "bool"}
# host_read: repro.core.telemetry's counted np.asarray, a sync all the same
_SYNC_ATTRS = {"item", "block_until_ready", "device_get", "asarray", "array",
               "host_read"}


class HostSyncInLoopRule(Rule):
    id = "RA103"
    name = "host-sync-in-loop"
    severity = "warning"
    rationale = ("float()/.item()/np.asarray/jax.device_get/host_read on a "
                 "device value blocks on the accelerator; inside a Python loop "
                 "(e.g. a solver driver) that is one sync per iteration and "
                 "the async dispatch pipeline is dead.")

    def _sync_call(self, node: ast.Call) -> str | None:
        if isinstance(node.func, ast.Name):
            if (node.func.id in _SYNC_BUILTINS and node.args
                    and not isinstance(node.args[0], ast.Constant)):
                return f"{node.func.id}()"
            if node.func.id == "host_read":
                return "host_read()"
            return None
        if isinstance(node.func, ast.Attribute):
            tail = node.func.attr
            if tail == "item":
                return ".item()"
            if tail in ("asarray", "array"):
                root = _dotted(node.func).split(".")[0]
                if root in ("np", "numpy"):
                    return f"{root}.{tail}()"
                return None
            if tail in _SYNC_ATTRS:
                return f"{_dotted(node.func) or tail}()"
        return None

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        # Only meaningful in modules that can hold device values.
        if not _imports_jax(ctx.tree):
            return
        # A call nested in several loops is reached once per enclosing
        # loop by this walk — report each call node exactly once.
        seen: set[int] = set()
        for loop in ast.walk(ctx.tree):
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            for node in ast.walk(loop):
                if not isinstance(node, ast.Call) or id(node) in seen:
                    continue
                seen.add(id(node))
                what = self._sync_call(node)
                if what is not None:
                    yield self.finding(
                        ctx, node,
                        f"{what} inside a Python loop forces a host sync "
                        "per iteration if the value lives on device; hoist "
                        "it out of the loop or keep the loop on-device "
                        "(lax.while_loop / lax.fori_loop)")


def _imports_jax(tree: ast.Module) -> bool:
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            if any(a.name.split(".")[0] == "jax" for a in node.names):
                return True
        if isinstance(node, ast.ImportFrom):
            if (node.module or "").split(".")[0] == "jax":
                return True
    return False


# --------------------------------------------------------------------------
# RA104: implicit dtype promotion via bare Python scalars
# --------------------------------------------------------------------------
class ImplicitPromotionRule(Rule):
    id = "RA104"
    name = "implicit-promotion"
    severity = "warning"
    rationale = ("Identity arithmetic with a bare Python float (* 1.0, "
                 "+ 0.0) is a no-op that can still promote weak dtypes, and "
                 "the builtin `float` used as a dtype means float64 under "
                 "x64 — both silently double memory traffic.")

    def _identity_op(self, node: ast.BinOp) -> str | None:
        left, right = node.left, node.right
        def is_const(n, v):
            return (isinstance(n, ast.Constant)
                    and isinstance(n.value, float) and n.value == v)
        if isinstance(node.op, ast.Mult):
            if is_const(left, 1.0) or is_const(right, 1.0):
                return "* 1.0"
        if isinstance(node.op, ast.Div) and is_const(right, 1.0):
            return "/ 1.0"
        if isinstance(node.op, ast.Add):
            if is_const(left, 0.0) or is_const(right, 0.0):
                return "+ 0.0"
        if isinstance(node.op, ast.Sub) and is_const(right, 0.0):
            return "- 0.0"
        return None

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.BinOp):
                op = self._identity_op(node)
                if op is not None:
                    yield self.finding(
                        ctx, node,
                        f"identity arithmetic `{op}` with a bare Python "
                        "scalar: a no-op that can promote weak dtypes — "
                        "drop it or make the dtype explicit")
            if isinstance(node, ast.Call):
                # x.astype(float) / jnp.asarray(x, float) / dtype=float
                is_float = lambda a: isinstance(a, ast.Name) and a.id in (
                    "float", "int")
                if (isinstance(node.func, ast.Attribute)
                        and node.func.attr == "astype" and node.args
                        and is_float(node.args[0])):
                    yield self.finding(
                        ctx, node,
                        "astype(float) is float64 under x64; name the dtype "
                        "explicitly (e.g. jnp.float32)")
                for kw in node.keywords:
                    if kw.arg == "dtype" and is_float(kw.value):
                        yield self.finding(
                            ctx, node,
                            "dtype=float is float64 under x64; name the "
                            "dtype explicitly (e.g. jnp.float32)")


# --------------------------------------------------------------------------
# RA105: mutable default arguments
# --------------------------------------------------------------------------
class MutableDefaultRule(Rule):
    id = "RA105"
    name = "mutable-default"
    severity = "error"
    rationale = ("A mutable default ([], {}, set()) is created once and "
                 "shared across every call — state leaks between calls, and "
                 "on pytree dataclasses it aliases leaves between "
                 "instances.")

    def _is_mutable(self, node: ast.AST) -> bool:
        if isinstance(node, (ast.List, ast.Dict, ast.Set)):
            return True
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id in ("list", "dict", "set") and not node.args
                and not node.keywords):
            return True
        return False

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for func in ast.walk(ctx.tree):
            if not isinstance(func, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.Lambda)):
                continue
            defaults = list(func.args.defaults) + [
                d for d in func.args.kw_defaults if d is not None]
            for d in defaults:
                if self._is_mutable(d):
                    name = getattr(func, "name", "<lambda>")
                    yield self.finding(
                        ctx, d,
                        f"mutable default argument in {name!r} is shared "
                        "across calls; default to None and create inside "
                        "the body (or use dataclasses.field(default_factory))")


# --------------------------------------------------------------------------
# RA106: banned imports (scipy / torch under src/repro)
# --------------------------------------------------------------------------
class BannedImportRule(Rule):
    id = "RA106"
    name = "banned-import"
    severity = "error"
    rationale = ("The library must stay importable from jax + numpy alone: "
                 "scipy (the real package — jax.scipy is fine) and torch "
                 "must not be imported anywhere under src/repro, at module "
                 "or function level.")

    def __init__(self, banned: tuple[str, ...] = BANNED_IMPORT_ROOTS):
        self.banned = banned

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    root = a.name.split(".")[0]
                    if root in self.banned:
                        yield self.finding(
                            ctx, node,
                            f"import of banned dependency {a.name!r}: "
                            f"{root} must not be used under src/repro")
            elif isinstance(node, ast.ImportFrom):
                if node.level:    # relative import, never a banned root
                    continue
                root = (node.module or "").split(".")[0]
                if root in self.banned:
                    yield self.finding(
                        ctx, node,
                        f"import from banned dependency {node.module!r}: "
                        f"{root} must not be used under src/repro")


ALL_RULES: tuple[Rule, ...] = (
    PrngKeyReuseRule(),
    TracedBranchRule(),
    HostSyncInLoopRule(),
    ImplicitPromotionRule(),
    MutableDefaultRule(),
    BannedImportRule(),
)

RULES_BY_ID = {r.id: r for r in ALL_RULES}
