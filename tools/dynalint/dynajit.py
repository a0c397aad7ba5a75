"""dynajit: static compilation-stability & device-residency analysis
(DL015-DL017).

The engine's load-bearing invariant — *no XLA compile ever happens
mid-serving; ``warmup()`` pre-compiles the full bucket grid* — is pure
discipline: one unbucketed shape reaching a jitted call, one
request-varying ``static_argnames`` value, and every distinct value pays
a multi-second serve-time compile that stalls every in-flight request.
Donation discipline is just as silent: a donated buffer read after its
jit call is a correctness bug XLA only reports at runtime (and only
sometimes). This pass makes both checkable, on the same shared AST parse
and call graph as dynaflow/dynarace.

The analysis types values along two axes:

- **shape provenance** — ``BUCKETED`` (int literals, ``EngineConfig``
  /``ModelConfig`` attribute reads, and anything laundered through a
  bucket helper: ``bucket_batch``/``prefill_bucket_batch``/``bucket_len``
  /``bucket_pages``/``_pick``/``_long_bucket``/``_pad_pow2``/
  ``choose_prefill_bucket``), ``RAW``
  (request-varying: ``len(...)`` of request data, ``List``-annotated
  parameters, list comprehensions — their length is data-dependent), or
  ``UNKNOWN``. Only definitely-RAW shapes are reported: a whole-program
  lint must never guess.
- **device residency** — ``DEVICE`` (returns of jitted calls, the engine
  KV pools/params, ``jnp.*`` constructors and ops over device values) vs
  ``HOST`` (``np.*`` results, host pools, Python scalars) vs unknown.

Rules (tier-1-enforced with an EMPTY baseline):

- **DL015 recompile-hazard** — a jitted call site (a resolved
  ``@jax.jit`` function, or the engine's ``self.<name>_fn`` step-fn
  convention) taking an argument whose shape is RAW, a
  ``static_argnames``/``static_argnums`` value that is request-varying,
  or a device-pool gather (``self.kv_k[:, idx]``) whose index shape is
  RAW — each distinct shape/value is one serve-time XLA compile.
  (Warmup coverage — entries dispatched at serving time that
  ``warmup()`` never exercises — lives in dynaform's DL026 call-form
  matching, which subsumes the per-entry check this rule used to own.)
- **DL016 donation-discipline** — (a) a donated argument (the callee's
  ``donate_argnames``/``donate_argnums``, or the ``self.kv_k``/
  ``self.kv_v`` pool-donation convention of the step fns) that is
  neither rebound by the calling statement nor dead afterwards: the
  buffer is invalid the moment the call dispatches; (b) a jitted
  function that updates a parameter in place (``param.at[...]``) and
  returns it without donating it — XLA keeps a second copy of the
  buffer in HBM.
- **DL017 implicit-host-transfer** — a device-typed value flowing into
  a host-transfer sink (``np.asarray``/``np.array``/``.item()``/
  ``.tolist()``/``float()``/``int()``/``bool()``/iteration). Value-flow
  based, so it catches the assignments-then-sync shapes the
  callsite-pattern DL005 cannot — and stays quiet on ``np.asarray`` of
  host lists, which DL005's pattern match cannot distinguish. Applies
  to every non-jitted function in engine modules (``HOT_SYNC_ALLOWLIST``
  members excluded — they ARE the designed sync points), and
  chain-reports sinks reached from hot step functions through sync
  helpers, exactly like interprocedural DL005.

Suppression: the usual ``# dynalint: disable=<rule>`` on the line or the
line above. Policy (docs/static_analysis.md): fix RAW shapes by
laundering through a bucket helper; suppress only where the transfer or
the shape variance is the operation's documented purpose (e.g. the
disagg extract — the D2H *is* the product).
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .analyzer import (HOT_SYNC_ALLOWLIST, RULES, ModuleSource,
                       Violation, call_attr, dotted)
from .callgraph import DEFAULT_DL008_DEPTH, CallGraph
from .dynahot import HOT_FRAME_RE

# ------------------------------------------------------------------- config

# modules scanned for jit definitions (DL016b) — the device-code tree
DEVICE_MODULE_MARKERS = ("engine/", "models/", "parallel/", "ops/")
# modules whose call sites are checked (DL015/016a/017) — the serving layer
ENGINE_MARKER = "engine/"

# shape-laundering helpers: their RESULT is bucketed regardless of input
# (that is their whole job). New helpers must be added here AND warmed.
BUCKET_HELPERS = frozenset({
    "bucket_batch", "prefill_bucket_batch", "bucket_len", "bucket_pages",
    "_pick", "_pad_pow2", "_long_bucket", "choose_prefill_bucket",
})
# attribute bases whose reads are config-static (never request-varying)
CONFIG_BASE_RE = re.compile(
    r"^(self\.)?(ecfg|cfg|mcfg|model_cfg|engine_cfg|config)$")
# self-attributes that are config-derived scalars
CONFIG_SELF_ATTRS = frozenset({"cap_pages", "cap_tokens", "spec_steps"})
# device pools (config-static shapes; kv_k/kv_v are donated by convention
# at every self.<name>_fn step call)
DEVICE_POOL_ATTRS = frozenset({"kv_k", "kv_v", "params"})
DONATED_POOL_ATTRS = frozenset({"kv_k", "kv_v"})
HOST_POOL_ATTRS = frozenset({"host_k", "host_v", "host_k_s", "host_v_s"})
# the engine step-fn convention: `self.<x>_fn(...)` is a jitted entry
JIT_ATTR_RE = re.compile(r"_fn$")

NP_BASES = ("np", "numpy")
JNP_BASES = ("jnp", "jax.numpy")
CONSTRUCTORS = frozenset({"zeros", "full", "ones", "empty", "arange"})
ELEMENTWISE = frozenset({"where", "minimum", "maximum", "clip", "mod"})
TRANSFER_SINK_ATTRS = frozenset({"item", "tolist"})
TRANSFER_SINK_BUILTINS = frozenset({"float", "int", "bool"})
LIST_ANNOT_RE = re.compile(r"^(typing\.)?(List|Sequence|list)\b")

# provenance lattice: B (bucketed/static) < U (unknown) < R (raw)
B, U, R = 0, 1, 2
# residency
DEV, HOST, UNK = "dev", "host", "unk"

_SCALAR = object()  # shape sentinel for scalar-valued expressions


def _join(*provs: int) -> int:
    return max(provs) if provs else U


@dataclass
class Prov:
    """(dim, shape, residency, elem) for one expression.

    ``dim`` — provenance of the VALUE used as an array dimension;
    ``shape`` — provenance of the expression's own array shape
    (B for scalars: a scalar's shape is statically ``()``);
    ``dev`` — device residency; ``elem`` — provenance of the elements
    when the value is iterated (loop targets inherit it)."""

    dim: int = 1            # U
    shape: int = 1          # U
    dev: str = UNK
    elem: Optional["Prov"] = None

    @staticmethod
    def bucketed(dev: str = HOST) -> "Prov":
        return Prov(B, B, dev, None)

    @staticmethod
    def raw(dev: str = UNK) -> "Prov":
        return Prov(R, R, dev, None)


UNKNOWN = Prov()


@dataclass
class JitInfo:
    """Statically-extracted jit metadata for one decorated function."""

    key: str                 # callgraph key '<module>:<qualname>'
    name: str
    path: str
    lineno: int
    params: List[str] = field(default_factory=list)
    static_names: Set[str] = field(default_factory=set)
    static_nums: Set[int] = field(default_factory=set)
    donate_names: Set[str] = field(default_factory=set)
    donate_nums: Set[int] = field(default_factory=set)

    def donated_params(self) -> Set[str]:
        out = set(self.donate_names)
        for i in self.donate_nums:
            if 0 <= i < len(self.params):
                out.add(self.params[i])
        return out

    def static_params(self) -> Set[str]:
        out = set(self.static_names)
        for i in self.static_nums:
            if 0 <= i < len(self.params):
                out.add(self.params[i])
        return out


# --------------------------------------------------------- jit collection

def _literal_set(node: ast.AST) -> Optional[Tuple]:
    try:
        v = ast.literal_eval(node)
    except (ValueError, SyntaxError):
        return None
    if isinstance(v, (str, int)):
        return (v,)
    if isinstance(v, (tuple, list, set)):
        return tuple(v)
    return None


def _jit_decorator_kw(dec: ast.AST) -> Optional[List[ast.keyword]]:
    """``@jax.jit`` → []; ``@partial(jax.jit, ...)`` /
    ``@functools.partial(jax.jit, ...)`` → its keywords; else None."""
    if isinstance(dec, ast.Attribute) or isinstance(dec, ast.Name):
        if dotted(dec) in ("jax.jit", "jit"):
            return []
        return None
    if not isinstance(dec, ast.Call):
        return None
    d = dotted(dec.func)
    if d in ("jax.jit", "jit"):
        return dec.keywords
    if d in ("partial", "functools.partial") and dec.args \
            and dotted(dec.args[0]) in ("jax.jit", "jit"):
        return dec.keywords
    return None


class _JitCollector(ast.NodeVisitor):
    """Find every jit-decorated def in a module (including nested defs
    inside builder functions) and its static/donate metadata."""

    def __init__(self, ms: ModuleSource, modname: str):
        self.ms = ms
        self.modname = modname
        self.jits: Dict[str, JitInfo] = {}   # key -> info
        self._stack: List[str] = []

    def _visit_func(self, node) -> None:
        qual = ".".join(self._stack + [node.name])
        kw = None
        for dec in node.decorator_list:
            kw = _jit_decorator_kw(dec)
            if kw is not None:
                break
        if kw is not None:
            info = JitInfo(key=f"{self.modname}:{qual}", name=node.name,
                           path=self.ms.path, lineno=node.lineno,
                           params=[a.arg for a in node.args.posonlyargs
                                   + node.args.args])
            for k in kw:
                vals = _literal_set(k.value) if k.arg else None
                if vals is None:
                    continue
                if k.arg == "static_argnames":
                    info.static_names |= {v for v in vals
                                          if isinstance(v, str)}
                elif k.arg == "static_argnums":
                    info.static_nums |= {v for v in vals
                                         if isinstance(v, int)}
                elif k.arg == "donate_argnames":
                    info.donate_names |= {v for v in vals
                                          if isinstance(v, str)}
                elif k.arg == "donate_argnums":
                    info.donate_nums |= {v for v in vals
                                         if isinstance(v, int)}
            self.jits[info.key] = info
        self._stack.append(node.name)
        self.generic_visit(node)
        self._stack.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._stack.append(node.name)
        self.generic_visit(node)
        self._stack.pop()


def collect_jits(sources: Sequence[ModuleSource]) -> Dict[str, JitInfo]:
    from .callgraph import module_name

    jits: Dict[str, JitInfo] = {}
    for ms in sources:
        norm = ms.path.replace("\\", "/")
        if not any(m in norm for m in DEVICE_MODULE_MARKERS):
            continue
        c = _JitCollector(ms, module_name(ms.path))
        c.visit(ms.tree)
        jits.update(c.jits)
    return jits


# ------------------------------------------------------- DL016(b) def check

def check_undonated_writes(sources: Sequence[ModuleSource],
                           jits: Dict[str, JitInfo]) -> List[Violation]:
    """A jitted def that updates a param via ``param.at[...]`` and
    returns it without donating it keeps two copies of the buffer in
    HBM. Reported at the def."""
    name, summary = RULES["DL016"]
    by_path: Dict[str, ModuleSource] = {ms.path: ms for ms in sources}
    out: List[Violation] = []
    for key in sorted(jits):
        info = jits[key]
        ms = by_path.get(info.path)
        if ms is None:
            continue
        node = _find_def(ms.tree, info)
        if node is None:
            continue
        donated = info.donated_params()
        written: Set[str] = set()
        returned: Set[str] = set()
        for sub in ast.walk(node):
            if isinstance(sub, ast.Attribute) and sub.attr == "at" \
                    and isinstance(sub.value, ast.Name) \
                    and sub.value.id in info.params:
                written.add(sub.value.id)
            if isinstance(sub, ast.Return) and sub.value is not None:
                for n in ast.walk(sub.value):
                    if isinstance(n, ast.Name):
                        returned.add(n.id)
        for p in sorted((written & returned) - donated):
            if _suppressed(ms, info.lineno, "DL016"):
                continue
            out.append(Violation(
                info.path, info.lineno, 0, "DL016", name,
                f"{summary}: jitted `{info.name}` updates param `{p}` via "
                f".at[] and returns it without donating it — add it to "
                f"donate_argnames so XLA aliases the buffer in place",
                info.name))
    return out


def _find_def(tree: ast.AST, info: JitInfo):
    for sub in ast.walk(tree):
        if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and sub.name == info.name and sub.lineno >= info.lineno - 8 \
                and sub.lineno <= info.lineno + 8:
            return sub
    return None


# ----------------------------------------------------------- the flow scan

def _suppressed(ms: ModuleSource, line: int, code: str) -> bool:
    name = RULES[code][0]
    for probe in (line, line - 1):
        tags = ms.suppressed.get(probe)
        if tags and (code in tags or name in tags or "all" in tags):
            return True
    return False


def _allowlisted(qualname: str) -> bool:
    return qualname in HOT_SYNC_ALLOWLIST or any(
        qualname.startswith(a + ".") for a in HOT_SYNC_ALLOWLIST)


@dataclass
class FuncJitScan:
    """Per-function results: DL017 sink records for chain propagation."""

    key: str
    qualname: str
    transfer_sinks: List[Tuple[int, str]] = field(default_factory=list)


class _FlowScan(ast.NodeVisitor):
    """One ENGINE module: provenance/residency dataflow over every
    non-jitted function (nested defs share the enclosing scope chain —
    closures read outer locals), emitting DL015/DL016(a)/DL017."""

    def __init__(self, ms: ModuleSource, modname: str, graph: CallGraph,
                 jits: Dict[str, JitInfo]):
        self.ms = ms
        self.modname = modname
        self.graph = graph
        self.jits = jits
        # direct violations only in the serving layer (engine modules);
        # models/parallel/ops modules still contribute DL017 sink records
        # so hot engine functions chain-report transfers they reach
        self.report = ENGINE_MARKER in ms.path.replace("\\", "/")
        self.violations: List[Violation] = []
        self.func_scans: Dict[str, FuncJitScan] = {}
        # jitted entries called from serving code / from warmup bodies:
        # display-name -> representative (path, line)
        self.serving_entries: Dict[str, Tuple[str, int]] = {}
        self.warmed_entries: Set[str] = set()
        self._classes: List[str] = []
        self._funcs: List[str] = []
        self._scopes: List[Dict[str, Prov]] = []
        self._scan: List[Optional[FuncJitScan]] = []
        self._mod = graph.modules.get(modname)

    # ------------------------------------------------------------ plumbing

    def _qualname(self) -> str:
        return ".".join(self._classes + self._funcs) or "<module>"

    def _emit(self, node: ast.AST, code: str, detail: str) -> None:
        if not self.report:
            return
        line = getattr(node, "lineno", 0)
        if _suppressed(self.ms, line, code):
            return
        name, summary = RULES[code]
        self.violations.append(Violation(
            self.ms.path, line, getattr(node, "col_offset", 0), code,
            name, f"{summary}: {detail}", self._qualname()))

    # ------------------------------------------------------------- scoping

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._classes.append(node.name)
        self.generic_visit(node)
        self._classes.pop()

    def _visit_func(self, node) -> None:
        # jitted bodies trace on device — host-transfer/provenance rules
        # do not apply inside them (DL016b covers their discipline)
        if any(_jit_decorator_kw(d) is not None
               for d in node.decorator_list):
            return
        qual = ".".join(self._classes + self._funcs + [node.name])
        fs = FuncJitScan(key=f"{self.modname}:{qual}", qualname=qual)
        self.func_scans[fs.key] = fs
        scope: Dict[str, Prov] = {}
        for a in node.args.posonlyargs + node.args.args + [
                node.args.vararg, node.args.kwarg] + node.args.kwonlyargs:
            if a is None:
                continue
            ann = ast.unparse(a.annotation) if a.annotation else ""
            if LIST_ANNOT_RE.match(ann) and "ndarray" not in ann:
                scope[a.arg] = Prov(R, R, HOST)
            else:
                scope[a.arg] = UNKNOWN
        self._funcs.append(node.name)
        self._scopes.append(scope)
        self._scan.append(fs)
        for stmt in node.body:
            self.visit(stmt)
        self._scan.pop()
        self._scopes.pop()
        self._funcs.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    def _lookup(self, name: str) -> Prov:
        for scope in reversed(self._scopes):
            if name in scope:
                return scope[name]
        return UNKNOWN

    def _bind(self, name: str, prov: Prov) -> None:
        if self._scopes:
            old = self._scopes[-1].get(name)
            if old is not None and old is not UNKNOWN:
                # flow-insensitive join of re-assignments
                prov = Prov(_join(old.dim, prov.dim),
                            _join(old.shape, prov.shape),
                            prov.dev if prov.dev == old.dev else UNK,
                            prov.elem or old.elem)
            self._scopes[-1][name] = prov

    # -------------------------------------------------------- the evaluator

    def eval(self, node: Optional[ast.AST]) -> Prov:  # noqa: C901
        if node is None:
            return Prov.bucketed()
        if isinstance(node, ast.Constant):
            return Prov(B, B, HOST)
        if isinstance(node, ast.Name):
            return self._lookup(node.id)
        if isinstance(node, ast.Attribute):
            return self._eval_attr(node)
        if isinstance(node, ast.Call):
            return self._eval_call(node)
        if isinstance(node, ast.Tuple) or isinstance(node, ast.Set):
            elts = [self.eval(e) for e in node.elts]
            return Prov(_join(*[p.dim for p in elts]) if elts else B,
                        _join(*[p.shape for p in elts]) if elts else B,
                        DEV if any(p.dev == DEV for p in elts) else HOST
                        if all(p.dev == HOST for p in elts) else UNK,
                        elts[0] if elts else None)
        if isinstance(node, ast.List):
            # display: a FIXED number of elements — static length
            elts = [self.eval(e) for e in node.elts]
            return Prov(U, B, HOST, elts[0] if elts else None)
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp)):
            # data-dependent length
            elem = (self._elem_of(node.generators[0].iter)
                    if isinstance(node.elt, ast.Name)
                    and node.generators and isinstance(
                        node.generators[0].target, ast.Name)
                    and node.elt.id == node.generators[0].target.id
                    else self.eval(node.elt))
            return Prov(U, R, HOST, elem)
        if isinstance(node, ast.IfExp):
            a, b = self.eval(node.body), self.eval(node.orelse)
            return Prov(_join(a.dim, b.dim), _join(a.shape, b.shape),
                        a.dev if a.dev == b.dev else UNK, a.elem)
        if isinstance(node, ast.BinOp):
            return self._eval_binop(node)
        if isinstance(node, ast.UnaryOp):
            return self.eval(node.operand)
        if isinstance(node, ast.BoolOp):
            provs = [self.eval(v) for v in node.values]
            return Prov(_join(*[p.dim for p in provs]),
                        _join(*[p.shape for p in provs]), UNK, None)
        if isinstance(node, ast.Compare):
            shapes = [self.eval(node.left).shape] + \
                [self.eval(c).shape for c in node.comparators]
            return Prov(U, _join(*shapes), UNK, None)
        if isinstance(node, ast.Subscript):
            return self._eval_subscript(node)
        if isinstance(node, ast.Starred):
            return self.eval(node.value)
        return UNKNOWN

    def _eval_attr(self, node: ast.Attribute) -> Prov:
        base = dotted(node.value)
        if base is not None and CONFIG_BASE_RE.match(base):
            return Prov(B, B, HOST)
        if base in ("self",):
            if node.attr in DEVICE_POOL_ATTRS:
                return Prov(U, B, DEV)
            if node.attr in HOST_POOL_ATTRS:
                return Prov(U, B, HOST)
            if node.attr in CONFIG_SELF_ATTRS:
                return Prov(B, B, HOST)
        # any other attribute read: request-varying as a DIMENSION value,
        # unknown as an array
        return Prov(R, U, UNK)

    def _elem_of(self, node: ast.AST) -> Prov:
        if isinstance(node, ast.Attribute):
            base = dotted(node.value)
            if base is not None and CONFIG_BASE_RE.match(base):
                return Prov(B, B, HOST)
            return UNKNOWN
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            provs = [self.eval(e) for e in node.elts]
            return Prov(_join(*[p.dim for p in provs]) if provs else B,
                        _join(*[p.shape for p in provs]) if provs else B,
                        HOST, None)
        if isinstance(node, ast.Call):
            d = dotted(node.func)
            tail = d.rsplit(".", 1)[-1] if d else None
            if tail in ("sorted", "set", "list", "tuple", "reversed") \
                    and node.args:
                return self._elem_of(node.args[0])
            if tail == "range":
                return Prov(_join(*[self.eval(a).dim for a in node.args]),
                            B, HOST)
            if tail == "enumerate" or tail == "zip":
                return UNKNOWN
        if isinstance(node, ast.Name):
            p = self._lookup(node.id)
            return p.elem or UNKNOWN
        p = self.eval(node)
        return p.elem or UNKNOWN

    def _eval_binop(self, node: ast.BinOp) -> Prov:
        left, right = self.eval(node.left), self.eval(node.right)
        # scalar/static-shaped operands broadcast: join the non-static
        # operand shapes (a raw-length list concatenation stays raw)
        shapes = [p.shape for p in (left, right) if p.shape != B]
        shape = _join(*shapes) if shapes else B
        dev = DEV if DEV in (left.dev, right.dev) else (
            HOST if left.dev == right.dev == HOST else UNK)
        return Prov(_join(left.dim, right.dim), shape, dev, left.elem)

    def _eval_subscript(self, node: ast.Subscript) -> Prov:
        value = self.eval(node.value)
        idx = node.slice
        parts = list(idx.elts) if isinstance(idx, ast.Tuple) else [idx]
        arr_parts = [p for p in parts if not isinstance(
            p, (ast.Slice, ast.Constant))]
        if not arr_parts:
            # pure slicing / constant index: view of, or element of, the
            # subscripted value
            if any(isinstance(p, ast.Slice) for p in parts):
                return Prov(value.dim, value.shape, value.dev, value.elem)
            return value.elem or Prov(U, U, value.dev)
        ip = [self.eval(p) for p in arr_parts]
        ishape = _join(*[p.shape for p in ip])
        # a gather's result shape follows the INDEX shape: a raw-length
        # index into a device pool is one XLA compile per distinct length
        if value.dev == DEV and ishape == R:
            self._emit(node, "DL015",
                       f"device gather `{ast.unparse(node)[:60]}` with a "
                       f"request-varying index shape — each distinct "
                       f"length is one XLA compile; pad through "
                       f"`_pad_pow2`/a bucket helper")
        return Prov(U, ishape, value.dev, None)

    # ---------------------------------------------------------------- calls

    def _jit_callee(self, node: ast.Call) -> Tuple[Optional[str],
                                                   Optional[JitInfo]]:
        """(display-name, JitInfo|None) when this is a jitted call site."""
        d = dotted(node.func)
        if d is None:
            return None, None
        parts = d.split(".")
        if parts[0] == "self" and len(parts) == 2 \
                and JIT_ATTR_RE.search(parts[1]):
            return parts[1], None          # step-fn convention
        # resolved project function with jit metadata
        if self._mod is not None:
            first = self._qualname().split(".")[0]
            cls_name = first if first in self._mod.classes else None
            fi = self._mod.functions.get(self._qualname())
            fi_key = self.graph._resolve(
                self._mod, d, cls_name, fi if fi is not None else _DUMMY_FI)
            if fi_key is not None and fi_key in self.jits:
                return d.rsplit(".", 1)[-1], self.jits[fi_key]
        return None, None

    def _eval_call(self, node: ast.Call) -> Prov:  # noqa: C901
        d = dotted(node.func)
        tail = d.rsplit(".", 1)[-1] if d else call_attr(node)
        base = d.rsplit(".", 1)[0] if d and "." in d else None

        if tail in BUCKET_HELPERS:
            for a in node.args:
                self.eval(a)
            return Prov(B, B, HOST, Prov(B, B, HOST))
        if base not in NP_BASES and base not in JNP_BASES:
            if tail == "len":
                return Prov(R, B, HOST)
            if tail in ("min", "max", "sum", "abs", "round"):
                provs = [self.eval(a) for a in node.args]
                return Prov(_join(*[p.dim for p in provs]) if provs else U,
                            _join(*[p.shape for p in provs]) if provs
                            else B, HOST, None)
            if tail in ("sorted", "set", "list", "tuple") and node.args:
                inner = self.eval(node.args[0])
                return Prov(U, inner.shape, HOST,
                            self._elem_of(node.args[0]))

        if base in NP_BASES or base in JNP_BASES:
            dev = DEV if base in JNP_BASES else HOST
            if tail in CONSTRUCTORS:
                shape = self._shape_arg_prov(node)
                return Prov(U, shape, dev)
            if tail in ("asarray", "array"):
                src = self.eval(node.args[0]) if node.args else UNKNOWN
                if dev == HOST and src.dev == DEV:
                    self._transfer_sink(node, f"`{d}(...)` on a "
                                              f"device value")
                return Prov(src.dim, src.shape, dev, src.elem)
            if tail in ELEMENTWISE:
                provs = [self.eval(a) for a in node.args]
                shapes = [p.shape for p in provs
                          if p.shape != B]  # scalars broadcast away
                return Prov(U, _join(*shapes) if shapes else B, dev)
            if tail == "bincount" or tail == "unique":
                for a in node.args:
                    self.eval(a)
                return Prov(U, U, dev)
            for a in node.args:
                self.eval(a)
            return Prov(U, U, dev)

        # host-transfer builtin sinks: float(dev) / int(dev) / bool(dev)
        if isinstance(node.func, ast.Name) \
                and node.func.id in TRANSFER_SINK_BUILTINS and node.args:
            src = self.eval(node.args[0])
            if src.dev == DEV:
                self._transfer_sink(node, f"`{node.func.id}()` on a "
                                          f"device value")
            return Prov(U, B, HOST)
        # .item() / .tolist() on a device value
        if call_attr(node) in TRANSFER_SINK_ATTRS \
                and isinstance(node.func, ast.Attribute):
            src = self.eval(node.func.value)
            if src.dev == DEV:
                self._transfer_sink(node, f"`.{call_attr(node)}()` on a "
                                          f"device value")
            return Prov(U, B, HOST)

        jit_name, info = self._jit_callee(node)
        if jit_name is not None:
            return self._check_jit_call(node, jit_name, info)

        for a in node.args:
            self.eval(a)
        for k in node.keywords:
            self.eval(k.value)
        # a method call on a device receiver stays on device (.sum(),
        # .astype(), .reshape(), ...) — .item()/.tolist() were handled
        # above as transfer sinks
        if isinstance(node.func, ast.Attribute):
            recv = self.eval(node.func.value)
            if recv.dev == DEV:
                return Prov(U, U, DEV)
        return UNKNOWN

    def _shape_arg_prov(self, node: ast.Call) -> int:
        """np.zeros(shape)/np.full(shape, fill)/np.arange(a[, b]): the
        result's shape provenance comes from the DIM values."""
        tail = (dotted(node.func) or "").rsplit(".", 1)[-1]
        args = node.args[:2] if tail == "arange" else node.args[:1]
        dims: List[int] = []
        for a in args:
            if isinstance(a, ast.Tuple):
                for e in a.elts:
                    if isinstance(e, ast.Starred):
                        p = self.eval(e.value)
                        dims.append(_join(p.dim, p.shape))
                    else:
                        dims.append(self.eval(e).dim)
            else:
                p = self.eval(a)
                # a shape TUPLE variable: its element values are the dims
                dims.append(p.dim if p.elem is None else
                            _join(p.dim, p.elem.dim))
        return _join(*dims) if dims else U

    def _check_jit_call(self, node: ast.Call, name: str,
                        info: Optional[JitInfo]) -> Prov:
        """DL015 shape/static-value checks + DL016(a) donation checks at
        one jitted call site; result is device-resident with the join of
        the argument shape provenances."""
        self._note_entry(name, node)
        arg_provs: List[Prov] = []
        static_params = info.static_params() if info else set()
        static_nums = info.static_nums if info else set()
        params = info.params if info else []
        for i, a in enumerate(node.args):
            p = self.eval(a)
            arg_provs.append(p)
            pname = params[i] if i < len(params) else None
            if i in static_nums or (pname and pname in static_params):
                if p.dim == R:
                    self._emit(node, "DL015",
                               f"static arg {i} of `{name}` takes a "
                               f"request-varying value — every distinct "
                               f"value is one serve-time XLA compile")
                continue
            if p.shape == R:
                self._emit(node, "DL015",
                           f"arg {i} (`{ast.unparse(a)[:48]}`) of jitted "
                           f"`{name}` has a request-varying shape — "
                           f"launder it through a bucket helper "
                           f"(bucket_batch/bucket_len/bucket_pages/"
                           f"_pad_pow2)")
        for k in node.keywords:
            p = self.eval(k.value)
            if k.arg and k.arg in static_params and p.dim == R:
                self._emit(node, "DL015",
                           f"static arg `{k.arg}` of `{name}` takes a "
                           f"request-varying value — every distinct "
                           f"value is one serve-time XLA compile")
            elif k.arg and p.shape == R:
                self._emit(node, "DL015",
                           f"arg `{k.arg}` of jitted `{name}` has a "
                           f"request-varying shape — launder it through "
                           f"a bucket helper")
        self._check_donation(node, name, info)
        shape = _join(*[p.shape for p in arg_provs if p.shape != B]) \
            if any(p.shape != B for p in arg_provs) else B
        return Prov(U, shape, DEV)

    # ------------------------------------------------------ DL016(a) calls

    def _check_donation(self, node: ast.Call, name: str,
                        info: Optional[JitInfo]) -> None:
        donated: List[ast.AST] = []
        if info is not None:
            dparams = info.donated_params()
            for i, a in enumerate(node.args):
                pname = info.params[i] if i < len(info.params) else None
                if pname in dparams or i in info.donate_nums:
                    donated.append(a)
        else:
            # engine step-fn convention: the KV pools are donated
            for a in node.args:
                if isinstance(a, ast.Attribute) \
                        and isinstance(a.value, ast.Name) \
                        and a.value.id == "self" \
                        and a.attr in DONATED_POOL_ATTRS:
                    donated.append(a)
        if not donated:
            return
        stmt = node
        parent = getattr(node, "_dl_parent", None)
        while parent is not None and not isinstance(parent, ast.stmt):
            stmt = parent
            parent = getattr(parent, "_dl_parent", None)
        stmt = parent if isinstance(parent, ast.stmt) else stmt
        rebound: Set[str] = set()
        if isinstance(stmt, ast.Assign):
            for t in stmt.targets:
                for n in ast.walk(t):
                    nd = dotted(n)
                    if nd:
                        rebound.add(nd)
        fn_node = self._enclosing_fn_node(node)
        after = getattr(stmt, "end_lineno", None) or \
            getattr(node, "end_lineno", node.lineno)
        for a in donated:
            ad = dotted(a)
            if ad is None or ad in rebound:
                continue
            use = self._load_after(fn_node, ad, after) \
                if fn_node is not None else None
            if use is not None:
                self._emit(use, "DL016",
                           f"`{ad}` was donated to `{name}` at line "
                           f"{node.lineno} and is used here afterwards — "
                           f"the buffer is invalid once the call "
                           f"dispatches; rebind it from the call's "
                           f"result")

    def _enclosing_fn_node(self, node: ast.AST):
        cur = getattr(node, "_dl_parent", None)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return cur
            cur = getattr(cur, "_dl_parent", None)
        return None

    def _load_after(self, fn_node, name: str, line: int):
        """First Load of ``name`` after ``line`` with no intervening
        rebinding store (textual order — the donated-use-after shape)."""
        events: List[Tuple[int, int, str, ast.AST]] = []
        for sub in ast.walk(fn_node):
            nd = dotted(sub)
            if nd != name:
                continue
            ln = getattr(sub, "lineno", 0)
            if ln <= line:
                continue
            ctx = getattr(sub, "ctx", None)
            kind = "store" if isinstance(ctx, ast.Store) else "load"
            events.append((ln, getattr(sub, "col_offset", 0), kind, sub))
        for ln, _col, kind, sub in sorted(events, key=lambda e: (e[0],
                                                                 e[1])):
            if kind == "store":
                return None
            return sub
        return None

    # --------------------------------------------------- DL017 + coverage

    def _transfer_sink(self, node: ast.AST, what: str) -> None:
        qual = self._qualname()
        if _allowlisted(qual):
            return
        line = getattr(node, "lineno", 0)
        if self._scan and self._scan[-1] is not None:
            if not _suppressed(self.ms, line, "DL017"):
                self._scan[-1].transfer_sinks.append((line, what))
        # direct report for non-jitted ENGINE functions (_emit no-ops
        # elsewhere); sinks in models/parallel/ops chain-report at the
        # hot engine call site via check_transitive_transfer
        self._emit(node, "DL017", what)

    def _note_entry(self, name: str, node: ast.AST) -> None:
        if not self.report:
            return  # serving/warmed entries are an engine-layer notion
        fn = self._funcs[0] if self._funcs else "<module>"
        if fn == "warmup":
            self.warmed_entries.add(name)
        else:
            self.serving_entries.setdefault(
                name, (self.ms.path, getattr(node, "lineno", 0)))

    # ------------------------------------------------------------ visitors

    def visit_Assign(self, node: ast.Assign) -> None:
        prov = self.eval(node.value)
        for t in node.targets:
            self._bind_target(t, prov)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        if node.value is not None:
            self._bind_target(node.target, self.eval(node.value))

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        prov = self.eval(node.value)
        if isinstance(node.target, ast.Name):
            old = self._lookup(node.target.id)
            self._bind(node.target.id,
                       Prov(_join(old.dim, prov.dim),
                            _join(old.shape if old.shape != B else B,
                                  B if prov.shape == B else prov.shape),
                            old.dev, old.elem))

    def _bind_target(self, t: ast.AST, prov: Prov) -> None:
        if isinstance(t, ast.Name):
            self._bind(t.id, prov)
        elif isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                # tuple-unpack of a call result: residency flows to every
                # target (out_d, acc_d = verify_greedy_draft(...))
                self._bind_target(e, Prov(U, U, prov.dev))
        elif isinstance(t, (ast.Subscript, ast.Attribute)):
            self.eval(t.value if isinstance(t, ast.Attribute) else t.value)

    def visit_For(self, node: ast.For) -> None:
        it = self.eval(node.iter)
        # a tuple/list LITERAL of device values is host iteration over
        # array objects, not a device sync
        if it.dev == DEV and not isinstance(
                node.iter, (ast.Tuple, ast.List, ast.Set)):
            self._transfer_sink(node, "iteration over a device value "
                                      "syncs every element to host")
        elem = self._elem_of(node.iter)
        self._bind_target(node.target,
                          elem if isinstance(node.target, ast.Name)
                          else UNKNOWN)
        for stmt in node.body + node.orelse:
            self.visit(stmt)

    visit_AsyncFor = visit_For

    def visit_Expr(self, node: ast.Expr) -> None:
        self.eval(node.value)

    def visit_Return(self, node: ast.Return) -> None:
        if node.value is not None:
            self.eval(node.value)

    def visit_If(self, node: ast.If) -> None:
        self.eval(node.test)
        for stmt in node.body + node.orelse:
            self.visit(stmt)

    def visit_While(self, node: ast.While) -> None:
        self.eval(node.test)
        for stmt in node.body + node.orelse:
            self.visit(stmt)

    def _visit_with(self, node) -> None:
        for item in node.items:
            self.eval(item.context_expr)
        for stmt in node.body:
            self.visit(stmt)

    visit_With = _visit_with
    visit_AsyncWith = _visit_with

    def visit_Try(self, node: ast.Try) -> None:
        for stmt in (node.body + node.orelse + node.finalbody
                     + [s for h in node.handlers for s in h.body]):
            self.visit(stmt)

    def visit_Await(self, node: ast.Await) -> None:
        self.eval(node.value)

    def visit_Raise(self, node: ast.Raise) -> None:
        if node.exc is not None:
            self.eval(node.exc)

    def visit_Delete(self, node: ast.Delete) -> None:
        pass


class _DummyFI:
    qualname = "<module>"
    calls: List = []


_DUMMY_FI = _DummyFI()


# ---------------------------------------------------- chain DL017 reporting

def check_transitive_transfer(graph: CallGraph,
                              scans: Dict[str, FuncJitScan],
                              max_depth: int = DEFAULT_DL008_DEPTH
                              ) -> List[Violation]:
    """DL017 sinks reached from an engine hot-path (step) function
    through sync helpers fire at the hot call site with the full chain —
    the same shape as interprocedural DL005, sharing its allowlist."""
    reach: Dict[str, Tuple[int, List[str], str, int, str]] = {}
    for key, fs in scans.items():
        fi = graph.functions.get(key)
        if fi is None or fi.is_async or _allowlisted(fs.qualname) \
                or not fs.transfer_sinks:
            continue
        line, what = fs.transfer_sinks[0]
        reach[key] = (0, [key], fi.path, line, what)
    changed = True
    while changed:
        changed = False
        for fi in graph.functions.values():
            if fi.is_async or _allowlisted(fi.qualname):
                continue
            for cs in fi.calls:
                sub = reach.get(cs.target) if cs.target else None
                if sub is None:
                    continue
                callee = graph.functions.get(cs.target)
                if callee is None or callee.is_async \
                        or _allowlisted(callee.qualname):
                    continue
                depth = sub[0] + 1
                cur = reach.get(fi.key)
                if depth <= max_depth and (cur is None or depth < cur[0]):
                    reach[fi.key] = (depth, [fi.key] + sub[1], sub[2],
                                     sub[3], sub[4])
                    changed = True

    name, summary = RULES["DL017"]
    out: List[Violation] = []
    seen: Set[Tuple[str, str]] = set()
    for fi in graph.functions.values():
        if ENGINE_MARKER not in fi.path.replace("\\", "/"):
            continue
        if not HOT_FRAME_RE.search(fi.name) or _allowlisted(fi.qualname):
            continue
        mod = graph.modules[fi.module]
        for cs in fi.calls:
            sub = reach.get(cs.target) if cs.target else None
            if sub is None or cs.target == fi.key:
                continue
            callee = graph.functions.get(cs.target)
            if sub[0] == 0 and callee is not None and ENGINE_MARKER in \
                    callee.path.replace("\\", "/"):
                continue  # engine sinks were already reported directly
            if callee is not None and HOT_FRAME_RE.search(callee.name):
                continue
            if (fi.key, cs.target) in seen:
                continue
            seen.add((fi.key, cs.target))
            suppressed = False
            for probe in (cs.line, cs.line - 1):
                tags = mod.suppressed.get(probe)
                if tags and ({"DL017", name, "all"} & tags):
                    suppressed = True
            if suppressed:
                continue
            chain = " -> ".join(k.split(":", 1)[1] for k in sub[1])
            out.append(Violation(
                fi.path, cs.line, cs.col, "DL017", name,
                f"{summary}: `{cs.raw}` reaches {sub[4]} via {chain} "
                f"({sub[2]}:{sub[3]})", fi.qualname))
    return out


# ------------------------------------------------------------------ driver

def analyze_jit(sources: Sequence[ModuleSource],
                graph: Optional[CallGraph] = None) -> List[Violation]:
    """Run the dynajit passes (DL015/DL016/DL017) over already-loaded
    modules, reusing a shared call graph when given. Warmup coverage —
    which jitted entries serving dispatches that warmup() never
    exercises — moved to dynaform's DL026, where it is subsumed by full
    call-form matching (dtype/provenance/kwarg-set per site)."""
    from .callgraph import module_name

    if graph is None:
        graph = CallGraph.build(sources)
    jits = collect_jits(sources)
    out: List[Violation] = []
    out.extend(check_undonated_writes(sources, jits))
    scans: Dict[str, FuncJitScan] = {}
    for ms in sources:
        norm = ms.path.replace("\\", "/")
        if not any(m in norm for m in DEVICE_MODULE_MARKERS):
            continue
        scan = _FlowScan(ms, module_name(ms.path), graph, jits)
        scan.visit(ms.tree)
        out.extend(scan.violations)
        scans.update(scan.func_scans)
    out.extend(check_transitive_transfer(graph, scans))
    out.sort(key=lambda v: (v.path, v.line, v.code))
    return out
