"""The R1-R5 rule implementations: one AST pass per file.

Analysis model (deliberately per-module and heuristic — this is a lint
pass, not a type checker):

- **Traced roots** are functions literally handed to a tracing entry
  point (``jax.jit``/``shard_map``/``lax.scan``/``jax.vmap``/``grad``/
  ``value_and_grad``/``checkpoint``/``custom_vjp``/``defvjp``/pjit) or
  decorated with one.  Everything lexically inside a traced root is a
  *traced region*; functions *called* from a traced region (matched by
  name against module-level/nested defs) are traced transitively.
- **Traced-ish values** (R1/R5 only): inside a DIRECT traced root every
  parameter except ``self``/``cls`` is seeded as traced; inside
  transitively-traced functions only values derived from ``jnp.``/
  ``lax.``/``jax.nn``/``jax.random`` calls are.  A single forward pass
  propagates through assignments, arithmetic, subscripts and calls,
  stopping at static surfaces (``.shape``/``.dtype``/``.ndim``,
  ``jax.tree_util`` structure helpers, ``len``/``isinstance``/...).
  This errs toward silence: a helper with config-string parameters
  never has them flagged as traced.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass


RULES = {
    "R1": "host-sync call or implicit bool() branch on a traced value "
          "inside a jit/shard_map region",
    "R2": "retrace hazard: jit/shard_map constructed per call or inside "
          "a loop, or unhashable static args",
    "R3": "collective axis name not in the mesh axis vocabulary / "
          "enclosing shard_map specs",
    "R4": "donation hygiene: donated buffer reused after the call, or "
          "engine entry point (jit of shard_map) without donate_argnums",
    "R5": "dtype-promotion trap: float64 constructor or dtype=float in "
          "traced code, accumulator carry inheriting input dtype",
    "R6": "checkpoint_name label outside the discovered remat-name "
          "vocabulary (a typo'd label silently degrades a named remat "
          "policy to save-nothing)",
}

# Mesh axis vocabulary fallback when no mesh.py is found on the lint path.
# "slice" is the hierarchical outer axis (ISSUE 13) — a framework-standard
# name like the others; a discovered mesh.py overrides this set entirely.
DEFAULT_AXIS_VOCAB = frozenset(
    {"data", "model", "pipe", "seq", "expert", "fsdp", "slice"})

# Named-activation vocabulary fallback when no models/__init__.py
# REMAT_NAMES constant is found on the lint path (ISSUE 15).  R6 is the
# R3 construction applied to checkpoint_name labels: like a typo'd axis
# name, a label outside the vocabulary doesn't error — it just never
# matches a --remat_policy save_names:/offload_names: set, silently
# degrading the policy to save-NOTHING for that activation.
DEFAULT_REMAT_NAME_VOCAB = frozenset(
    {"attn_out", "mlp_out", "block_out", "moe_dispatch", "flash_out",
     "flash_lse"})

# Call spellings whose string label R6 validates (the repo imports the
# jax primitive under its own name; dotted jax spellings included so
# direct uses lint too).
_CHECKPOINT_NAME_CALLS = {
    "checkpoint_name", "jax.ad_checkpoint.checkpoint_name",
    "ad_checkpoint.checkpoint_name",
}

# Call targets (dotted-suffix spellings) that make their first function
# argument a traced root.
_TRACER_CALLS = {
    "jax.jit", "jit", "pjit", "jax.pmap", "pmap",
    "jax.vmap", "vmap", "jax.grad", "grad",
    "jax.value_and_grad", "value_and_grad",
    "jax.checkpoint", "checkpoint", "jax.remat", "remat",
    "jax.custom_vjp", "custom_vjp", "jax.custom_jvp", "custom_jvp",
    "shard_map", "jax.shard_map",
    "lax.scan", "jax.lax.scan", "scan",
}
# jit-like spellings (compile + cache semantics) for R2/R4.
_JIT_CALLS = {"jax.jit", "jit", "pjit"}
_SHARD_MAP_CALLS = {"shard_map", "jax.shard_map"}

# lax collectives whose axis-name argument R3 validates.
# name -> index of the positional axis argument.
_COLLECTIVES = {
    "psum": 1, "pmean": 1, "pmax": 1, "pmin": 1, "ppermute": 1,
    "all_gather": 1, "all_to_all": 1, "psum_scatter": 1,
    "axis_index": 0, "axis_size": 0, "pbroadcast": 1, "pshuffle": 1,
}

# Module roots whose call results are traced-ish.
_ARRAY_ROOTS = ("jnp", "lax", "jax")
# Call basenames that return host/static values even on traced arguments
# (structure inspection, python builtins) — they BREAK the traced chain.
_CHAIN_BREAKERS = {
    "len", "isinstance", "getattr", "hasattr", "type", "print", "range",
    "enumerate", "zip", "tuple", "list", "dict", "set", "sorted", "repr",
    "str", "id", "tree_structure", "tree_flatten", "tree_leaves",
    "tree_unflatten", "tree_map", "ShapeDtypeStruct", "dtype", "format",
    # static under tracing too: lax.axis_size is a Python int (the bound
    # mesh axis's size), jax.typeof an aval (shape/dtype/vma metadata)
    "axis_size", "typeof",
}
# Attribute reads that yield static metadata, not traced values.
_STATIC_ATTRS = {"shape", "dtype", "ndim", "size", "sharding",
                 "is_fully_addressable", "addressable_shards"}

# R1 host-sync method calls on traced values.
_HOST_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
# R1 host-sync free calls when fed a traced value.
_HOST_SYNC_CALLS = {"np.asarray", "np.array", "numpy.asarray",
                    "numpy.array", "jax.device_get", "device_get",
                    "float", "int", "bool"}
# R5 float64-forcing constructors (anywhere in a traced region).
_F64_CALLS = {"np.float64", "numpy.float64", "np.double", "numpy.double",
              "jnp.float64"}


@dataclass
class RawFinding:
    """One rule hit before suppression/baseline filtering."""

    rule: str
    line: int
    col: int
    message: str


def _dotted(node: ast.AST) -> str | None:
    """Dotted name of a call target: ``jax.lax.psum`` -> "jax.lax.psum";
    None for non-name expressions (subscripts, calls)."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _suffix_in(dotted: str | None, names: set[str]) -> bool:
    """True when ``dotted`` equals any entry or ends with ``.entry`` for
    a dotted entry (``jax.lax.scan`` matches "lax.scan")."""
    if dotted is None:
        return False
    if dotted in names:
        return True
    return any(dotted.endswith("." + n) for n in names)


def _basename(dotted: str | None) -> str | None:
    return dotted.rsplit(".", 1)[-1] if dotted else None


def _const_str(node: ast.AST) -> str | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _func_args(fn: ast.FunctionDef | ast.AsyncFunctionDef | ast.Lambda
               ) -> list[str]:
    a = fn.args
    names = [x.arg for x in (*a.posonlyargs, *a.args, *a.kwonlyargs)]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return names


_FUNCS = (ast.FunctionDef, ast.AsyncFunctionDef)


class _ModuleIndex:
    """Module-wide context: traced roots, transitive closure, parents."""

    def __init__(self, tree: ast.Module):
        self.tree = tree
        self.parent: dict[ast.AST, ast.AST] = {}
        self.defs_by_name: dict[str, list[ast.AST]] = {}
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                self.parent[child] = node
            if isinstance(node, _FUNCS):
                self.defs_by_name.setdefault(node.name, []).append(node)

        direct: set[ast.AST] = set()   # function nodes passed to a tracer
        names: set[str] = set()        # names passed to a tracer
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                d = _dotted(node.func)
                if _suffix_in(d, _TRACER_CALLS) and node.args:
                    arg = node.args[0]
                    if isinstance(arg, ast.Lambda):
                        direct.add(arg)
                    else:
                        base = _basename(_dotted(arg))
                        if base:
                            names.add(base)
                elif d is not None and d.endswith(".defvjp"):
                    for arg in node.args:
                        base = _basename(_dotted(arg))
                        if base:
                            names.add(base)
            if isinstance(node, _FUNCS):
                for dec in node.decorator_list:
                    dd = _dotted(dec if not isinstance(dec, ast.Call)
                                 else dec.func)
                    if _suffix_in(dd, _TRACER_CALLS):
                        direct.add(node)
                    # @partial(jax.jit, ...) / @functools.partial(jit, ...)
                    if (isinstance(dec, ast.Call)
                            and _basename(dd) == "partial" and dec.args):
                        inner = _dotted(dec.args[0])
                        if _suffix_in(inner, _TRACER_CALLS):
                            direct.add(node)
        for name in names:
            direct.update(self.defs_by_name.get(name, []))
        self.direct_roots = direct

        # transitive closure: defs CALLED from a traced region are traced
        traced: set[ast.AST] = set(direct)
        work = list(direct)
        while work:
            fn = work.pop()
            for node in ast.walk(fn):
                if isinstance(node, ast.Call):
                    base = _basename(_dotted(node.func))
                    for cand in self.defs_by_name.get(base or "", []):
                        if cand not in traced:
                            traced.add(cand)
                            work.append(cand)
        self.traced_funcs = traced

    def enclosing_function(self, node: ast.AST):
        cur = self.parent.get(node)
        while cur is not None and not isinstance(cur, (*_FUNCS, ast.Lambda)):
            cur = self.parent.get(cur)
        return cur

    def in_traced_region(self, node: ast.AST) -> bool:
        cur: ast.AST | None = node
        while cur is not None:
            if cur in self.traced_funcs or cur in self.direct_roots:
                return True
            cur = self.parent.get(cur)
        return False

    def in_loop(self, node: ast.AST) -> bool:
        """Lexically inside a for/while body (within the same function)."""
        cur = self.parent.get(node)
        while cur is not None and not isinstance(cur, (*_FUNCS, ast.Lambda)):
            if isinstance(cur, (ast.For, ast.While, ast.AsyncFor)):
                return True
            cur = self.parent.get(cur)
        return False


class _TracedValues:
    """Single-forward-pass traced-ish value propagation for one function."""

    def __init__(self, fn, *, seed_params: bool):
        self.traced: set[str] = set()
        if seed_params and not isinstance(fn, ast.Lambda):
            self.traced.update(a for a in _func_args(fn)
                               if a not in ("self", "cls"))
        elif seed_params:
            self.traced.update(_func_args(fn))

    def expr_is_traced(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Name):
            return node.id in self.traced
        if isinstance(node, ast.Attribute):
            if node.attr in _STATIC_ATTRS:
                return False
            # attribute reads only stay traced when their base is
            # (self.<x> is config, x.T / x.at are array surface)
            return self.expr_is_traced(node.value)
        if isinstance(node, ast.Call):
            d = _dotted(node.func)
            base = _basename(d)
            if base in _CHAIN_BREAKERS:
                return False
            if d is not None and (d.split(".", 1)[0] in _ARRAY_ROOTS):
                return True
            args = list(node.args) + [k.value for k in node.keywords]
            return any(self.expr_is_traced(a) for a in args)
        if isinstance(node, (ast.BinOp,)):
            return (self.expr_is_traced(node.left)
                    or self.expr_is_traced(node.right))
        if isinstance(node, ast.UnaryOp):
            return self.expr_is_traced(node.operand)
        if isinstance(node, ast.BoolOp):
            return any(self.expr_is_traced(v) for v in node.values)
        if isinstance(node, ast.Compare):
            return (self.expr_is_traced(node.left)
                    or any(self.expr_is_traced(c) for c in node.comparators))
        if isinstance(node, ast.Subscript):
            return self.expr_is_traced(node.value)
        if isinstance(node, (ast.Tuple, ast.List)):
            return any(self.expr_is_traced(e) for e in node.elts)
        if isinstance(node, ast.Starred):
            return self.expr_is_traced(node.value)
        if isinstance(node, ast.IfExp):
            return (self.expr_is_traced(node.body)
                    or self.expr_is_traced(node.orelse))
        return False

    def note_assign(self, node: ast.AST) -> None:
        targets: list[ast.AST] = []
        if isinstance(node, ast.Assign):
            targets, value = node.targets, node.value
        elif isinstance(node, ast.AugAssign):
            targets, value = [node.target], node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets, value = [node.target], node.value
        else:
            return
        is_traced = self.expr_is_traced(value)
        for t in targets:
            for n in ast.walk(t):
                if isinstance(n, ast.Name):
                    if is_traced:
                        self.traced.add(n.id)
                    else:
                        self.traced.discard(n.id)


def _is_none_test(node: ast.AST) -> bool:
    """``x is None`` / ``x is not None`` — a static structure test."""
    return (isinstance(node, ast.Compare)
            and len(node.ops) == 1
            and isinstance(node.ops[0], (ast.Is, ast.IsNot)))


def _call_kw(call: ast.Call, name: str):
    for k in call.keywords:
        if k.arg == name:
            return k.value
    return None


def _iter_axis_names(node: ast.AST):
    """String-literal axis names in an axis argument (str or tuple/list)."""
    if isinstance(node, (ast.Tuple, ast.List)):
        for e in node.elts:
            yield from _iter_axis_names(e)
    else:
        s = _const_str(node)
        if s is not None:
            yield s, node


def _shard_map_spec_axes(call: ast.Call, axis_vocab: frozenset[str]
                         ) -> set[str] | None:
    """Statically visible axis names in a shard_map call's arguments.

    Returns None when any spec is dynamic (a bare Name or call we cannot
    see into beyond ``P(...)``), in which case the subset check is
    skipped — silence over false positives.  Only the spec kwargs are
    scanned: ``mesh`` is virtually always a variable, and treating it as
    dynamic would disable the check for every realistic call site."""
    axes: set[str] = set()
    dynamic = False
    for kw in call.keywords:
        if kw.arg not in ("in_specs", "out_specs"):
            continue
        for node in ast.walk(kw.value):
            if isinstance(node, ast.Constant) and isinstance(node.value, str):
                if node.value in axis_vocab:
                    axes.add(node.value)
            elif isinstance(node, ast.Name) and node.id.endswith("_AXIS"):
                axes.add(node.id)  # resolved by the caller via vocab map
            elif isinstance(node, ast.Name) and node.id not in ("P", "None"):
                dynamic = True
    return None if dynamic else axes


def lint_source(src: str, path: str = "<string>",
                axis_vocab: frozenset[str] | None = None,
                axis_constants: dict[str, str] | None = None,
                remat_vocab: frozenset[str] | None = None
                ) -> list[RawFinding]:
    """All R1-R6 findings for one file's source (pre-suppression)."""
    vocab = axis_vocab or DEFAULT_AXIS_VOCAB
    consts = axis_constants or {}
    rvocab = remat_vocab or DEFAULT_REMAT_NAME_VOCAB
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [RawFinding("R2", e.lineno or 1, 0,
                           f"file does not parse: {e.msg}")]
    idx = _ModuleIndex(tree)
    findings: list[RawFinding] = []

    def emit(rule: str, node: ast.AST, msg: str) -> None:
        findings.append(RawFinding(rule, getattr(node, "lineno", 1),
                                   getattr(node, "col_offset", 0), msg))

    # ---- per-function R1/R5 traced-value analysis ---------------------
    for fn in sorted(idx.traced_funcs | idx.direct_roots,
                     key=lambda f: getattr(f, "lineno", 0)):
        tv = _TracedValues(fn, seed_params=fn in idx.direct_roots)
        body = fn.body if isinstance(fn.body, list) else [fn.body]
        nested = {n for b in body for n in ast.walk(b)
                  if isinstance(n, (*_FUNCS, ast.Lambda))}
        for stmt in body:
            for node in ast.walk(stmt):
                # skip nodes owned by a nested def (analyzed separately)
                owner = idx.enclosing_function(node)
                if owner is not fn and owner in nested:
                    continue
                tv.note_assign(node)
                if isinstance(node, ast.Call):
                    d = _dotted(node.func)
                    base = _basename(d)
                    # R1: .item()/.tolist()/block_until_ready on traced
                    if (isinstance(node.func, ast.Attribute)
                            and node.func.attr in _HOST_SYNC_METHODS
                            and tv.expr_is_traced(node.func.value)):
                        emit("R1", node,
                             f".{node.func.attr}() on a traced value "
                             "forces a device->host sync inside the "
                             "traced region")
                    # R1: np.asarray/float/int/bool/device_get on traced
                    elif (_suffix_in(d, _HOST_SYNC_CALLS) and node.args
                            and tv.expr_is_traced(node.args[0])):
                        emit("R1", node,
                             f"{d}() on a traced value is a host "
                             "transfer/concretization inside the traced "
                             "region")
                    # R5: float64-forcing constructors
                    if _suffix_in(d, _F64_CALLS):
                        emit("R5", node,
                             f"{d}() in a traced region promotes to "
                             "float64 (or fails under x64-disabled) — "
                             "pin an explicit 32-bit dtype")
                    # R5: dtype=float / astype(float)
                    if (isinstance(node.func, ast.Attribute)
                            and node.func.attr == "astype" and node.args
                            and isinstance(node.args[0], ast.Name)
                            and node.args[0].id == "float"):
                        emit("R5", node,
                             "astype(float) means float64 — pin "
                             "jnp.float32 (or the compute dtype)")
                    dt = _call_kw(node, "dtype")
                    if isinstance(dt, ast.Name) and dt.id == "float":
                        emit("R5", node,
                             "dtype=float means float64 — pin "
                             "jnp.float32 (or the compute dtype)")
                    # R5: scan carry init inheriting dtype
                    if _suffix_in(d, {"lax.scan", "jax.lax.scan", "scan"}) \
                            and len(node.args) >= 2:
                        for sub in ast.walk(node.args[1]):
                            if (isinstance(sub, ast.Call)
                                    and _basename(_dotted(sub.func))
                                    == "zeros_like"
                                    and _call_kw(sub, "dtype") is None
                                    # dtype is also zeros_like's second
                                    # positional parameter
                                    and len(sub.args) < 2):
                                emit("R5", sub,
                                     "scan carry init via zeros_like "
                                     "inherits the input dtype — an "
                                     "accumulator carry should pin "
                                     "dtype=jnp.float32")
                # R1: implicit bool branch on a traced value
                if isinstance(node, (ast.If, ast.While)) \
                        and idx.enclosing_function(node) is fn:
                    test = node.test
                    if not _is_none_test(test) and tv.expr_is_traced(test):
                        emit("R1", test,
                             "Python branch on a traced value "
                             "concretizes it at trace time (use "
                             "lax.cond / jnp.where, or hoist the test "
                             "to host code)")
                if isinstance(node, ast.Assert) \
                        and tv.expr_is_traced(node.test) \
                        and not _is_none_test(node.test):
                    emit("R1", node,
                         "assert on a traced value concretizes it — "
                         "use checkify or debug.check, or assert on "
                         "static metadata")

    # ---- module-wide R2/R3/R4 ----------------------------------------
    # Name -> [(lineno, assigned_from_shard_map)] in source order: the R4
    # jit-of-shard_map check resolves the LATEST assignment before the
    # jit call, so rebinding a name to something else clears it (and a
    # jit call textually before the shard_map assignment never matches).
    sm_assigns: dict[str, list[tuple[int, bool]]] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            is_sm_value = (isinstance(node.value, ast.Call)
                           and _suffix_in(_dotted(node.value.func),
                                          _SHARD_MAP_CALLS))
            for t in node.targets:
                if isinstance(t, ast.Name):
                    sm_assigns.setdefault(t.id, []).append(
                        (node.lineno, is_sm_value))
    for entries in sm_assigns.values():
        entries.sort()

    def _is_shard_map_name(name: str, before_line: int) -> bool:
        latest = None
        for lineno, is_sm in sm_assigns.get(name, []):
            if lineno <= before_line:
                latest = is_sm
        return bool(latest)

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func)
        is_jit = _suffix_in(d, _JIT_CALLS)
        is_sm = _suffix_in(d, _SHARD_MAP_CALLS)

        # R2: jit/shard_map constructed inside a loop
        if (is_jit or is_sm) and idx.in_loop(node):
            emit("R2", node,
                 f"{d}() inside a loop builds a fresh traced callable "
                 "every iteration — each one retraces and recompiles; "
                 "hoist the construction out of the loop (cache it)")
        # R2: construct-and-call — jax.jit(f)(args) in one expression
        if is_jit:
            par = idx.parent.get(node)
            if isinstance(par, ast.Call) and par.func is node \
                    and idx.enclosing_function(node) is not None:
                emit("R2", node,
                     f"{d}(...)(...) constructs and calls in one "
                     "expression inside a function — a fresh cache "
                     "entry (full retrace+compile) per invocation; "
                     "cache the jitted callable")
            # R2: unhashable static args at a direct construct-and-call
            sa = _call_kw(node, "static_argnums")
            if sa is not None and isinstance(par, ast.Call) \
                    and par.func is node:
                statics = []
                if isinstance(sa, ast.Constant) \
                        and isinstance(sa.value, int):
                    statics = [sa.value]
                elif isinstance(sa, (ast.Tuple, ast.List)):
                    statics = [e.value for e in sa.elts
                               if isinstance(e, ast.Constant)
                               and isinstance(e.value, int)]
                for i in statics:
                    if i < len(par.args) and isinstance(
                            par.args[i], (ast.List, ast.Dict, ast.Set)):
                        emit("R2", par.args[i],
                             f"static arg {i} is an unhashable "
                             "list/dict/set literal — jit static args "
                             "must be hashable (use a tuple)")

        # R4: jit-of-shard_map without donation
        if is_jit and node.args:
            target = node.args[0]
            target_d = _dotted(target)
            sm_like = (isinstance(target, ast.Call)
                       and _suffix_in(_dotted(target.func),
                                      _SHARD_MAP_CALLS))
            if not sm_like and isinstance(target, ast.Name):
                sm_like = _is_shard_map_name(target.id, node.lineno)
            if sm_like and _call_kw(node, "donate_argnums") is None \
                    and _call_kw(node, "donate_argnames") is None:
                emit("R4", node,
                     f"{d}() of a shard_map program without "
                     "donate_argnums — an engine entry point that "
                     "does not donate doubles peak memory of its "
                     "state; donate (or suppress with a reason if the "
                     "inputs must survive, e.g. eval programs)")
        # R3: collective axis names
        base = _basename(d)
        if base in _COLLECTIVES and d is not None \
                and (d.startswith(("lax.", "jax.lax."))
                     or base in ("psum_scatter", "axis_size",
                                 "pbroadcast")):
            pos = _COLLECTIVES[base]
            axis_arg = (node.args[pos] if len(node.args) > pos
                        else _call_kw(node, "axis_name"))
            if axis_arg is not None:
                for name, sub in _iter_axis_names(axis_arg):
                    if name not in vocab:
                        emit("R3", sub,
                             f"collective axis name {name!r} is not in "
                             f"the mesh axis vocabulary "
                             f"{sorted(vocab)} — a typo traces as an "
                             "unbound-axis error or reduces over the "
                             "wrong group")

    # R3 subset check: collectives inside a fn whose enclosing shard_map
    # call has fully-static specs must use axes visible in those specs.
    sm_calls = [n for n in ast.walk(tree)
                if isinstance(n, ast.Call)
                and _suffix_in(_dotted(n.func), _SHARD_MAP_CALLS)]
    for call in sm_calls:
        if not call.args:
            continue
        fn_name = _basename(_dotted(call.args[0]))
        spec_axes = _shard_map_spec_axes(call, vocab)
        if spec_axes is None or not fn_name:
            continue
        resolved = {consts.get(a, a) for a in spec_axes}
        for fn in idx.defs_by_name.get(fn_name, []):
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                d2 = _dotted(node.func)
                b2 = _basename(d2)
                if b2 not in _COLLECTIVES or d2 is None \
                        or not d2.startswith(("lax.", "jax.lax.")):
                    continue
                pos = _COLLECTIVES[b2]
                axis_arg = (node.args[pos] if len(node.args) > pos
                            else _call_kw(node, "axis_name"))
                if axis_arg is None:
                    continue
                for name, sub in _iter_axis_names(axis_arg):
                    if name in vocab and name not in resolved:
                        emit("R3", sub,
                             f"axis {name!r} is not bound by the "
                             f"enclosing shard_map's specs "
                             f"({sorted(resolved)}) — the collective "
                             "would fail at trace time (or worse, "
                             "bind an outer axis)")

    # R2: jit assigned to a local and CALLED in the same function scope —
    # the callable is rebuilt (and thus fully retraced) every time the
    # enclosing function runs.  Builders that only RETURN the jitted fn
    # (or hand it to a cache / nested closure) are exempt.
    for fn in [n for n in ast.walk(tree) if isinstance(n, _FUNCS)]:
        local_jits: dict[str, ast.AST] = {}
        for node in ast.walk(fn):
            if idx.enclosing_function(node) is not fn:
                continue
            if isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call) \
                    and _suffix_in(_dotted(node.value.func), _JIT_CALLS):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        local_jits[t.id] = node.value
            # a jit-DECORATED local def is the same hazard: the def
            # statement runs (and builds a fresh callable) on every
            # invocation of the enclosing function
            if isinstance(node, _FUNCS) and node is not fn:
                for dec in node.decorator_list:
                    dd = _dotted(dec if not isinstance(dec, ast.Call)
                                 else dec.func)
                    if _suffix_in(dd, _JIT_CALLS):
                        local_jits[node.name] = node
        for node in ast.walk(fn):
            if idx.enclosing_function(node) is not fn:
                continue
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Name) \
                    and node.func.id in local_jits:
                jc = local_jits.pop(node.func.id)
                emit("R2", jc,
                     f"jit callable {node.func.id!r} is constructed AND "
                     "called inside one function — every invocation of "
                     "the enclosing function pays a fresh "
                     "retrace+compile; hoist/cache the jitted callable "
                     "(module level, __init__, or a program cache)")

    # R6: checkpoint_name labels vs the remat-name vocabulary (ISSUE 15;
    # the R3 construction applied to named-activation labels).  Only
    # string LITERALS are checked — a dynamic label is someone else's
    # contract (same silence rule as R3's dynamic axis args).
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        if not _suffix_in(_dotted(node.func), _CHECKPOINT_NAME_CALLS):
            continue
        label_arg = (node.args[1] if len(node.args) > 1
                     else _call_kw(node, "name"))
        label = _const_str(label_arg) if label_arg is not None else None
        if label is not None and label not in rvocab:
            emit("R6", label_arg,
                 f"checkpoint_name label {label!r} is not in the "
                 f"remat-name vocabulary {sorted(rvocab)} — a label "
                 "outside the vocabulary never matches a --remat_policy "
                 "save_names:/offload_names: set, silently degrading "
                 "the policy to save-nothing for that activation (add "
                 "it to models.REMAT_NAMES if it is a new name)")

    # R4: use-after-donate within one function
    for fn in [n for n in ast.walk(tree) if isinstance(n, _FUNCS)]:
        donated_fns: dict[str, list[int]] = {}
        stmts = list(ast.walk(fn))
        for node in stmts:
            if isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call):
                d = _dotted(node.value.func)
                if _suffix_in(d, _JIT_CALLS):
                    dn = _call_kw(node.value, "donate_argnums")
                    if dn is not None:
                        nums = []
                        if isinstance(dn, ast.Constant) \
                                and isinstance(dn.value, int):
                            nums = [dn.value]
                        elif isinstance(dn, (ast.Tuple, ast.List)):
                            nums = [e.value for e in dn.elts
                                    if isinstance(e, ast.Constant)
                                    and isinstance(e.value, int)]
                        for t in node.targets:
                            if isinstance(t, ast.Name) and nums:
                                donated_fns[t.id] = nums
        if not donated_fns:
            continue
        # find calls of the donated callable; donated positional Name
        # args must not be read after the call line (unless reassigned
        # by the same statement)
        for node in stmts:
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id in donated_fns):
                continue
            call_line = node.lineno
            reassigned: set[str] = set()
            par = idx.parent.get(node)
            if isinstance(par, ast.Assign):
                for t in par.targets:
                    for nn in ast.walk(t):
                        if isinstance(nn, ast.Name):
                            reassigned.add(nn.id)
            for i in donated_fns[node.func.id]:
                if i >= len(node.args):
                    continue
                arg = node.args[i]
                if not isinstance(arg, ast.Name) \
                        or arg.id in reassigned:
                    continue
                # a rebinding of the name AFTER the call makes later
                # reads refer to the new value, not the donated buffer
                # — only reads BEFORE the first such Store count.  Both
                # walks stay in fn's OWN scope: a nested def's parameter
                # or local sharing the name is a different variable.
                own = [nn for nn in ast.walk(fn)
                       if isinstance(nn, ast.Name) and nn.id == arg.id
                       and idx.enclosing_function(nn) is fn]
                rebinds = [nn.lineno for nn in own
                           if isinstance(nn.ctx, ast.Store)
                           and nn.lineno > call_line]
                horizon = min(rebinds) if rebinds else float("inf")
                for later in own:
                    if (isinstance(later.ctx, ast.Load)
                            and call_line < later.lineno <= horizon):
                        emit("R4", later,
                             f"{arg.id!r} was donated to "
                             f"{node.func.id}() (donate_argnums) on "
                             f"line {call_line} and is read again "
                             "here — its buffer may already be "
                             "overwritten; use the call's output")
                        break
    return findings
