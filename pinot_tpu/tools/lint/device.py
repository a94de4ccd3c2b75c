"""``device`` family: TPU-lowering obligations on the kernel builders.

The static half of the kernel preflight (tools/preflight.py is the
runtime-shape half): abstractly interpret the kernel-builder modules —
``engine/pallas_kernels.py``, ``parallel/combine.py``, ``engine/plan.py``,
``engine/startree_device.py`` — tracking symbolic shape/dtype facts per
ref, and discharge the lowering obligations a real chip would otherwise
discover at Mosaic time:

- ``blockspec`` — every ``pl.BlockSpec`` block shape's LANE (last) dim is
  provably a multiple of 128 (integer arithmetic over PALLAS_TILE, or the
  ``num_groups_padded`` div-128 fact, whose provenance is itself checked:
  every value reaching a spec's ``num_groups_padded`` must be ceil-padded
  to ``_G_CHUNK``); index-map arity matches the grid rank; the out_specs /
  out_shape tuples and the kernel body's output unpack agree.
- ``refs`` — ``value_limbs`` planes size the ref blocks: the count the
  in_specs value-block loop appends and the count the kernel body slices
  ``refs`` with must BOTH be the ``l if l else 1`` accumulation over
  ``spec.value_limbs`` (a drift means the kernel reads someone else's
  plane).
- ``smem-cap`` — SMEM scalar-prefetch slots stay bounded by the
  ``pinot.server.query.pallas.lut.max.runs`` config table: the module's
  ``DEFAULT_LUT_RUN_CAP`` must not exceed the config default, and every
  ``_lut_runs`` cap argument must flow from the threaded ``lut_run_cap``
  (or stay under the config value).
- ``kernel-dtype`` — no i64/f64 inside a Pallas kernel body (Mosaic has
  no i64 vectors; f64 is unsupported on TPU), and no i64 compute outside
  the blessed limb-reassembly functions (``assemble_outputs``, the
  sharded combine's post-kernel widening).
- ``mesh-axis`` — every ``psum``/``pmin``/``pmax``/``all_gather``/
  ``all_to_all``/``axis_index`` axis argument in the combine builders
  resolves to a declared mesh axis name (``SEG_AXIS``/``DOC_AXIS``),
  interprocedurally through helper params (``_cross_reduce``'s
  ``axes``).
- ``pow2-narrow`` — ``narrow_plan_groups`` preserves the pow2 capacity
  slot and routes the narrowed group count through ``_next_pow2``.
- ``idxcap`` — the star-tree device rung's padded index buffer is sized
  by the plan spec's capacity slot.

Like every lint family: pure stdlib ``ast``, scoped by module basename so
test fixtures (scratch copies of the real modules with one seeded
mutation) exercise each obligation. Cross-module constants (staging
``PALLAS_TILE``, config ``DEFAULT_PALLAS_LUT_MAX_RUNS``) are read from
the scanned tree when present, the installed package otherwise — never
imported.
"""

from __future__ import annotations

import ast
import os

from typing import Any, Dict, List, Optional, Set, Tuple

from pinot_tpu.tools.lint.core import (
    Finding,
    LintContext,
    Module,
    register,
)

_PKG_ROOT = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir))

_LANE = 128

# i64/f64 dtype attribute names that must not appear in kernel bodies
_WIDE_DTYPES = {"int64", "uint64", "float64"}
# top-level functions blessed to hold i64/f64 OUTSIDE kernel bodies:
# the limb-reassembly decode and the sharded combine's post-kernel
# cross-device widening (both run after pallas returns)
_BLESSED_WIDE = {"assemble_outputs", "build_sharded_pallas_kernel"}

_COLLECTIVE_AXIS_ARG = {
    "psum": 1, "pmin": 1, "pmax": 1, "all_gather": 1, "all_to_all": 1,
    "axis_index": 0, "pbroadcast": 1, "ppermute": 1, "pshuffle": 1,
}


# -- cross-module constant loading (mirrors declines._load_tables) ----------

def _module_tree(ctx: LintContext, suffix: str,
                 fallback: str) -> Optional[ast.AST]:
    for mod in ctx.modules:
        if mod.relpath.replace(os.sep, "/").endswith(suffix):
            return mod.tree
    path = os.path.normpath(os.path.join(_PKG_ROOT, fallback))
    if not os.path.exists(path):
        return None
    with open(path, encoding="utf-8") as f:
        return ast.parse(f.read(), filename=path)


def _int_consts(tree: Optional[ast.AST]) -> Dict[str, int]:
    out: Dict[str, int] = {}
    if tree is None:
        return out
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and isinstance(node.value, ast.Constant) \
                and isinstance(node.value.value, int) \
                and not isinstance(node.value.value, bool):
            out[node.targets[0].id] = node.value.value
    return out


def _staging_consts(ctx: LintContext) -> Dict[str, int]:
    consts = _int_consts(_module_tree(
        ctx, "engine/staging.py", os.path.join("engine", "staging.py")))
    consts.setdefault("PALLAS_TILE", 4096)
    consts.setdefault("LIMB_BITS", 12)
    return consts


def _config_lut_cap(ctx: LintContext) -> Optional[int]:
    tree = _module_tree(ctx, "spi/config.py",
                        os.path.join("spi", "config.py"))
    return _int_consts(tree).get("DEFAULT_PALLAS_LUT_MAX_RUNS")


# -- tiny symbolic integer evaluator ----------------------------------------

class _Div128:
    """Marker fact: value provably a multiple of 128."""


DIV128 = _Div128()


def _is_ceil_chunk(expr: ast.expr, env: Dict[str, Any]) -> bool:
    """``-(-x // C) * C`` with C a lane-multiple constant."""
    if not (isinstance(expr, ast.BinOp) and isinstance(expr.op, ast.Mult)):
        return False
    left, right = expr.left, expr.right
    if not isinstance(right, ast.Name):
        return False
    c = env.get(right.id)
    if not (isinstance(c, int) and c and c % _LANE == 0):
        return False
    return (isinstance(left, ast.UnaryOp)
            and isinstance(left.op, ast.USub)
            and isinstance(left.operand, ast.BinOp)
            and isinstance(left.operand.op, ast.FloorDiv)
            and isinstance(left.operand.right, ast.Name)
            and left.operand.right.id == right.id)


def _eval_int(expr: ast.expr, env: Dict[str, Any]) -> Optional[Any]:
    """-> int, DIV128, or None (unknown)."""
    if isinstance(expr, ast.Constant) and isinstance(expr.value, int) \
            and not isinstance(expr.value, bool):
        return expr.value
    if isinstance(expr, ast.Name):
        return env.get(expr.id)
    if isinstance(expr, ast.Attribute):
        if expr.attr == "num_groups_padded":
            return DIV128   # provenance checked by _check_gpad
        return None
    if isinstance(expr, ast.UnaryOp) and isinstance(expr.op, ast.USub):
        v = _eval_int(expr.operand, env)
        return -v if isinstance(v, int) else None
    if isinstance(expr, ast.BinOp):
        if _is_ceil_chunk(expr, env):
            return DIV128
        a = _eval_int(expr.left, env)
        b = _eval_int(expr.right, env)
        if isinstance(a, int) and isinstance(b, int):
            try:
                if isinstance(expr.op, ast.Add):
                    return a + b
                if isinstance(expr.op, ast.Sub):
                    return a - b
                if isinstance(expr.op, ast.Mult):
                    return a * b
                if isinstance(expr.op, ast.FloorDiv):
                    return a // b
                if isinstance(expr.op, ast.LShift):
                    return a << b
            except (ZeroDivisionError, ValueError):
                return None
    return None


def _lane_ok(dim: Any) -> bool:
    if dim is DIV128:
        return True
    return isinstance(dim, int) and dim > 0 and dim % _LANE == 0


# -- shared AST helpers ------------------------------------------------------

def _callee(node: ast.Call) -> Optional[str]:
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return None


def _kwarg(node: ast.Call, name: str) -> Optional[ast.expr]:
    for kw in node.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _func_env(fn: ast.AST, base: Dict[str, Any]) -> Dict[str, Any]:
    """Integer env from a function's straight-line assignments."""
    env = dict(base)
    for st in ast.walk(fn):
        if isinstance(st, ast.Assign) and len(st.targets) == 1 \
                and isinstance(st.targets[0], ast.Name):
            v = _eval_int(st.value, env)
            if v is not None:
                env[st.targets[0].id] = v
    return env


# -- blockspec / refs / grid (pallas_kernels.py builders) --------------------

def _tuple_elts(expr: ast.expr) -> Optional[List[ast.expr]]:
    """Flatten a tuple expression, following ``(a, b) + (c,)`` concats."""
    if isinstance(expr, ast.Tuple):
        return list(expr.elts)
    if isinstance(expr, ast.BinOp) and isinstance(expr.op, ast.Add):
        a = _tuple_elts(expr.left)
        b = _tuple_elts(expr.right)
        if a is not None and b is not None:
            return a + b
    return None


def _is_smem(call: ast.Call) -> bool:
    ms = _kwarg(call, "memory_space")
    return isinstance(ms, ast.Attribute) and ms.attr == "SMEM"


def _block_helpers(fn: ast.AST) -> Dict[str, Tuple[List[ast.expr],
                                                   List[ast.expr],
                                                   Optional[ast.Lambda]]]:
    """Local defs that wrap pl.BlockSpec with a shape concat around their
    single parameter: name -> (prefix elts, suffix elts, index-map
    lambda). Effective call-site shape = prefix + arg + suffix."""
    out = {}
    for st in ast.walk(fn):
        if not isinstance(st, ast.FunctionDef) or st is fn:
            continue
        if len(st.args.args) != 1:
            continue
        param = st.args.args[0].arg
        for sub in ast.walk(st):
            if isinstance(sub, ast.Return) \
                    and isinstance(sub.value, ast.Call) \
                    and _callee(sub.value) == "BlockSpec" \
                    and sub.value.args:
                shape = sub.value.args[0]
                if not (isinstance(shape, ast.BinOp)
                        and isinstance(shape.op, ast.Add)):
                    continue
                lam = (sub.value.args[1]
                       if len(sub.value.args) > 1
                       and isinstance(sub.value.args[1], ast.Lambda)
                       else None)
                if isinstance(shape.left, ast.Tuple) \
                        and isinstance(shape.right, ast.Name) \
                        and shape.right.id == param:
                    out[st.name] = (list(shape.left.elts), [], lam)
                elif isinstance(shape.right, ast.Tuple) \
                        and isinstance(shape.left, ast.Name) \
                        and shape.left.id == param:
                    out[st.name] = ([], list(shape.right.elts), lam)
    return out


def _check_builder(mod: Module, fn: ast.FunctionDef,
                   base_env: Dict[str, Any],
                   findings: List[Finding]) -> None:
    """Blockspec + refs + grid obligations inside one builder function
    that calls pl.pallas_call."""
    env = _func_env(fn, base_env)
    helpers = _block_helpers(fn)

    pallas_call = None
    for node in ast.walk(fn):
        if isinstance(node, ast.Call) and _callee(node) == "pallas_call":
            pallas_call = node
            break
    if pallas_call is None:
        return
    grid = _kwarg(pallas_call, "grid")
    grid_rank = len(grid.elts) if isinstance(grid, ast.Tuple) else None

    def note(line: int, sym: str, msg: str) -> None:
        findings.append(Finding("device", mod.relpath, line,
                                f"{fn.name}:{sym}", msg))

    def check_shape(call_line: int, elts: List[ast.expr],
                    anchor: str) -> None:
        if not elts:
            return
        dim = _eval_int(elts[-1], env)
        if not _lane_ok(dim):
            note(call_line, f"blockspec:{anchor}",
                 f"BlockSpec lane dim {ast.unparse(elts[-1])} is not "
                 f"provably a multiple of {_LANE} — Mosaic tiles the "
                 f"last dim by lanes; swap/realign the block shape")

    def check_lambda(call_line: int, lam: Optional[ast.Lambda],
                     anchor: str) -> None:
        if lam is None or grid_rank is None:
            return
        if len(lam.args.args) != grid_rank:
            note(call_line, f"blockspec:{anchor}",
                 f"index map takes {len(lam.args.args)} args but the "
                 f"grid has rank {grid_rank}")

    seen_anchor: Set[str] = set()
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        name = _callee(node)
        if name == "BlockSpec":
            if _is_smem(node) or not node.args:
                continue
            elts = _tuple_elts(node.args[0])
            if elts is None:
                continue   # helper-internal concat handled at call sites
            anchor = ast.unparse(node.args[0])[:40]
            if anchor in seen_anchor:
                continue
            seen_anchor.add(anchor)
            check_shape(node.lineno, elts, anchor)
            lam = (node.args[1] if len(node.args) > 1
                   and isinstance(node.args[1], ast.Lambda) else None)
            check_lambda(node.lineno, lam, anchor)
        elif name in helpers and node.args:
            prefix, suffix, lam = helpers[name]
            arg_elts = _tuple_elts(node.args[0])
            if arg_elts is None:
                continue
            anchor = f"{name}({ast.unparse(node.args[0])[:36]})"
            if anchor in seen_anchor:
                continue
            seen_anchor.add(anchor)
            check_shape(node.lineno, prefix + arg_elts + suffix, anchor)
            check_lambda(node.lineno, lam, anchor)

    # out_specs / out_shape / kernel output unpack arity
    out_specs = _kwarg(pallas_call, "out_specs")
    out_shape = _kwarg(pallas_call, "out_shape")
    n_specs = len(out_specs.elts) if isinstance(out_specs, ast.Tuple) \
        else None
    n_shape = len(out_shape.elts) if isinstance(out_shape, ast.Tuple) \
        else None
    if n_specs is not None and n_shape is not None and n_specs != n_shape:
        note(pallas_call.lineno, "blockspec:outs",
             f"out_specs has {n_specs} entries but out_shape {n_shape}")

    kernel_fn = None
    if pallas_call.args and isinstance(pallas_call.args[0], ast.Name):
        kname = pallas_call.args[0].id
        for sub in ast.walk(fn):
            if isinstance(sub, ast.FunctionDef) and sub.name == kname:
                kernel_fn = sub
                break
    if kernel_fn is not None and n_specs is not None:
        for st in ast.walk(kernel_fn):
            if isinstance(st, ast.Assign) and len(st.targets) == 1 \
                    and isinstance(st.targets[0], ast.Tuple) \
                    and isinstance(st.value, ast.Subscript) \
                    and isinstance(st.value.value, ast.Name) \
                    and st.value.value.id == "refs" \
                    and isinstance(st.value.slice, ast.Slice) \
                    and st.value.slice.upper is None:
                n_outs = len(st.targets[0].elts)
                if n_outs != n_specs:
                    note(st.lineno, "blockspec:outs",
                         f"kernel unpacks {n_outs} output refs but "
                         f"out_specs binds {n_specs}")

    _check_value_refs(mod, fn, kernel_fn, findings)


def _check_value_refs(mod: Module, fn: ast.FunctionDef,
                      kernel_fn: Optional[ast.FunctionDef],
                      findings: List[Finding]) -> None:
    """``refs`` obligation: the limb-plane ref count (``l if l else 1``
    over spec.value_limbs) must size BOTH the in_specs value-block loop
    and the kernel's values slice."""
    acc_name = None
    for st in ast.walk(fn):
        if isinstance(st, ast.AugAssign) and isinstance(st.op, ast.Add) \
                and isinstance(st.target, ast.Name) \
                and isinstance(st.value, ast.IfExp):
            acc_name = st.target.id
    if acc_name is None:
        return

    def note(line: int, sym: str, msg: str) -> None:
        findings.append(Finding("device", mod.relpath, line,
                                f"{fn.name}:{sym}", msg))

    # in_specs value-block loop: for _ in range(X): in_specs.append(...)
    for st in ast.walk(fn):
        if isinstance(st, ast.For) and isinstance(st.iter, ast.Call) \
                and _callee(st.iter) == "range" \
                and len(st.iter.args) == 1 \
                and isinstance(st.iter.args[0], ast.Name):
            rng = st.iter.args[0].id
            appends_spec = any(
                isinstance(s, ast.Call) and _callee(s) == "append"
                and isinstance(s.func, ast.Attribute)
                and isinstance(s.func.value, ast.Name)
                and s.func.value.id == "in_specs"
                for s in ast.walk(st))
            if appends_spec and rng != acc_name:
                note(st.lineno, "refs:in_specs",
                     f"value ref blocks appended {rng} times but the "
                     f"limb-plane count is {acc_name} — spec.value_limbs "
                     f"planes must size the ref blocks")
    # kernel values slice: refs[a : a + X]
    if kernel_fn is None:
        return
    for st in ast.walk(kernel_fn):
        if isinstance(st, ast.Subscript) \
                and isinstance(st.value, ast.Name) \
                and st.value.id == "refs" \
                and isinstance(st.slice, ast.Slice) \
                and isinstance(st.slice.upper, ast.BinOp) \
                and isinstance(st.slice.upper.op, ast.Add) \
                and isinstance(st.slice.upper.right, ast.Name):
            up = st.slice.upper.right.id
            if up != acc_name:
                note(st.lineno, "refs:slice",
                     f"kernel slices value refs with {up} but the "
                     f"limb-plane count is {acc_name}")


# -- num_groups_padded provenance (gpad) ------------------------------------

def _check_gpad(mod: Module, env: Dict[str, Any],
                findings: List[Finding]) -> None:
    """Every value reaching a spec's ``num_groups_padded`` must be
    ceil-padded to a lane-multiple chunk (the div-128 fact the blockspec
    evaluator relies on)."""
    def assigns_of(fn: ast.AST, name: str) -> List[ast.expr]:
        return [st.value for st in ast.walk(fn)
                if isinstance(st, ast.Assign) and len(st.targets) == 1
                and isinstance(st.targets[0], ast.Name)
                and st.targets[0].id == name]

    def padded(src: ast.expr, scope: Dict[str, Any]) -> bool:
        return _lane_ok(_eval_int(src, scope)) or _is_ceil_chunk(src, env)

    def pads_on_return(src: ast.expr) -> bool:
        """A call of one of this module's functions whose every return is
        lane-padded (``padded_groups``)."""
        if not (isinstance(src, ast.Call) and isinstance(src.func, ast.Name)):
            return False
        defs = [d for d in mod.tree.body if isinstance(d, ast.FunctionDef)
                and d.name == src.func.id]
        rets = [r.value for d in defs for r in ast.walk(d)
                if isinstance(r, ast.Return) and r.value is not None]
        return bool(rets) and all(padded(r, env) for r in rets)

    for fn in ast.walk(mod.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Call)
                    and _callee(node) in ("PallasSpec", "PallasPlan")):
                continue
            expr = _kwarg(node, "num_groups_padded")
            if expr is None:
                continue
            ok = False
            if isinstance(expr, ast.Attribute):
                ok = expr.attr == "num_groups_padded"
            elif _lane_ok(_eval_int(expr, env)):
                ok = True
            elif isinstance(expr, ast.Name):
                srcs = assigns_of(fn, expr.id)
                ok = bool(srcs) and all(
                    padded(s, _func_env(fn, env)) or pads_on_return(s)
                    for s in srcs)
            if not ok:
                findings.append(Finding(
                    "device", mod.relpath, node.lineno,
                    f"gpad:{ast.unparse(expr)[:40]}",
                    f"num_groups_padded={ast.unparse(expr)} is not "
                    f"provably lane-padded (ceil to _G_CHUNK); the "
                    f"two-level accumulate and out blocks assume %128"))


# -- SMEM cap vs the config table (smem-cap) --------------------------------

def _check_smem_cap(mod: Module, cfg_cap: Optional[int],
                    findings: List[Finding]) -> None:
    if cfg_cap is None:
        return
    env = _int_consts(mod.tree)
    cap = env.get("DEFAULT_LUT_RUN_CAP")
    if cap is not None and cap > cfg_cap:
        line = next((n.lineno for n in ast.walk(mod.tree)
                     if isinstance(n, ast.Assign)
                     and isinstance(n.targets[0], ast.Name)
                     and n.targets[0].id == "DEFAULT_LUT_RUN_CAP"), 0)
        findings.append(Finding(
            "device", mod.relpath, line, "smem-cap:DEFAULT_LUT_RUN_CAP",
            f"DEFAULT_LUT_RUN_CAP={cap} exceeds the config table's "
            f"DEFAULT_PALLAS_LUT_MAX_RUNS={cfg_cap} "
            f"(pinot.server.query.pallas.lut.max.runs) — SMEM "
            f"scalar-prefetch slots would outgrow the budget the "
            f"preflight verifies"))
    for node in ast.walk(mod.tree):
        if not (isinstance(node, ast.Call)
                and _callee(node) == "_lut_runs"
                and len(node.args) >= 2):
            continue
        arg = node.args[1]
        names = {n.id for n in ast.walk(arg) if isinstance(n, ast.Name)}
        if "lut_run_cap" in names:
            continue
        v = _eval_int(arg, env)
        if isinstance(v, int) and v > cfg_cap:
            findings.append(Finding(
                "device", mod.relpath, node.lineno,
                f"smem-cap:lut_runs:{v}",
                f"_lut_runs cap {v} bypasses the configured "
                f"lut.max.runs bound ({cfg_cap})"))


# -- i64/f64 bans (kernel-dtype) --------------------------------------------

def _kernel_body_names(mod: Module) -> Set[int]:
    """ids of FunctionDef nodes that are pallas kernel bodies (passed by
    name as the first arg to pallas_call, plus their nested defs)."""
    bodies: Set[int] = set()
    for fn in ast.walk(mod.tree):
        if not isinstance(fn, ast.FunctionDef):
            continue
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) \
                    and _callee(node) == "pallas_call" \
                    and node.args and isinstance(node.args[0], ast.Name):
                kname = node.args[0].id
                for sub in ast.walk(fn):
                    if isinstance(sub, ast.FunctionDef) \
                            and sub.name == kname:
                        for inner in ast.walk(sub):
                            if isinstance(inner, ast.FunctionDef):
                                bodies.add(id(inner))
                        bodies.add(id(sub))
    return bodies


def _check_dtypes(mod: Module, findings: List[Finding]) -> None:
    bodies = _kernel_body_names(mod)
    seen: Set[str] = set()

    def walk(node: ast.AST, top: Optional[str], in_kernel: bool) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if top is None:
                top = node.name
            in_kernel = in_kernel or id(node) in bodies
        for child in ast.iter_child_nodes(node):
            walk(child, top, in_kernel)
        if isinstance(node, ast.Attribute) \
                and node.attr in _WIDE_DTYPES:
            if in_kernel:
                key = f"kernel:{node.lineno}"
                if key not in seen:
                    seen.add(key)
                    findings.append(Finding(
                        "device", mod.relpath, node.lineno,
                        f"kernel-dtype:{node.attr}:{top}",
                        f"{node.attr} inside a Pallas kernel body — "
                        f"Mosaic has no 64-bit vectors; use the "
                        f"limb-plane scheme (i32 rows + carry chain)"))
            elif top not in _BLESSED_WIDE:
                key = f"out:{node.lineno}"
                if key not in seen:
                    seen.add(key)
                    findings.append(Finding(
                        "device", mod.relpath, node.lineno,
                        f"kernel-dtype:{node.attr}:{top or '<module>'}",
                        f"{node.attr} outside the blessed "
                        f"limb-reassembly functions "
                        f"({sorted(_BLESSED_WIDE)}) — widen only in the "
                        f"post-kernel decode/psum layer"))

    walk(mod.tree, None, False)


# -- mesh axis names (mesh-axis) --------------------------------------------

class _AxisChecker:
    """Interprocedural axis-name resolution for the combine builders."""

    def __init__(self, mod: Module):
        self.mod = mod
        self.axis_values: Set[str] = set()
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and node.targets[0].id.endswith("_AXIS") \
                    and isinstance(node.value, ast.Constant) \
                    and isinstance(node.value.value, str):
                self.axis_values.add(node.value.value)
        self.axis_names = {
            n.targets[0].id for n in ast.walk(mod.tree)
            if isinstance(n, ast.Assign) and len(n.targets) == 1
            and isinstance(n.targets[0], ast.Name)
            and n.targets[0].id.endswith("_AXIS")}
        # function name -> (def node, enclosing scope chain)
        self.funcs: Dict[str, Tuple[ast.FunctionDef, Tuple]] = {}
        self._index(mod.tree, ())

    def _index(self, node: ast.AST, chain: Tuple) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.FunctionDef):
                self.funcs.setdefault(child.name, (child, chain))
                self._index(child, chain + (child,))
            else:
                self._index(child, chain)

    def _scope_assigns(self, fn: ast.FunctionDef,
                       chain: Tuple) -> Dict[str, ast.expr]:
        """Name -> value expr across the scope chain (outer first), NOT
        descending into nested defs — their assigns are their own."""
        env: Dict[str, ast.expr] = {}

        def local(scope: ast.AST) -> None:
            stack: List[ast.AST] = list(ast.iter_child_nodes(scope))
            while stack:
                st = stack.pop()
                if isinstance(st, (ast.FunctionDef,
                                   ast.AsyncFunctionDef, ast.Lambda)):
                    continue
                if isinstance(st, ast.Assign) and len(st.targets) == 1 \
                        and isinstance(st.targets[0], ast.Name):
                    env[st.targets[0].id] = st.value
                if isinstance(st, (ast.For, ast.AsyncFor)) \
                        and isinstance(st.target, ast.Name):
                    env[st.target.id] = ("elem", st.iter)
                stack.extend(ast.iter_child_nodes(st))

        for scope in chain + (fn,):
            local(scope)
        return env

    def resolve(self, expr: Any, env: Dict[str, ast.expr],
                params: Set[str], visited: Set[str], depth: int):
        """-> ("ok",) | ("bad", detail) | ("params", set) | ("unknown",)"""
        if isinstance(expr, tuple) and expr and expr[0] == "elem":
            return self.resolve(expr[1], env, params, visited, depth)
        if isinstance(expr, ast.Constant):
            if isinstance(expr.value, str):
                if expr.value in self.axis_values:
                    return ("ok",)
                return ("bad", f"axis {expr.value!r} is not a declared "
                               f"mesh axis {sorted(self.axis_values)}")
            return ("unknown",)
        if isinstance(expr, ast.Name):
            if expr.id in self.axis_names:
                return ("ok",)
            if expr.id in visited:
                return (("params", {expr.id}) if expr.id in params
                        else ("unknown",))
            if expr.id in env:
                return self.resolve(env[expr.id], env, params,
                                    visited | {expr.id}, depth)
            if expr.id in params:
                return ("params", {expr.id})
            return ("unknown",)
        if isinstance(expr, ast.Tuple):
            out_params: Set[str] = set()
            unknown = False
            for e in expr.elts:
                r = self.resolve(e, env, params, visited, depth)
                if r[0] == "bad":
                    return r
                if r[0] == "params":
                    out_params |= r[1]
                elif r[0] == "unknown":
                    unknown = True
            if out_params:
                return ("params", out_params)
            return ("unknown",) if unknown else ("ok",)
        if isinstance(expr, ast.Call) and _callee(expr) == "tuple" \
                and expr.args:
            return self.resolve(expr.args[0], env, params, visited, depth)
        if isinstance(expr, ast.GeneratorExp):
            return self.resolve(expr.generators[0].iter, env, params,
                                visited, depth)
        return ("unknown",)

    def check(self) -> List[Finding]:
        findings: List[Finding] = []
        if not self.axis_values:
            return findings
        # pass 1: direct resolutions + param obligations per function
        obligations: Dict[str, Set[str]] = {}
        for fname, (fn, chain) in self.funcs.items():
            env = self._scope_assigns(fn, chain)
            params = ({a.arg for a in fn.args.args}
                      | {a.arg for a in fn.args.kwonlyargs})
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                cal = _callee(node)
                if cal not in _COLLECTIVE_AXIS_ARG:
                    continue
                idx = _COLLECTIVE_AXIS_ARG[cal]
                arg = (node.args[idx] if len(node.args) > idx
                       else _kwarg(node, "axis_name"))
                if arg is None:
                    continue
                r = self.resolve(arg, env, params, set(), 0)
                if r[0] == "bad":
                    findings.append(Finding(
                        "device", self.mod.relpath, node.lineno,
                        f"mesh-axis:{cal}:{ast.unparse(arg)[:30]}",
                        f"{cal} axis {ast.unparse(arg)}: {r[1]}"))
                elif r[0] == "params":
                    obligations.setdefault(fname, set()).update(r[1])
        # pass 2: param obligations discharge at call sites
        for fname, pnames in obligations.items():
            fn, _chain = self.funcs[fname]
            pos = {a.arg: i for i, a in enumerate(fn.args.args)}
            for caller_name, (caller, cchain) in self.funcs.items():
                env = self._scope_assigns(caller, cchain)
                cparams = {a.arg for a in caller.args.args}
                for node in ast.walk(caller):
                    if not (isinstance(node, ast.Call)
                            and isinstance(node.func, ast.Name)
                            and node.func.id == fname):
                        continue
                    for pname in pnames:
                        i = pos.get(pname)
                        arg = (node.args[i] if i is not None
                               and len(node.args) > i
                               else _kwarg(node, pname))
                        if arg is None:
                            continue
                        r = self.resolve(arg, env, cparams, set(), 1)
                        if r[0] == "bad":
                            findings.append(Finding(
                                "device", self.mod.relpath, node.lineno,
                                f"mesh-axis:{fname}:{pname}",
                                f"{fname}({pname}="
                                f"{ast.unparse(arg)[:30]}): {r[1]}"))
                        # params-of-params: one more hop is enough for
                        # the combine builders; deeper stays silent
        return findings


# -- narrow_plan_groups pow2 preservation (pow2-narrow) ---------------------

def _check_narrow(mod: Module, findings: List[Finding]) -> None:
    fn = next((n for n in ast.walk(mod.tree)
               if isinstance(n, ast.FunctionDef)
               and n.name == "narrow_plan_groups"), None)
    if fn is None:
        return
    # names unpacked from plan.spec (the capacity slot must come back)
    spec_names: Set[str] = set()
    assigns: Dict[str, ast.expr] = {}
    for st in ast.walk(fn):
        if isinstance(st, ast.Assign) and len(st.targets) == 1:
            t = st.targets[0]
            if isinstance(t, ast.Tuple) \
                    and isinstance(st.value, ast.Attribute) \
                    and st.value.attr == "spec":
                spec_names |= {e.id for e in t.elts
                               if isinstance(e, ast.Name)}
            elif isinstance(t, ast.Name):
                assigns[t.id] = st.value
    for st in ast.walk(fn):
        if not (isinstance(st, ast.Assign) and len(st.targets) == 1
                and isinstance(st.targets[0], ast.Name)
                and st.targets[0].id == "spec"
                and isinstance(st.value, ast.Tuple)
                and len(st.value.elts) == 5):
            continue
        ng, cap = st.value.elts[3], st.value.elts[4]
        ng_src = assigns.get(ng.id) if isinstance(ng, ast.Name) else None
        if not (isinstance(ng_src, ast.Call)
                and _callee(ng_src) == "_next_pow2"):
            findings.append(Finding(
                "device", mod.relpath, st.lineno, "pow2-narrow:num_groups",
                "narrowed num_groups does not flow through _next_pow2 — "
                "the dense rung assumes pow2 padding survives "
                "narrowing"))
        if not (isinstance(cap, ast.Name) and cap.id in spec_names):
            findings.append(Finding(
                "device", mod.relpath, st.lineno, "pow2-narrow:capacity",
                "narrowed spec does not preserve the original capacity "
                "slot — block/tile sizing would drift from the staged "
                "arrays"))


# -- star-tree idx pad sized by the spec capacity (idxcap) ------------------

def _check_idxcap(mod: Module, findings: List[Finding]) -> None:
    for fn in ast.walk(mod.tree):
        if not isinstance(fn, ast.FunctionDef):
            continue
        assigns: Dict[str, ast.expr] = {}
        for st in ast.walk(fn):
            if isinstance(st, ast.Assign) and len(st.targets) == 1 \
                    and isinstance(st.targets[0], ast.Name):
                assigns[st.targets[0].id] = st.value
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Call)
                    and _callee(node) == "zeros" and node.args):
                continue
            dt = _kwarg(node, "dtype")
            if not (isinstance(dt, ast.Attribute) and dt.attr == "int32"):
                continue
            size = node.args[0]
            src = assigns.get(size.id) if isinstance(size, ast.Name) \
                else size
            ok = (isinstance(src, ast.Subscript)
                  and isinstance(src.value, ast.Attribute)
                  and src.value.attr == "spec")
            if not ok:
                # symbol keyed on the size expr, not the enclosing def:
                # nested launch closures are walked by both scopes
                findings.append(Finding(
                    "device", mod.relpath, node.lineno,
                    f"idxcap:{ast.unparse(size)[:30]}",
                    "padded index buffer is not sized by the plan "
                    "spec's capacity slot — the kernel's block shapes "
                    "are derived from spec[-1], a drifting pad would "
                    "gather out of bounds"))


# -- family entry ------------------------------------------------------------

@register("device")
def check_device(ctx: LintContext) -> List[Finding]:
    findings: List[Finding] = []
    staging = None
    cfg_cap = None
    for mod in ctx.modules:
        base = os.path.basename(mod.relpath)
        if base == "pallas_kernels.py":
            if staging is None:
                staging = _staging_consts(ctx)
                cfg_cap = _config_lut_cap(ctx)
            env = dict(staging)
            env.update(_int_consts(mod.tree))
            for fn in ast.walk(mod.tree):
                if isinstance(fn, ast.FunctionDef):
                    _check_builder(mod, fn, env, findings)
            _check_gpad(mod, env, findings)
            _check_smem_cap(mod, cfg_cap, findings)
            _check_dtypes(mod, findings)
        elif base == "combine.py":
            _check_dtypes(mod, findings)
            findings.extend(_AxisChecker(mod).check())
        elif base == "reduce_device.py":
            # broker-reduce merge kernels: mesh-axis resolution through
            # the reduce helper params (_axis_reduce's / _slice_reduce's
            # ``axis``). NO _check_dtypes — i64 keys/sums are this
            # module's contract
            findings.extend(_AxisChecker(mod).check())
        elif base == "plan.py":
            _check_narrow(mod, findings)
        elif base == "startree_device.py":
            _check_idxcap(mod, findings)
    # one finding per stable key (helpers shared by several call sites
    # would otherwise multiply one root cause)
    seen: Set[str] = set()
    out: List[Finding] = []
    for f in findings:
        if f.key not in seen:
            seen.add(f.key)
            out.append(f)
    return out
