"""RPL004 pallas-vmem-budget: static VMEM footprint + masked-tail check.

A TPU core has ~16 MiB of VMEM and a ``pl.pallas_call`` must fit its
working set there: every in/out BlockSpec block is double-buffered by
the pipeline (fetch of step i+1 overlaps compute of step i), and
scratch shapes are resident for the whole grid.  A kernel that compiles
fine at test shapes can silently blow VMEM at production shapes, and
Mosaic's failure mode is an opaque allocation error at trace time — so
this rule recomputes the footprint *statically* from the AST:

    bytes = (sum(in blocks) + sum(out blocks)) * pipeline_buffers
            + sum(scratch shapes)

Block dims are evaluated against a symbol-binding table
(``options["bindings"]``, default: the production shapes in
``lintconfig.DEFAULT_DIM_BINDINGS``); an unbound symbol is itself a
finding — the estimator refuses to guess.  Dtypes come from literal
annotations (``jnp.float32`` on scratch / out_shape), from
``<operand>.dtype`` references resolved through the call's operand
list, or from ``options["operand_dtypes"]`` overrides (e.g. int8 KV).

``PrefetchScalarGridSpec(num_scalar_prefetch=N, ...)`` is understood:
the first N invocation operands are scalar-prefetch (SMEM) and carry no
VMEM blocks, so in_specs align with operands[N:].  A BlockSpec with
``memory_space=pl.ANY`` (or HBM / SMEM) stays out of VMEM and counts
nothing: a kernel that gathers such an operand with its own DMAs
declares the destination as a ``pltpu.VMEM`` scratch, and that is
where its bytes are counted.  Only ``VMEM`` scratch shapes count;
semaphores and SMEM scratch do not live in VMEM.

The second sub-check is the **masked tail**: a grid axis that does not
divide the array needs either an in-kernel ``broadcasted_iota`` bounds
mask (followed transitively through local kernel helpers) or an
explicit divisibility ``assert x % block == 0`` in the wrapper.  A
pallas_call with neither reads garbage out of the last partial tile.

The extraction/estimation helpers are import-stable API — the VMEM
tests drive them directly against hand-computed block-shape math.
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Set, Tuple

from repro.analysis.base import Finding, Rule
from repro.analysis.walker import dotted_name, qualified, root_name, walk_scope

DTYPE_BYTES: Dict[str, int] = {
    "float64": 8, "int64": 8, "uint64": 8,
    "float32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1, "bool": 1, "bool_": 1,
    "float8_e4m3fn": 1, "float8_e5m2": 1,
}


class UnboundDim(Exception):
    """A BlockSpec dimension references a symbol with no binding."""

    def __init__(self, symbol: str):
        super().__init__(symbol)
        self.symbol = symbol


@dataclass
class PallasSite:
    """One ``pl.pallas_call`` site, decomposed for estimation."""

    line: int
    col: int
    node: ast.Call
    kernel: Optional[str] = None          # kernel function name
    in_specs: List[ast.Call] = field(default_factory=list)
    out_specs: List[ast.Call] = field(default_factory=list)
    out_shapes: List[ast.Call] = field(default_factory=list)
    scratch_shapes: List[ast.Call] = field(default_factory=list)
    num_scalar_prefetch: int = 0
    operands: List[str] = field(default_factory=list)   # invocation args
    enclosing: Optional[ast.AST] = None   # wrapper function node


def _elements(node: Optional[ast.AST]) -> List[ast.AST]:
    if node is None:
        return []
    if isinstance(node, (ast.Tuple, ast.List)):
        return list(node.elts)
    return [node]


def _kw(call: ast.Call, name: str) -> Optional[ast.AST]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _kernel_name(arg: ast.AST) -> Optional[str]:
    """Kernel function name from pallas_call's first positional arg —
    unwraps the ``functools.partial(_kernel, ...)`` idiom."""
    if isinstance(arg, ast.Call):
        fn = dotted_name(arg.func) or ""
        if fn.endswith("partial") and arg.args:
            return dotted_name(arg.args[0])
        return None
    return dotted_name(arg)


def _fill_specs(site: PallasSite, call: ast.Call) -> None:
    """Read in/out specs + scratch off either the pallas_call kwargs or
    a ``grid_spec=pltpu.PrefetchScalarGridSpec(...)`` value."""
    spec_src: ast.Call = call
    grid_spec = _kw(call, "grid_spec")
    if isinstance(grid_spec, ast.Call):
        spec_src = grid_spec
        nsp = _kw(grid_spec, "num_scalar_prefetch")
        if isinstance(nsp, ast.Constant) and isinstance(nsp.value, int):
            site.num_scalar_prefetch = nsp.value
    site.in_specs = [e for e in _elements(_kw(spec_src, "in_specs"))
                     if isinstance(e, ast.Call)]
    site.out_specs = [e for e in _elements(_kw(spec_src, "out_specs"))
                      if isinstance(e, ast.Call)]
    site.scratch_shapes = [e for e in
                           _elements(_kw(spec_src, "scratch_shapes"))
                           if isinstance(e, ast.Call)]
    site.out_shapes = [e for e in _elements(_kw(call, "out_shape"))
                       if isinstance(e, ast.Call)]


def extract_sites(tree: ast.Module,
                  imports: Optional[Dict[str, str]] = None
                  ) -> List[PallasSite]:
    """Every pallas_call in the module, with invocation operands and the
    enclosing wrapper function resolved."""
    imports = imports or {}
    sites: Dict[int, PallasSite] = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = qualified(dotted_name(node.func), imports)
        if not name.endswith("pallas_call"):
            continue
        site = PallasSite(line=node.lineno, col=node.col_offset, node=node)
        if node.args:
            site.kernel = _kernel_name(node.args[0])
        _fill_specs(site, node)
        sites[id(node)] = site
    for node in ast.walk(tree):
        # the invocation `pl.pallas_call(...)(q, k, v)` — a Call whose
        # func IS a pallas_call Call
        if isinstance(node, ast.Call) and id(node.func) in sites:
            sites[id(node.func)].operands = [
                root_name(a) or f"<arg{i}>"
                for i, a in enumerate(node.args)]
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for sub in walk_scope(fn):
            if id(sub) in sites and sites[id(sub)].enclosing is None:
                sites[id(sub)].enclosing = fn
    return sorted(sites.values(), key=lambda s: (s.line, s.col))


# ---------------------------------------------------------------------------
# dim / dtype evaluation
# ---------------------------------------------------------------------------


def eval_dim(node: ast.AST, bindings: Dict[str, int]) -> int:
    """Statically evaluate one BlockSpec dimension expression."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return node.value
    if isinstance(node, ast.Name):
        if node.id in bindings:
            return int(bindings[node.id])
        raise UnboundDim(node.id)
    if isinstance(node, ast.BinOp):
        left = eval_dim(node.left, bindings)
        right = eval_dim(node.right, bindings)
        if isinstance(node.op, ast.Mult):
            return left * right
        if isinstance(node.op, ast.FloorDiv):
            return left // right
        if isinstance(node.op, ast.Add):
            return left + right
        if isinstance(node.op, ast.Sub):
            return left - right
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        return -eval_dim(node.operand, bindings)
    raise UnboundDim(ast.dump(node)[:40])


def _shape_elems(call: ast.Call, pos: int = 0) -> List[ast.AST]:
    """The shape tuple of a BlockSpec/VMEM/ShapeDtypeStruct call."""
    val = call.args[pos] if len(call.args) > pos else _kw(call, "shape")
    return _elements(val)


def dtype_bytes(expr: Optional[ast.AST],
                operand_dtypes: Dict[str, str],
                default_dtype: str) -> int:
    """Bytes/element for a dtype expression: a ``jnp.float32``-style
    literal, an ``x.dtype`` operand reference, or the default."""
    name = None
    if expr is not None:
        d = dotted_name(expr) or ""
        tail = d.rsplit(".", 1)[-1]
        if tail in DTYPE_BYTES:
            name = tail
        elif tail == "dtype":
            base = root_name(expr)
            name = operand_dtypes.get(base or "", default_dtype)
    if name is None:
        name = default_dtype
    return DTYPE_BYTES.get(name, 4)


#: memory spaces a BlockSpec or scratch may name that are not VMEM
_NOT_VMEM = ("ANY", "HBM", "SMEM", "SEMAPHORE")


def _space(call: ast.Call) -> str:
    """Last component of a spec's ``memory_space`` (or "")."""
    space = _kw(call, "memory_space")
    return (dotted_name(space) or "").rsplit(".", 1)[-1] if space else ""


def _in_vmem_scratch(call: ast.Call) -> bool:
    """A scratch entry that occupies VMEM: ``pltpu.VMEM(shape, dtype)``
    (semaphores and ``SMEM`` scratch do not)."""
    return (dotted_name(call.func) or "").rsplit(".", 1)[-1] == "VMEM"


def _block_bytes(spec: ast.Call, bindings: Dict[str, int],
                 nbytes: int) -> int:
    if _space(spec) in _NOT_VMEM:
        return 0
    n = 1
    for dim in _shape_elems(spec):
        n *= eval_dim(dim, bindings)
    return n * nbytes


@dataclass
class VmemEstimate:
    total_bytes: int
    in_bytes: int
    out_bytes: int
    scratch_bytes: int
    pipeline_buffers: int


def estimate_site(site: PallasSite, *,
                  bindings: Dict[str, int],
                  operand_dtypes: Optional[Dict[str, str]] = None,
                  default_dtype: str = "float32",
                  pipeline_buffers: int = 2) -> VmemEstimate:
    """Static VMEM bytes for one site.  Raises :class:`UnboundDim` on a
    dimension symbol missing from ``bindings``."""
    odt = operand_dtypes or {}
    tiles = site.operands[site.num_scalar_prefetch:]
    in_b = 0
    for i, spec in enumerate(site.in_specs):
        op = tiles[i] if i < len(tiles) else ""
        nbytes = DTYPE_BYTES.get(odt.get(op, default_dtype), 4)
        in_b += _block_bytes(spec, bindings, nbytes)
    out_b = 0
    for i, spec in enumerate(site.out_specs):
        dt = None
        if i < len(site.out_shapes):
            sh = site.out_shapes[i]
            dt = (sh.args[1] if len(sh.args) > 1 else _kw(sh, "dtype"))
        out_b += _block_bytes(spec, bindings,
                              dtype_bytes(dt, odt, default_dtype))
    scr_b = 0
    for scr in filter(_in_vmem_scratch, site.scratch_shapes):
        dt = scr.args[1] if len(scr.args) > 1 else _kw(scr, "dtype")
        scr_b += _block_bytes(scr, bindings,
                              dtype_bytes(dt, odt, default_dtype))
    total = (in_b + out_b) * pipeline_buffers + scr_b
    return VmemEstimate(total_bytes=total, in_bytes=in_b, out_bytes=out_b,
                        scratch_bytes=scr_b,
                        pipeline_buffers=pipeline_buffers)


# ---------------------------------------------------------------------------
# masked-tail analysis
# ---------------------------------------------------------------------------


def _has_iota(fn: ast.AST, functions: Dict[str, ast.AST],
              seen: Set[str]) -> bool:
    """True if the kernel body (transitively through local helper
    calls) builds a ``broadcasted_iota`` position mask."""
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        name = dotted_name(node.func) or ""
        if name.rsplit(".", 1)[-1] in ("broadcasted_iota", "iota"):
            return True
        callee = name.rsplit(".", 1)[-1]
        if callee in functions and callee not in seen:
            seen.add(callee)
            if _has_iota(functions[callee], functions, seen):
                return True
    return False


def _has_divisibility_assert(fn: Optional[ast.AST]) -> bool:
    if fn is None:
        return False
    for node in walk_scope(fn):
        if isinstance(node, ast.Assert):
            for sub in ast.walk(node.test):
                if isinstance(sub, ast.BinOp) and isinstance(
                        sub.op, ast.Mod):
                    return True
    return False


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------


class PallasVmemRule(Rule):
    id = "RPL004"
    name = "pallas-vmem-budget"
    summary = ("pallas_call working set over the VMEM budget, unbound "
               "block dim, or unguarded non-divisible grid tail")

    def check(self, ctx) -> Iterator[Finding]:
        if "pallas_call" not in ctx.source:
            return
        budget = int(self.options.get("budget_bytes", 16 * 2 ** 20))
        bindings = dict(self.options.get("bindings", {}))
        for frag, extra in (self.options.get("per_file_bindings")
                            or {}).items():
            if frag in ctx.path:
                bindings.update(extra)
        odt = self.options.get("operand_dtypes", {})
        default_dtype = self.options.get("default_dtype", "float32")
        bufs = int(self.options.get("pipeline_buffers", 2))

        functions = {n.name: n for n in ast.walk(ctx.tree)
                     if isinstance(n, (ast.FunctionDef,
                                       ast.AsyncFunctionDef))}
        for site in extract_sites(ctx.tree, ctx.imports):
            try:
                est = estimate_site(site, bindings=bindings,
                                    operand_dtypes=odt,
                                    default_dtype=default_dtype,
                                    pipeline_buffers=bufs)
            except UnboundDim as exc:
                yield self.finding(
                    ctx, site.node,
                    f"cannot bound VMEM for this pallas_call: block dim "
                    f"symbol `{exc.symbol}` has no binding — add it to "
                    f"the RPL004 `bindings` option (production shape)")
            else:
                if est.total_bytes > budget:
                    yield self.finding(
                        ctx, site.node,
                        f"estimated VMEM working set "
                        f"{est.total_bytes:,} B "
                        f"(in {est.in_bytes:,} + out {est.out_bytes:,} "
                        f"x{est.pipeline_buffers} buffers + scratch "
                        f"{est.scratch_bytes:,}) exceeds the "
                        f"{budget:,} B budget — shrink the block shapes "
                        f"or split the grid")
            kernel_fn = functions.get(site.kernel or "")
            if kernel_fn is not None and not _has_iota(
                    kernel_fn, functions, {site.kernel or ""}):
                if not _has_divisibility_assert(site.enclosing):
                    yield self.finding(
                        ctx, site.node,
                        f"kernel `{site.kernel}` has no broadcasted_iota "
                        f"bounds mask and its wrapper asserts no "
                        f"divisibility — a non-divisible grid axis "
                        f"would read a garbage partial tile; add the "
                        f"iota mask or `assert dim % block == 0`")
