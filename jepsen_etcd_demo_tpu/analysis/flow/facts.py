"""Contract extraction: ``# jtflow:`` annotations + whole-program facts.

The flow rules (JTL401-405) and the contracts.json artifact both
consume one ``FlowFacts`` object extracted from a ``FlowIndex`` — the
extraction runs ONCE per lint invocation and is shared (the engine's
parse-once discipline extended to the cross-module layer).

Most facts are extracted from the code itself (packed-field tuples,
``jnp.stack`` widths, ``donate_argnums``, NamedTuple carries, mesh
constructions, collective axis names, metric-name literals). Where the
code cannot carry the contract — a bare integer literal that *means*
"the pack width", a tuple constant that *means* "pre-registered metric
set" — a small declarative annotation ties the literal to the contract
so drift becomes machine-checkable:

    # jtflow: packs wgl3.PACKED_FIELDS_XLA          (producer function)
    # jtflow: unpacks wgl3.PACKED_FIELDS_XLA        (consumer function)
    # jtflow: packed wgl3.PACKED_FIELDS_XLA         (declares a kernel's
                                                     packed result schema)
    # jtflow: packed-width=5 wgl3.PACKED_FIELDS     (this statement's
                                                     literal 5 IS the width)
    # jtflow: partials configs,live_tile_sum,real_steps
    # jtflow: partials-from wgl3._chunk_fn
    # jtflow: mesh-axes slice,batch
    # jtflow: table-word-bits=5
    # jtflow: metrics preregistered

An annotation binds to the next statement (or the statement on its own
line), exactly like a jtlint suppression. Annotations that fail to
bind, reference an unknown schema, or disagree with the code they
annotate are themselves JTL401 findings — a stale annotation is drift.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field
from typing import Optional

from ..astutil import dotted, walk_cached
from ..core import ModuleSource
from .index import FlowIndex

_ANNOT_RE = re.compile(r"#\s*jtflow:\s*(.+?)\s*$")

# Collective / sharding call suffixes whose axis argument names a mesh
# axis (positional index of the axis arg; kw axis_name also accepted).
COLLECTIVES = {"lax.psum": 1, "lax.pmax": 1, "lax.pmin": 1,
               "lax.pmean": 1, "lax.ppermute": 1, "lax.all_gather": 1,
               "lax.axis_index": 0, "lax.psum_scatter": 1}

_METRIC_METHODS = ("counter", "gauge", "histogram")


@dataclass
class Annotation:
    mod: ModuleSource
    line: int                    # the comment line
    directive: str
    arg: str
    node: Optional[ast.stmt]     # bound statement (None = failed to bind)


@dataclass
class SchemaDecl:
    ref: str                     # "wgl3.PACKED_FIELDS_XLA"
    module: str                  # relpath
    name: str
    fields: tuple[str, ...]
    line: int

    @property
    def width(self) -> int:
        return len(self.fields)


@dataclass
class KernelDecl:
    name: str                    # instrument_kernel's literal name
    module: str
    factory: str                 # enclosing function ("" = module level)
    line: int
    donates: tuple[int, ...] = ()
    packed: Optional[str] = None     # schema ref from a packed/packs annot


@dataclass
class CarryDecl:
    name: str
    module: str
    fields: tuple[str, ...]
    line: int


@dataclass
class AxisUse:
    mod: ModuleSource
    line: int
    kind: str                    # "psum", "ppermute", "partition-spec", ...
    axis: str


@dataclass
class MetricWrite:
    mod: ModuleSource
    line: int
    method: str                  # counter/gauge/histogram
    name: Optional[str]          # literal (or const-resolved) name
    family: Optional[str]        # f-string family prefix, "." / "_" trimmed


@dataclass
class FlowFacts:
    index: FlowIndex
    annotations: list[Annotation] = field(default_factory=list)
    schemas: dict[str, SchemaDecl] = field(default_factory=dict)
    kernels: list[KernelDecl] = field(default_factory=list)
    carries: dict[str, CarryDecl] = field(default_factory=dict)
    # factory symbol ("wgl3._init_carry3") -> carry class name
    carry_factories: dict[str, str] = field(default_factory=dict)
    mesh_axes: dict[str, list[str]] = field(default_factory=dict)
    axis_uses: list[AxisUse] = field(default_factory=list)
    # (mod, line, shift literal) of `1 << (K|k_slots - N)` table-width math
    word_shifts: list[tuple[ModuleSource, int, int]] = field(
        default_factory=list)
    table_word_bits: Optional[tuple[int, str, int]] = None  # (N, mod, line)
    # metric facts
    # name -> (declaring module relpath, annotation line)
    preregistered: dict[str, tuple[str, int]] = field(default_factory=dict)
    prereg_modules: set[str] = field(default_factory=set)
    labeled_families: dict[str, str] = field(default_factory=dict)
    metric_writes: list[MetricWrite] = field(default_factory=list)
    snapshot_reads: list[tuple[ModuleSource, int, str]] = field(
        default_factory=list)
    # "stem.func" -> declared partial-sum field names
    partial_layouts: dict[str, tuple[str, ...]] = field(default_factory=dict)


def flow_facts(index: FlowIndex) -> FlowFacts:
    """Extract (and memoize on the index) the whole-program facts."""
    if index._facts is None:
        index._facts = _extract(index)
    return index._facts


# -- helpers ---------------------------------------------------------------

def _stem(mod: ModuleSource) -> str:
    stem = mod.path.stem
    if stem == "__init__":
        stem = mod.path.parent.name
    return stem


def _stmt_at(mod: ModuleSource, line: int) -> Optional[ast.stmt]:
    """The outermost statement starting exactly at `line`."""
    for node in mod.walk_nodes():       # BFS: outermost first
        if isinstance(node, ast.stmt) and node.lineno == line:
            return node
    return None


def _bind_line(mod: ModuleSource, line: int) -> Optional[int]:
    """The code line an annotation at `line` governs: the same line when
    code precedes the comment, else the first following non-comment,
    non-blank line."""
    text = mod.line(line)
    before = text.split("#", 1)[0]
    if before.strip():
        return line
    n = line + 1
    while n <= len(mod.lines):
        s = mod.line(n).strip()
        if s and not s.startswith("#"):
            return n
        n += 1
    return None


def _const_str(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _str_tuple(node: ast.AST) -> Optional[tuple[str, ...]]:
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            s = _const_str(e)
            if s is None:
                return None
            out.append(s)
        return tuple(out)
    return None


def _module_consts(mod: ModuleSource) -> dict[str, ast.AST]:
    out: dict[str, ast.AST] = {}
    for node in mod.tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            out[node.targets[0].id] = node.value
    return out


def _resolve_fields(mod: ModuleSource, consts: dict[str, ast.AST],
                    node: ast.AST, depth: int = 0
                    ) -> Optional[tuple[str, ...]]:
    """A tuple-of-str constant, through one level of `BASE + (...)`."""
    if depth > 3:
        return None
    t = _str_tuple(node)
    if t is not None:
        return t
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        left = _resolve_fields(mod, consts, node.left, depth + 1)
        right = _resolve_fields(mod, consts, node.right, depth + 1)
        if left is not None and right is not None:
            return left + right
    if isinstance(node, ast.Name) and node.id in consts:
        return _resolve_fields(mod, consts, consts[node.id], depth + 1)
    return None


def enclosing_def_name(node: ast.AST) -> str:
    """The OUTERMOST enclosing function's name — contract layouts and
    kernel factories are addressed by the public factory
    (``wgl3._chunk_fn``), not the ubiquitous nested ``run``/``launch``
    defs the jit actually wraps."""
    from ..astutil import ancestors

    name = ""
    for a in ancestors(node):
        if isinstance(a, (ast.FunctionDef, ast.AsyncFunctionDef)):
            name = a.name
    return name


def _param_default_node(fn: ast.AST, name: str) -> Optional[ast.AST]:
    """The default-value NODE of parameter `name` on a FunctionDef —
    matched to the parameter itself, never to a neighboring default."""
    if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return None
    a = fn.args
    pos = a.posonlyargs + a.args
    defaults = a.defaults
    for i, arg in enumerate(reversed(pos)):
        if arg.arg == name and i < len(defaults):
            return defaults[-1 - i]
    for arg, d in zip(a.kwonlyargs, a.kw_defaults):
        if arg.arg == name and d is not None:
            return d
    return None


def _param_default(fn: ast.AST, name: str) -> Optional[str]:
    """String default of parameter `name` on a FunctionDef."""
    d = _param_default_node(fn, name)
    return _const_str(d) if d is not None else None


class _AxisResolver:
    """Resolve an axis-argument expression to a string axis name:
    a literal, a module constant, a parameter default of the enclosing
    function, or — for defaultless parameters — the single consistent
    string every intra-project call site passes (one propagation hop,
    which resolves the `_build_local_step(..., axis, ...)` idiom)."""

    def __init__(self, index: FlowIndex):
        self.index = index
        self._call_sites: Optional[dict] = None  # fname -> [(mod, Call)]

    def _sites(self, fname: str) -> list:
        """All project call sites by bare callee name — indexed ONCE
        (the per-lookup whole-project walk was the flow pass's dominant
        cost)."""
        if self._call_sites is None:
            self._call_sites = {}
            for m in self.index.modules.values():
                for call in m.walk_nodes():
                    if not isinstance(call, ast.Call):
                        continue
                    callee = dotted(call.func)
                    if callee is None:
                        continue
                    self._call_sites.setdefault(
                        callee.split(".")[-1], []).append((m, call))
        return self._call_sites.get(fname, [])

    def resolve(self, mod: ModuleSource, node: ast.AST,
                depth: int = 0) -> Optional[str]:
        s = _const_str(node)
        if s is not None:
            return s
        if depth > 2 or not isinstance(node, ast.Name):
            return None
        from ..astutil import enclosing_function

        fn = enclosing_function(node)
        seen = set()
        while fn is not None and fn not in seen:      # closures walk out
            seen.add(fn)
            d = _param_default(fn, node.id)
            if d is not None:
                return d
            if any(a.arg == node.id
                   for a in fn.args.posonlyargs + fn.args.args
                   + fn.args.kwonlyargs):
                return self._from_call_sites(mod, fn, node.id, depth)
            fn = enclosing_function(fn)
        consts = _module_consts(mod)
        if node.id in consts:
            return _const_str(consts[node.id])
        return None

    def _from_call_sites(self, mod: ModuleSource, fn, param: str,
                         depth: int) -> Optional[str]:
        values: set[str] = set()
        pos = fn.args.posonlyargs + fn.args.args
        try:
            pidx = [a.arg for a in pos].index(param)
        except ValueError:
            pidx = None
        for m, call in self._sites(fn.name):
            arg = None
            for kw in call.keywords:
                if kw.arg == param:
                    arg = kw.value
            if arg is None and pidx is not None and pidx < len(call.args):
                arg = call.args[pidx]
            if arg is not None:
                v = self.resolve(m, arg, depth + 1)
                if v is None:
                    return None     # ambiguous: stay conservative
                values.add(v)
        return values.pop() if len(values) == 1 else None


# -- extraction ------------------------------------------------------------

def contract_modules(index: FlowIndex) -> list[ModuleSource]:
    """The modules the flow pass analyzes: everything indexed except the
    analysis layer itself — the lint sources quote ``# jtflow:`` syntax
    in docstrings and rationale strings constantly, and they declare no
    kernel contracts of their own."""
    return [m for m in index.modules.values() if m.scope != "analysis"]


def _extract(index: FlowIndex) -> FlowFacts:
    facts = FlowFacts(index=index)
    mods = contract_modules(index)
    for mod in mods:
        _extract_annotations(facts, mod)
    for mod in mods:
        _extract_schemas(facts, mod)
        _extract_carries(facts, mod)
        _extract_metrics(facts, mod)
        _extract_word_shifts(facts, mod)
    axis_res = _AxisResolver(index)
    for mod in mods:
        _extract_mesh_axes(facts, mod, axis_res)
        _extract_axis_uses(facts, mod, axis_res)
        _extract_kernels(facts, mod)
    _apply_annotations(facts)
    return facts


def _extract_annotations(facts: FlowFacts, mod: ModuleSource) -> None:
    # Real comment tokens only (mod.comments): jtflow grammar quoted in
    # a docstring is prose, but a trailing comment after a multiline
    # string's closing quote is live.
    for i, ln in sorted(mod.comments.items()):
        m = _ANNOT_RE.search(ln)
        if not m:
            continue
        body = m.group(1)
        head, _, rest = body.partition(" ")
        directive, _, inline = head.partition("=")
        arg = (inline + " " + rest).strip() if inline else rest.strip()
        target = _bind_line(mod, i)
        node = _stmt_at(mod, target) if target is not None else None
        facts.annotations.append(Annotation(
            mod=mod, line=i, directive=directive, arg=arg, node=node))


def _extract_schemas(facts: FlowFacts, mod: ModuleSource) -> None:
    consts = _module_consts(mod)
    stem = _stem(mod)
    for node in mod.tree.body:
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)):
            continue
        name = node.targets[0].id
        if "PACKED_FIELDS" not in name:
            continue
        fields = _resolve_fields(mod, consts, node.value)
        if fields is not None:
            ref = f"{stem}.{name}"
            facts.schemas[ref] = SchemaDecl(
                ref=ref, module=mod.relpath, name=name, fields=fields,
                line=node.lineno)


def _extract_carries(facts: FlowFacts, mod: ModuleSource) -> None:
    stem = _stem(mod)
    for node in mod.walk_nodes():
        if not isinstance(node, ast.ClassDef):
            continue
        bases = {dotted(b) or "" for b in node.bases}
        if not any(b.endswith("NamedTuple") for b in bases):
            continue
        if not node.name.lstrip("_").lower().startswith("carry"):
            continue
        fields = tuple(
            t.target.id for t in node.body
            if isinstance(t, ast.AnnAssign) and isinstance(t.target,
                                                           ast.Name))
        if fields:
            facts.carries[node.name] = CarryDecl(
                name=node.name, module=mod.relpath, fields=fields,
                line=node.lineno)
    # Factory mapping: functions whose return constructs a known carry.
    for fn in mod.walk_nodes():
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for ret in walk_cached(fn):
            if isinstance(ret, ast.Return) and isinstance(ret.value,
                                                          ast.Call):
                callee = dotted(ret.value.func)
                if callee in facts.carries:
                    facts.carry_factories[f"{stem}.{fn.name}"] = callee
                    break


def _extract_word_shifts(facts: FlowFacts, mod: ModuleSource) -> None:
    """`1 << (K - N)` / `1 << (cfg.k_slots - N)` sites: the packed-table
    word-width math whose literal N must agree with the declared
    table-word-bits everywhere (JTL403's shard-width half)."""
    for node in mod.walk_nodes():
        if not (isinstance(node, ast.BinOp)
                and isinstance(node.op, ast.LShift)
                and isinstance(node.left, ast.Constant)
                and node.left.value == 1
                and isinstance(node.right, ast.BinOp)
                and isinstance(node.right.op, ast.Sub)
                and isinstance(node.right.right, ast.Constant)
                and isinstance(node.right.right.value, int)):
            continue
        base = dotted(node.right.left) or ""
        if base == "K" or base.endswith("k_slots"):
            facts.word_shifts.append(
                (mod, node.lineno, node.right.right.value))


def _extract_mesh_axes(facts: FlowFacts, mod: ModuleSource,
                       axis_res: _AxisResolver) -> None:
    from ..astutil import enclosing_function

    def declare(axis: Optional[str]) -> None:
        if axis:
            facts.mesh_axes.setdefault(axis, [])
            if mod.relpath not in facts.mesh_axes[axis]:
                facts.mesh_axes[axis].append(mod.relpath)

    for node in mod.walk_nodes():
        # def make_mesh(..., axes=("batch",)) — the `axes` parameter's
        # OWN default declares (not any tuple default the function
        # happens to carry).
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            d = _param_default_node(node, "axes")
            if d is not None:
                for ax in _str_tuple(d) or ():
                    declare(ax)
            continue
        if not isinstance(node, ast.Call):
            continue
        callee = mod.imports.resolve(node.func) or ""
        if callee.endswith("make_mesh"):
            for kw in node.keywords:
                if kw.arg == "axes":
                    for ax in _str_tuple(kw.value) or ():
                        declare(ax)
        elif callee.split(".")[-1] == "Mesh" and len(node.args) >= 2:
            axes_arg = node.args[1]
            if isinstance(axes_arg, (ast.Tuple, ast.List)):
                for e in axes_arg.elts:
                    declare(axis_res.resolve(mod, e))
            _ = enclosing_function  # (kept for symmetry with uses)


def _extract_axis_uses(facts: FlowFacts, mod: ModuleSource,
                       axis_res: _AxisResolver) -> None:
    for node in mod.walk_nodes():
        if not isinstance(node, ast.Call):
            continue
        callee = mod.imports.resolve(node.func) or ""
        matched = None
        for suffix, pos in COLLECTIVES.items():
            if callee == suffix or callee.endswith("." + suffix):
                matched = (suffix.split(".")[-1], pos)
                break
        if matched is not None:
            kind, pos = matched
            arg = None
            for kw in node.keywords:
                if kw.arg in ("axis_name", "axis"):
                    arg = kw.value
            if arg is None and pos < len(node.args):
                arg = node.args[pos]
            axis = axis_res.resolve(mod, arg) if arg is not None else None
            if axis is not None:
                facts.axis_uses.append(AxisUse(mod, node.lineno, kind,
                                               axis))
            continue
        if callee.endswith("PartitionSpec"):
            for e in node.args:
                axis = None
                if _const_str(e) is not None or isinstance(e, ast.Name):
                    axis = axis_res.resolve(mod, e)
                if axis is not None:
                    facts.axis_uses.append(
                        AxisUse(mod, node.lineno, "partition-spec", axis))


def _extract_kernels(facts: FlowFacts, mod: ModuleSource) -> None:
    for node in mod.walk_nodes():
        if not isinstance(node, ast.Call):
            continue
        if not mod.imports.is_call_to(node, "instrument_kernel",
                                      "obs.instrument_kernel"):
            continue
        if not node.args:
            continue
        name = _const_str(node.args[0])
        if name is None:
            continue
        donates: tuple[int, ...] = ()
        if len(node.args) > 1:
            d = facts.index.donates(mod, node.args[-1])
            if d is not None:
                donates = d[0]
        facts.kernels.append(KernelDecl(
            name=name, module=mod.relpath,
            factory=enclosing_def_name(node), line=node.lineno,
            donates=donates))


def _extract_metrics(facts: FlowFacts, mod: ModuleSource) -> None:
    consts = _module_consts(mod)
    # LABELED_FAMILIES = {...} (obs/export.py or a fixture's stand-in).
    fam = consts.get("LABELED_FAMILIES")
    if isinstance(fam, ast.Dict):
        for k, v in zip(fam.keys, fam.values):
            ks, vs = _const_str(k), _const_str(v)
            if ks is not None:
                facts.labeled_families[ks] = vs or ""
    for node in mod.walk_nodes():
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _METRIC_METHODS and node.args):
            continue
        recv = node.func.value
        if isinstance(recv, ast.Name) and recv.id in mod.imports.names:
            continue            # np.histogram(...) — not an instrument
        arg = node.args[0]
        name = _const_str(arg)
        family = None
        if name is None and isinstance(arg, ast.Name) \
                and arg.id in consts:
            name = _const_str(consts[arg.id])
        if name is None and isinstance(arg, ast.JoinedStr) and arg.values:
            lead = arg.values[0]
            prefix = _const_str(lead)
            if prefix:
                family = prefix.rstrip("._")
        if name is None and isinstance(arg, ast.Subscript) \
                and isinstance(arg.value, ast.Name) \
                and arg.value.id in consts:
            # `m.counter(NAMES[i])` over a module tuple of names writes
            # some member of it: count each member as written.
            for member in _resolve_fields(mod, consts,
                                          consts[arg.value.id]) or ():
                facts.metric_writes.append(MetricWrite(
                    mod=mod, line=node.lineno, method=node.func.attr,
                    name=member, family=None))
            continue
        if name is not None or family is not None:
            facts.metric_writes.append(MetricWrite(
                mod=mod, line=node.lineno, method=node.func.attr,
                name=name, family=family))
    # Snapshot readers live with the pre-registration declarations
    # (obs/__init__.py in the real tree) — collected in
    # _apply_annotations once prereg_modules is known.


def _extract_snapshot_reads(facts: FlowFacts, mod: ModuleSource) -> None:
    from ..astutil import walk_same_scope

    consts = _module_consts(mod)
    for fn in mod.walk_nodes():
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        has_snapshot = any(
            isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
            and n.func.attr == "snapshot" for n in walk_cached(fn))
        if not has_snapshot:
            continue
        nested = {n.name for n in walk_same_scope(fn)
                  if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
        for call in walk_cached(fn):
            if not (isinstance(call, ast.Call) and call.args):
                continue
            is_get = (isinstance(call.func, ast.Attribute)
                      and call.func.attr == "get")
            is_helper = (isinstance(call.func, ast.Name)
                         and call.func.id in nested)
            if not (is_get or is_helper):
                continue
            arg = call.args[0]
            name = _const_str(arg)
            if name is None and isinstance(arg, ast.Name) \
                    and arg.id in consts:
                name = _const_str(consts[arg.id])
            if name is not None and "." in name:
                facts.snapshot_reads.append((mod, call.lineno, name))


def _apply_annotations(facts: FlowFacts) -> None:
    """Fold annotation-declared facts into the registries (verification
    against the code happens in the flow rules, which own the finding
    format)."""
    for a in facts.annotations:
        if a.node is None:
            continue
        if a.directive == "mesh-axes":
            for ax in (s.strip() for s in a.arg.split(",")):
                if ax:
                    facts.mesh_axes.setdefault(ax, [])
                    if a.mod.relpath not in facts.mesh_axes[ax]:
                        facts.mesh_axes[ax].append(a.mod.relpath)
        elif a.directive == "table-word-bits":
            try:
                facts.table_word_bits = (int(a.arg), a.mod.relpath, a.line)
            except ValueError:
                pass        # malformed: JTL401 reports it
        elif a.directive == "metrics" and a.arg == "preregistered":
            names: tuple[str, ...] = ()
            if isinstance(a.node, ast.Assign):
                consts = _module_consts(a.mod)
                names = _resolve_fields(a.mod, consts, a.node.value) or ()
                if not names:
                    s = _const_str(a.node.value)
                    if s is not None:
                        names = (s,)
            for n in names:
                facts.preregistered.setdefault(n, (a.mod.relpath, a.line))
            facts.prereg_modules.add(a.mod.relpath)
        elif a.directive == "partials":
            names = tuple(s.strip() for s in a.arg.split(",") if s.strip())
            fname = (a.node.name
                     if isinstance(a.node, (ast.FunctionDef,
                                            ast.AsyncFunctionDef))
                     else enclosing_def_name(a.node))
            facts.partial_layouts[f"{_stem(a.mod)}.{fname}"] = names
        elif a.directive in ("packs", "packed"):
            # Attach the schema to kernels declared in the same factory.
            fname = enclosing_def_name(a.node)
            for k in facts.kernels:
                if k.module == a.mod.relpath and (
                        k.factory == fname
                        or (isinstance(a.node, ast.FunctionDef)
                            and a.node.name == k.factory)):
                    k.packed = a.arg
    # Snapshot-reader collection needs prereg_modules settled first.
    for rel in sorted(facts.prereg_modules):
        mod = facts.index.modules.get(rel)
        if mod is not None:
            _extract_snapshot_reads(facts, mod)
