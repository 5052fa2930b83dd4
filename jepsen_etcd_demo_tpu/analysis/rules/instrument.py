"""JTL105 uninstrumented-kernel: every jit cache wears obs.instrument_kernel.

The PR 1 invariant — every jit-compiled kernel the harness caches is
wrapped in ``obs.instrument_kernel`` so compile-vs-execute attribution
is never a blind spot. Until ISSUE 7 it was enforced by convention only,
and PR 3's lattice kernels (parallel/lattice.py) shipped uninstrumented
— exactly the drift this rule exists to stop.

Accepted shapes:

  * ``instrument_kernel("name", jax.jit(...))`` anywhere in the
    statement — wrapped at the jit site;
  * ``return jax.jit(...)`` from a PLAIN factory function — the repo's
    ``_chunk_fn`` idiom, where the CALLER wraps at its cache store
    (that store is itself checked: a bare ``_CACHE[...] = jax.jit(...)``
    flags). A factory decorated with ``functools.lru_cache`` gets no
    such exemption: the lru_cache IS the kernel cache, there is no
    later wrap point.
"""

from __future__ import annotations

import ast
from typing import Iterator

from ..astutil import CACHE_NAME_RE, ancestors, decorator_names, \
    enclosing_function, walk_same_scope
from ..core import KERNEL_SCOPES, ModuleSource, Rule, register
from ..findings import Finding

_LRU_DECOS = ("functools.lru_cache", "functools.cache", "lru_cache",
              "cache")


@register
class UninstrumentedKernelRule(Rule):
    id = "JTL105"
    name = "uninstrumented-kernel"
    scopes = KERNEL_SCOPES
    rationale = (
        "PR 1 invariant: every cached jit kernel is wrapped in "
        "obs.instrument_kernel for compile/execute attribution; "
        "parallel/lattice.py (PR 3) shipped without it — a telemetry "
        "blind spot this rule would have caught.")
    hint = ("wrap the jitted callable: obs.instrument_kernel(\"<kernel-"
            "name>\", jax.jit(...)) — same signature, near-zero cost "
            "outside a capture")

    def check(self, mod: ModuleSource) -> Iterator[Finding]:
        for node in mod.walk_nodes():
            if not (isinstance(node, ast.Call)
                    and mod.imports.is_call_to(node, "jax.jit")):
                continue
            if self._wrapped(node, mod):
                continue
            fn = enclosing_function(node)
            in_return = any(isinstance(a, ast.Return)
                            for a in ancestors(node))
            if in_return and fn is not None:
                decos = decorator_names(fn, mod.imports)
                if not any(d == want or d.endswith("." + want)
                           for d in decos for want in _LRU_DECOS):
                    continue   # plain factory: caller's store is checked
                yield mod.finding(
                    self, node,
                    f"jit kernel cached by functools.lru_cache on "
                    f"{fn.name}() but not wrapped in "
                    f"obs.instrument_kernel — the lru_cache IS the "
                    f"kernel cache, there is no later wrap point")
                continue
            yield mod.finding(
                self, node,
                "jit-compiled kernel not wrapped in "
                "obs.instrument_kernel — compile/execute attribution "
                "blind spot (the PR 1 invariant)")
        yield from self._factory_stores(mod)

    def _factory_stores(self, mod: ModuleSource) -> Iterator[Finding]:
        """The caller half of the plain-factory exemption: a cache
        store of a LOCAL factory's result (`_CACHE[k] = make_fn(...)`)
        flags when the factory's returns contain a bare jax.jit — the
        exact pre-fix parallel/lattice.py shape (factory + separate
        cached_* store, neither wrapping)."""
        # Resolve factories by name only when the name is UNIQUE in the
        # module: with duplicates (nested `measure`/`build` defs recur)
        # a bare name could consult the wrong def — stay conservative.
        all_fns = [n for n in mod.walk_nodes()
                   if isinstance(n, (ast.FunctionDef,
                                     ast.AsyncFunctionDef))]
        counts: dict[str, int] = {}
        for n in all_fns:
            counts[n.name] = counts.get(n.name, 0) + 1
        fns = {n.name: n for n in all_fns if counts[n.name] == 1}
        for node in mod.walk_nodes():
            if not isinstance(node, ast.Assign):
                continue
            for tgt in node.targets:
                if not (isinstance(tgt, ast.Subscript)
                        and isinstance(tgt.value, ast.Name)
                        and CACHE_NAME_RE.search(tgt.value.id)):
                    continue
                val = node.value
                if self._contains_instrument(val, mod):
                    continue
                if isinstance(val, ast.Call) \
                        and isinstance(val.func, ast.Name) \
                        and val.func.id in fns \
                        and self._returns_bare_jit(fns[val.func.id], mod):
                    yield mod.finding(
                        self, node,
                        f"cache store of {val.func.id}()'s result: the "
                        f"factory returns a bare jax.jit and nothing "
                        f"wraps it in obs.instrument_kernel — the "
                        f"pre-fix parallel/lattice.py blind spot")

    def _returns_bare_jit(self, fn, mod: ModuleSource) -> bool:
        for node in walk_same_scope(fn):
            if isinstance(node, ast.Return) and node.value is not None:
                for c in ast.walk(node.value):
                    if isinstance(c, ast.Call) \
                            and mod.imports.is_call_to(c, "jax.jit") \
                            and not self._wrapped(c, mod):
                        return True
        return False

    def _contains_instrument(self, expr: ast.AST,
                             mod: ModuleSource) -> bool:
        return any(isinstance(c, ast.Call) and mod.imports.is_call_to(
            c, "instrument_kernel", "obs.instrument_kernel")
            for c in ast.walk(expr))

    def _wrapped(self, jit_call: ast.Call, mod: ModuleSource) -> bool:
        for a in ancestors(jit_call):
            if isinstance(a, ast.Call) and mod.imports.is_call_to(
                    a, "instrument_kernel", "obs.instrument_kernel"):
                return True
            if isinstance(a, ast.stmt):
                break
        return False
