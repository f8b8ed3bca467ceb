"""CAS008 — sharding-spec consistency between core/ and sharding/.

The batched engine's mesh placement contract lives in
``sharding/specs.py`` (lane-major dims shard via ``lane_spec``/
``put_lanes``, shared cascade state replicates via ``put_replicated``,
and the ``jit_*`` factories carry the ``donate_argnums`` annotations),
while the arrays it governs live in ``core/batched.py``.  The per-file
rules cannot see across that boundary; this rule checks three
cross-module invariants:

1. **spec-surface integrity** — every name a ``core/`` module imports
   from ``repro.sharding`` must exist in ``sharding/specs.py`` and be
   exported through ``sharding/__init__.__all__``.  A renamed or
   un-exported helper otherwise only fails at engine import time (or
   silently resolves to a stale re-export).
2. **explicit placement** — engine state reaches devices only through
   the spec helpers: a bare single-argument ``jax.device_put(x)`` in
   ``core/`` picks the default device with no lane/replication rule and
   desyncs from the mesh'd path; use ``put_lanes``/``put_replicated``
   (or pass an explicit sharding).
3. **donation deadness across function boundaries** — for every
   ``self.<attr> = jit_*factory*(...)`` whose factory body (in
   ``sharding/specs.py``) jits with ``donate_argnums``, any
   ``self``-rooted buffer passed at a donated position of a
   ``self.<attr>(...)`` call site must be reassigned later in the same
   function, and must not be read between the call and that
   reassignment.  CAS003 checks donated *locals* against a literal
   ``donate_argnums`` in the same file; here the donation annotation
   lives in another module, so the per-file rule is blind to it — this
   is exactly how a stale ``self._cache_x`` read after the scatter
   donated it would slip through.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterator, Optional, Set

from repro.analysis.engine import Finding, ModuleContext, RepoContext, Rule
from repro.analysis.rules.common import (
    call_name, import_table, string_value)

CORE_MARKER = "/core/"
SPECS_PATH = "src/repro/sharding/specs.py"
INIT_PATH = "src/repro/sharding/__init__.py"
PKG = "repro.sharding"


def _public_defs(tree: ast.Module) -> Set[str]:
    return {n.name for n in tree.body
            if isinstance(n, (ast.FunctionDef, ast.ClassDef))
            and not n.name.startswith("_")}


def _module_constants(tree: ast.Module) -> Set[str]:
    out: Set[str] = set()
    for node in tree.body:
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    out.add(t.id)
        elif isinstance(node, ast.AnnAssign) and isinstance(node.target,
                                                            ast.Name):
            out.add(node.target.id)
    return out


def _all_exports(tree: ast.Module) -> Optional[Set[str]]:
    for node in tree.body:
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "__all__":
                    vals = {string_value(e)
                            for e in getattr(node.value, "elts", [])}
                    return {v for v in vals if v}
    return None


def _donating_factories(tree: ast.Module) -> Dict[str, Set[int]]:
    """Factory defs in specs.py whose bodies jit with donate_argnums.

    ``jit_route_pass`` -> {2}, ``jit_cache_scatter`` -> {0, 1},
    ``jit_tick_update`` -> {0, 1, 2}.  The
    donation may be conditional (mesh-gated); callers must satisfy
    deadness unconditionally, so positions are collected from every
    branch.
    """
    out: Dict[str, Set[int]] = {}
    for node in tree.body:
        if not isinstance(node, ast.FunctionDef):
            continue
        donated: Set[int] = set()
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            for kw in sub.keywords:
                if kw.arg != "donate_argnums":
                    continue
                for e in ast.walk(kw.value):
                    if isinstance(e, ast.Constant) and \
                            isinstance(e.value, int):
                        donated.add(e.value)
        if donated:
            out[node.name] = donated
    return out


def _self_attr_chain(node: ast.AST) -> Optional[str]:
    """``X`` when ``node`` is rooted at ``self.X`` (through subscripts /
    a wrapping ``tuple()``/``list()`` copy — the copy shares buffers, so
    donation still kills the original)."""
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
            and node.func.id in ("tuple", "list") and node.args:
        node = node.args[0]
    while isinstance(node, (ast.Subscript,)):
        node = node.value
    if (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"):
        return node.attr
    return None


class ShardingContractRule(Rule):
    """core/ and sharding/specs.py agree on surface, placement, donation."""

    id = "CAS008"
    title = "sharding-spec consistency (surface, placement, donation)"

    def check_repo(self, repo: RepoContext) -> Iterator[Finding]:
        """Resolve the spec surface once, then sweep core/ modules."""
        specs = repo.module(SPECS_PATH)
        init = repo.module(INIT_PATH)
        if specs is None and init is None:
            # look outside the scanned set (narrowed runs still check)
            for rel in (SPECS_PATH, INIT_PATH):
                path = repo.root / rel
                if path.is_file():
                    from repro.analysis.engine import load_module
                    ctx, _ = load_module(repo.root, path)
                    if ctx is not None:
                        if rel == SPECS_PATH:
                            specs = ctx
                        else:
                            init = ctx
        if specs is None:
            return          # no sharding package in this tree (fixtures)
        surface = _public_defs(specs.tree) | _module_constants(specs.tree)
        exports = _all_exports(init.tree) if init is not None else None
        factories = _donating_factories(specs.tree)
        for mod in repo.modules:
            if CORE_MARKER not in f"/{mod.rel}":
                continue
            yield from self._check_imports(mod, surface, exports)
            yield from self._check_bare_device_put(mod)
            yield from self._check_donation_deadness(mod, factories)

    # -- 1. spec-surface integrity ----------------------------------------
    def _check_imports(self, mod: ModuleContext, surface: Set[str],
                       exports: Optional[Set[str]]) -> Iterator[Finding]:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.ImportFrom) or node.level:
                continue
            if node.module != PKG and not (
                    node.module or "").startswith(PKG + "."):
                continue
            for alias in node.names:
                name = alias.name
                if node.module == PKG and exports is not None \
                        and name not in exports:
                    yield Finding(
                        self.id, mod.rel, node.lineno, node.col_offset,
                        f"'{name}' is imported from {PKG} but not "
                        "exported in sharding/__init__.__all__ — add it "
                        "to the package surface or import from "
                        f"{PKG}.specs directly")
                if name not in surface:
                    yield Finding(
                        self.id, mod.rel, node.lineno, node.col_offset,
                        f"'{name}' is imported from {node.module} but "
                        "sharding/specs.py defines no such helper — the "
                        "engine/spec surface drifted")

    # -- 2. explicit placement --------------------------------------------
    def _check_bare_device_put(self, mod: ModuleContext
                               ) -> Iterator[Finding]:
        imports = import_table(mod.tree)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            qn = call_name(node, imports)
            if qn != "jax.device_put":
                continue
            if len(node.args) >= 2 or any(
                    kw.arg in ("device", "sharding") or kw.arg is None
                    for kw in node.keywords):
                continue
            yield Finding(
                self.id, mod.rel, node.lineno, node.col_offset,
                "bare jax.device_put(x) in core/ places engine state "
                "with no lane/replication rule — use put_lanes / "
                "put_replicated (sharding/specs.py) or pass an explicit "
                "sharding")

    # -- 3. donation deadness across function boundaries ------------------
    def _check_donation_deadness(self, mod: ModuleContext,
                                 factories: Dict[str, Set[int]]
                                 ) -> Iterator[Finding]:
        if not factories:
            return
        # which self attrs hold a donating jitted callable (assignments
        # may sit inside list comprehensions — the pipelined per-level
        # route passes)
        donating_attrs: Dict[str, Set[int]] = {}
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Assign):
                continue
            attr = None
            for t in node.targets:
                if (isinstance(t, ast.Attribute)
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self"):
                    attr = t.attr
            if attr is None:
                continue
            for sub in ast.walk(node.value):
                if isinstance(sub, ast.Call):
                    fname = sub.func.attr if isinstance(
                        sub.func, ast.Attribute) else getattr(
                        sub.func, "id", "")
                    if fname in factories:
                        donating_attrs[attr] = factories[fname]
        if not donating_attrs:
            return
        for fn in ast.walk(mod.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            yield from self._check_call_sites(mod, fn, donating_attrs)

    def _check_call_sites(self, mod: ModuleContext, fn,
                          donating_attrs: Dict[str, Set[int]]
                          ) -> Iterator[Finding]:
        body = list(ast.walk(fn))
        for node in body:
            if not isinstance(node, ast.Call):
                continue
            target = node.func
            while isinstance(target, ast.Subscript):
                target = target.value
            attr = _self_attr_chain(target)
            if attr not in donating_attrs:
                continue
            for pos in donating_attrs[attr]:
                if pos >= len(node.args):
                    continue
                donated = _self_attr_chain(node.args[pos])
                if donated is None:
                    continue        # transient value: dies on its own
                rebind = self._rebind_line(fn, node.lineno, donated)
                if rebind is None:
                    yield Finding(
                        self.id, mod.rel, node.args[pos].lineno,
                        node.args[pos].col_offset,
                        f"self.{donated} is passed at donated position "
                        f"{pos} of self.{attr}(...) (donate_argnums in "
                        "sharding/specs.py) but never reassigned in this "
                        "function — the attribute keeps pointing at a "
                        "dead buffer; rebind it from the call's outputs")
                    continue
                end = getattr(node, "end_lineno", node.lineno)
                for read in body:
                    if (isinstance(read, ast.Attribute)
                            and isinstance(read.ctx, ast.Load)
                            and _self_attr_chain(read) == donated
                            and end < read.lineno <= rebind):
                        yield Finding(
                            self.id, mod.rel, read.lineno, read.col_offset,
                            f"self.{donated} is read after it was passed "
                            f"at donated position {pos} of "
                            f"self.{attr}(...) (line {node.lineno}) and "
                            "before it is rebound — it is a dead buffer "
                            "there; read the call's outputs instead")

    @staticmethod
    def _rebind_line(fn, lineno: int, attr: str) -> Optional[int]:
        """First line after ``lineno`` assigning ``self.<attr>`` (alone
        or in a tuple target), or None."""
        lines = []
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and node.lineno > lineno:
                for t in node.targets:
                    elts = t.elts if isinstance(t, (ast.Tuple, ast.List)) \
                        else [t]
                    if any(isinstance(e, ast.Attribute)
                           and isinstance(e.value, ast.Name)
                           and e.value.id == "self" and e.attr == attr
                           for e in elts):
                        lines.append(node.lineno)
        return min(lines, default=None)
