"""JAX-aware AST helpers shared by the repro-lint passes.

The central approximation is *jit-reachability*: a per-module fixpoint over
which function definitions can end up inside a jax trace.  Entry points are
functions decorated with (or passed to) any of the tracing transforms in
``JIT_WRAPPERS``; the closure adds nested ``def``s and same-module callees
reached by bare-name or ``self.``-method calls.  This is deliberately
module-local — cross-module call graphs buy little here because every
tracing boundary in this repo is declared next to the traced function —
and errs toward over-approximation, which is the right direction for a
lint that feeds a baseline file.
"""
from __future__ import annotations

import ast
import dataclasses

# Dotted names whose callees/decorated functions run under a jax trace.
JIT_WRAPPERS = {
    "jax.jit", "jax.pmap", "jax.vmap", "jax.grad", "jax.value_and_grad",
    "jax.jacfwd", "jax.jacrev", "jax.hessian", "jax.linearize",
    "jax.checkpoint", "jax.remat", "jax.custom_vjp", "jax.custom_jvp",
    "jax.lax.scan", "jax.lax.cond", "jax.lax.switch", "jax.lax.while_loop",
    "jax.lax.fori_loop", "jax.lax.map", "jax.lax.associative_scan",
    "jax.experimental.pallas.pallas_call",
    "jax.shard_map", "jax.experimental.shard_map.shard_map",
}

# The subset that memoizes compiled programs keyed on operand structure —
# calling these inside a Python loop is the classic retrace smell.
PROGRAM_BUILDERS = {
    "jax.jit", "jax.pmap",
    "jax.experimental.pallas.pallas_call",
    "jax.shard_map", "jax.experimental.shard_map.shard_map",
}

PRNG_SOURCES = {
    "jax.random.PRNGKey", "jax.random.key", "jax.random.split",
    "jax.random.fold_in", "jax.random.clone", "jax.random.wrap_key_data",
}


def dotted_name(node: ast.AST) -> str | None:
    """``a.b.c`` for Name/Attribute chains, else None."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = dotted_name(node.value)
        return f"{base}.{node.attr}" if base else None
    return None


def alias_map(tree: ast.Module) -> dict[str, str]:
    """Local name -> canonical dotted path, from every import statement."""
    out: dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                out[a.asname or a.name.split(".")[0]] = (
                    a.name if a.asname else a.name.split(".")[0])
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            prefix = "." * node.level + mod
            for a in node.names:
                if a.name == "*":
                    continue
                out[a.asname or a.name] = f"{prefix}.{a.name}"
    return out


def resolve(node: ast.AST, aliases: dict[str, str]) -> str | None:
    """Canonical dotted path of a Name/Attribute, through import aliases."""
    name = dotted_name(node)
    if name is None:
        return None
    head, _, rest = name.partition(".")
    if head in aliases:
        name = aliases[head] + ("." + rest if rest else "")
    return name


def resolves_to(node: ast.AST, aliases: dict[str, str],
                targets: set[str]) -> str | None:
    r = resolve(node, aliases)
    if r is None:
        return None
    if r in targets:
        return r
    # Unaliased tail paths (`shard_map(...)` imported without going through
    # an import statement we saw, e.g. re-exported names): suffix match.
    for t in targets:
        if t.endswith("." + r):
            return t
    return None


@dataclasses.dataclass
class FuncInfo:
    node: ast.FunctionDef | ast.AsyncFunctionDef | ast.Lambda
    qualname: str
    pos_params: list[str]       # positional (incl. pos-only) arg names
    kwonly: set[str]
    in_class: str | None        # enclosing class name, if a method


def collect_functions(tree: ast.Module) -> list[FuncInfo]:
    out: list[FuncInfo] = []

    def visit(node: ast.AST, stack: list[str], cls: str | None):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qn = ".".join(stack + [child.name])
                a = child.args
                pos = [p.arg for p in a.posonlyargs + a.args]
                out.append(FuncInfo(child, qn, pos,
                                    {p.arg for p in a.kwonlyargs}, cls))
                visit(child, stack + [child.name], cls)
            elif isinstance(child, ast.ClassDef):
                visit(child, stack + [child.name], child.name)
            else:
                visit(child, stack, cls)

    visit(tree, [], None)
    return out


def _callable_refs(node: ast.AST, aliases: dict[str, str]) -> list[str]:
    """Names that a jit-wrapper argument might bind to a local def."""
    if isinstance(node, ast.Name):
        return [node.id]
    if isinstance(node, ast.Attribute):
        # self.method or module.fn — keep the final attribute for matching.
        return [node.attr]
    if isinstance(node, ast.Call):
        if resolves_to(node.func, aliases, {"functools.partial"}) and node.args:
            return _callable_refs(node.args[0], aliases)
    if isinstance(node, ast.Lambda):
        return []   # handled by the caller via the node itself
    return []


def jit_reachable(tree: ast.Module,
                  aliases: dict[str, str]) -> dict[ast.AST, FuncInfo]:
    """Approximate the set of function defs that can run under a trace."""
    funcs = collect_functions(tree)
    by_name: dict[str, list[FuncInfo]] = {}
    for f in funcs:
        by_name.setdefault(f.node.name, []).append(f)

    entries: set[ast.AST] = set()
    for f in funcs:
        for dec in f.node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            if resolves_to(target, aliases, JIT_WRAPPERS):
                entries.add(f.node)
            elif (isinstance(dec, ast.Call)
                  and resolves_to(dec.func, aliases, {"functools.partial"})
                  and dec.args
                  and resolves_to(dec.args[0], aliases, JIT_WRAPPERS)):
                entries.add(f.node)

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        is_wrapper = resolves_to(node.func, aliases, JIT_WRAPPERS)
        is_defvjp = (isinstance(node.func, ast.Attribute)
                     and node.func.attr in ("defvjp", "defjvp", "def_fwd",
                                            "def_bwd"))
        if not (is_wrapper or is_defvjp):
            continue
        operands = list(node.args) + [kw.value for kw in node.keywords]
        for op in operands:
            if isinstance(op, ast.Lambda):
                entries.add(op)
                continue
            for ref in _callable_refs(op, aliases):
                for f in by_name.get(ref, []):
                    entries.add(f.node)

    # Fixpoint: nested defs + same-module callees of reachable functions.
    info = {f.node: f for f in funcs}
    reachable = {n for n in entries if n in info}
    frontier = list(reachable)
    while frontier:
        fn = frontier.pop()
        for node in ast.walk(fn):
            name = None
            if (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and node is not fn and node in info):
                name = node.name   # nested def: conservatively reachable
                cands = [info[node]]
            elif isinstance(node, ast.Call):
                if isinstance(node.func, ast.Name):
                    name = node.func.id
                elif (isinstance(node.func, ast.Attribute)
                      and isinstance(node.func.value, ast.Name)
                      and node.func.value.id == "self"):
                    name = node.func.attr
                cands = by_name.get(name, []) if name else []
            else:
                continue
            for c in cands:
                if c.node not in reachable:
                    reachable.add(c.node)
                    frontier.append(c.node)
    return {n: info[n] for n in reachable if n in info}


def module_int_constants(tree: ast.Module) -> dict[str, int]:
    """Top-level ``NAME = <int literal>`` assignments."""
    out: dict[str, int] = {}
    for node in tree.body:
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, int)
                and not isinstance(node.value.value, bool)):
            out[node.targets[0].id] = node.value.value
    return out


def contains_call(node: ast.AST) -> bool:
    return any(isinstance(n, ast.Call) for n in ast.walk(node))
