"""CCR rules: concurrency discipline over the lock-set dataflow.

CCR001  blocking-under-lock        blocking call (classifier, applied
                                   transitively through local helpers)
                                   while a lock is held
CCR002  hot-path-device-sync       device sync reachable (depth 2) from
                                   an engine hot-path root
CCR003  guarded-by-violation       write to a ``# guarded-by:`` field
                                   without the named lock held
CCR004  acquire-without-release    manual ``.acquire()`` not covered by
                                   a ``try/finally`` release
CCR005  thread-unguarded-capture   ``threading.Thread`` target mutates
                                   captured state with no lock guard
CCR006  lock-order-cycle           lexical ABBA ordering cycle
                                   (absorbed TPL004; the old id stays a
                                   live alias for baselines/disables)

Deliberate hazards go to the baseline with a ``why`` (pre-existing debt,
e.g. the ROADMAP item-3a admission fetch) or an inline
``# tpulint: disable=CCR00x`` (locally explainable, e.g. the sanctioned
one-step-delayed drain readback).
"""

from __future__ import annotations

import ast
from dataclasses import replace
from typing import Iterator

from ray_tpu.lint.callgraph import CallGraph, classify_blocking, _walk_body
from ray_tpu.lint.engine import FileContext, Finding, Rule, call_keyword, dotted
from ray_tpu.lint.concur.lockset import (
    MUTATOR_ATTRS,
    acquire_key,
    guarded_fields,
    holds_locks,
    iter_functions,
    iter_held,
    lock_key,
    self_attr_root,
)


class BlockingUnderLock(Rule):
    id = "CCR001"
    name = "blocking-under-lock"
    summary = "blocking call (plane/index RPC, sleep, join, unbounded get/wait, engine entry) while a lock is held"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        cg = CallGraph(ctx.tree)
        for fn, cls, qual in iter_functions(ctx.tree):
            seed = holds_locks(ctx.lines, fn, cls)
            skip: set[int] = set()
            seen: set[tuple[int, str, str]] = set()
            for node, held in iter_held(fn, cls, seed):
                if not held or not isinstance(node, ast.Call) or id(node) in skip:
                    continue
                if isinstance(node.func, ast.Attribute):
                    recv_key = lock_key(node.func.value, cls)
                    if recv_key is not None and recv_key in held:
                        # a call ON a held lock: cv.wait()/notify() inside
                        # ``with cv:`` is the condition-variable protocol,
                        # release/locked are bookkeeping — not hazards
                        continue
                effects = []
                eff = classify_blocking(node)
                if eff is not None:
                    effects = [eff]
                else:
                    callee = cg.resolve(node, cls)
                    if callee is not None:
                        effects = [
                            replace(e, chain=(callee.name,) + e.chain)
                            for e in cg.blocking_effects(callee, depth=2)
                            if e.kind != "device-sync"  # CCR002's half of the taxonomy
                        ]
                if not effects:
                    continue
                locks = ", ".join(sorted(held))
                for e in effects:
                    key = (id(node), e.kind, e.label)
                    if key in seen:
                        continue
                    seen.add(key)
                    yield self.finding(
                        ctx, node, f"{e.describe()} while holding {locks}", context=qual
                    )
                # nested calls inside a reported anchor would re-report
                # the same hazard from a deeper (noisier) vantage point
                skip.update(id(n) for n in ast.walk(node) if isinstance(n, ast.Call))


def _hot_root(name: str) -> bool:
    """Engine hot-path roots: the per-step serving loop and the telemetry
    sample sites it calls. ``_drain_once`` (the cold shutdown drain in
    serve/) is NOT one — only exact ``_drain``/``_drain_spec`` (the
    device-readback tails of the fused step) qualify."""
    return (
        name in ("step", "on_step", "record_step", "_drain", "_drain_spec")
        or name.startswith("_stage_")
        or name.startswith("_dispatch")
    )


class HotPathDeviceSync(Rule):
    id = "CCR002"
    name = "hot-path-device-sync"
    summary = "device-to-host sync (np.asarray/.item()/float(x[i])/block_until_ready) reachable from an engine hot-path root"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        cg = CallGraph(ctx.tree)
        owner: dict[int, str] = {}
        roots = []
        for fn, cls, qual in iter_functions(ctx.tree):
            for n in _walk_body(fn):
                if isinstance(n, ast.Call):
                    owner.setdefault(id(n), qual)
            if _hot_root(fn.name):
                roots.append((fn, qual))
        reported: set[int] = set()
        for fn, qual in roots:
            for e in cg.blocking_effects(fn, depth=2):
                if e.kind != "device-sync" or id(e.node) in reported:
                    continue
                reported.add(id(e.node))
                via = f" via {' -> '.join(e.chain)}" if e.chain else ""
                yield self.finding(
                    ctx, e.node,
                    f"device sync {e.label} reachable from hot path {qual}(){via}",
                    context=owner.get(id(e.node), qual),
                )


def _name_root(expr: ast.AST) -> str | None:
    """The root Name id of an Attribute/Subscript chain, or None."""
    while isinstance(expr, (ast.Attribute, ast.Subscript)):
        expr = expr.value
    return expr.id if isinstance(expr, ast.Name) else None


class GuardedByViolation(Rule):
    id = "CCR003"
    name = "guarded-by-violation"
    summary = "write to a `# guarded-by: <lock>` field without the named lock in the lock-set"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        fields = guarded_fields(ctx.lines, ctx.tree)
        if not fields:
            return
        for fn, cls, qual in iter_functions(ctx.tree):
            if cls not in fields or fn.name == "__init__":
                continue
            decls = fields[cls]
            seed = holds_locks(ctx.lines, fn, cls)
            for node, held in iter_held(fn, cls, seed):
                for attr, write in self._writes(node):
                    need = decls.get(attr)
                    if need is not None and need not in held:
                        yield self.finding(
                            ctx, node,
                            f"{write} self.{attr} without holding {need} (declared `# guarded-by`)",
                            context=qual,
                        )

    @staticmethod
    def _writes(node: ast.AST) -> Iterator[tuple[str, str]]:
        """(guarded attr, verb) for every write this node performs."""
        if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign, ast.Delete)):
            targets = (
                node.targets if isinstance(node, (ast.Assign, ast.Delete)) else [node.target]
            )
            verb = "del of" if isinstance(node, ast.Delete) else "write to"
            for t in targets:
                for leaf in t.elts if isinstance(t, (ast.Tuple, ast.List)) else [t]:
                    attr = self_attr_root(leaf)
                    if attr is not None:
                        yield attr, verb
        elif isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
            if node.func.attr in MUTATOR_ATTRS:
                attr = self_attr_root(node.func.value)
                if attr is not None:
                    yield attr, f".{node.func.attr}() on"


class AcquireWithoutRelease(Rule):
    id = "CCR004"
    name = "acquire-without-release"
    summary = "manual `.acquire()` whose release is not guaranteed by a try/finally"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for fn, cls, qual in iter_functions(ctx.tree):
            yield from self._block(fn.body, cls, qual, [], [], ctx)

    def _block(self, stmts, cls, qual, chain, tries, ctx) -> Iterator[Finding]:
        for i, stmt in enumerate(stmts):
            k = acquire_key(stmt, cls)
            if k is not None:
                recv = dotted(stmt.value.func.value)
                if not (
                    any(self._releases(t.finalbody, recv) for t in tries)
                    or self._released_after(chain + [(stmts, i)], recv)
                ):
                    yield self.finding(
                        ctx, stmt.value,
                        f"{recv}.acquire() is not followed by (or enclosed in) a "
                        f"try/finally that calls {recv}.release() — an exception "
                        "leaks the lock; prefer `with`",
                        context=qual,
                    )
                continue
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
                continue  # own walk via iter_functions
            for blocks, sub_tries in self._child_blocks(stmt, tries):
                yield from self._block(blocks, cls, qual, chain + [(stmts, i)], sub_tries, ctx)

    @staticmethod
    def _child_blocks(stmt, tries):
        if isinstance(stmt, ast.Try):
            yield stmt.body, tries + [stmt]
            for h in stmt.handlers:
                yield h.body, tries
            yield stmt.orelse, tries
            yield stmt.finalbody, tries
            return
        for _, value in ast.iter_fields(stmt):
            if isinstance(value, list) and value and isinstance(value[0], ast.stmt):
                yield value, tries
            elif isinstance(value, list) and value and isinstance(value[0], ast.match_case):
                for case in value:
                    yield case.body, tries

    @classmethod
    def _released_after(cls, chain, recv) -> bool:
        """Is the statement AFTER the acquire (popping out of enclosing
        blocks when the acquire is a block's last statement — the
        hand-over-hand chained-locking shape) a try/finally releasing
        ``recv``?"""
        stmts, i = chain[-1]
        if i + 1 < len(stmts):
            nxt = stmts[i + 1]
            return isinstance(nxt, ast.Try) and cls._releases(nxt.finalbody, recv)
        if len(chain) > 1:
            return cls._released_after(chain[:-1], recv)
        return False

    @staticmethod
    def _releases(body, recv) -> bool:
        for stmt in body:
            for n in ast.walk(stmt):
                if (
                    isinstance(n, ast.Call)
                    and isinstance(n.func, ast.Attribute)
                    and n.func.attr == "release"
                    and dotted(n.func.value) == recv
                ):
                    return True
        return False


class ThreadUnguardedCapture(Rule):
    id = "CCR005"
    name = "thread-unguarded-capture"
    summary = "threading.Thread target mutates state captured from the spawning scope with no lock guard"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        for fn, cls, qual in iter_functions(ctx.tree):
            nested = {
                d.name: d
                for d, dcls, dq in iter_functions_within(fn)
            }
            outer_names = _assigned_names(fn)
            for node in _walk_body(fn):
                if not (isinstance(node, ast.Call) and _is_thread_ctor(node)):
                    continue
                tkw = call_keyword(node, "target")
                if tkw is None:
                    continue
                target = tkw.value
                if isinstance(target, ast.Name) and target.id in nested:
                    tfn = nested[target.id]
                    if holds_locks(ctx.lines, tfn, cls) or _has_lock_guard(tfn, cls):
                        continue
                    mutated = _mutated_captures(tfn, outer_names)
                    label = f"nested function {target.id}"
                elif isinstance(target, ast.Lambda):
                    mutated = _lambda_mutations(target, outer_names)
                    label = "lambda"
                else:
                    continue  # bound methods guard via their own class lock
                if mutated:
                    yield self.finding(
                        ctx, node,
                        f"Thread target {label} mutates captured state "
                        f"({', '.join(sorted(mutated))}) with no lock guard "
                        "(no `with <lock>:` in the target, no `# holds-lock:`)",
                        context=qual,
                    )


def iter_functions_within(fn: ast.FunctionDef | ast.AsyncFunctionDef):
    """Function defs nested directly under ``fn``'s lexical body (any
    block depth, but not inside a deeper def)."""

    def walk(stmts):
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield stmt, None, stmt.name
                continue
            for _, value in ast.iter_fields(stmt):
                if isinstance(value, list) and value and isinstance(value[0], ast.stmt):
                    yield from walk(value)
                elif isinstance(value, list) and value and isinstance(value[0], (ast.ExceptHandler, ast.match_case)):
                    for sub in value:
                        yield from walk(sub.body)

    yield from walk(fn.body)


def _is_thread_ctor(call: ast.Call) -> bool:
    name = dotted(call.func)
    return name is not None and (name == "Thread" or name.endswith(".Thread"))


def _assigned_names(fn) -> set[str]:
    names = {a.arg for a in fn.args.args + fn.args.kwonlyargs + fn.args.posonlyargs}
    for n in _walk_body(fn):
        if isinstance(n, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = n.targets if isinstance(n, ast.Assign) else [n.target]
            for t in targets:
                for leaf in t.elts if isinstance(t, (ast.Tuple, ast.List)) else [t]:
                    if isinstance(leaf, ast.Name):
                        names.add(leaf.id)
        elif isinstance(n, ast.NamedExpr) and isinstance(n.target, ast.Name):
            names.add(n.target.id)
        elif isinstance(n, (ast.For, ast.AsyncFor)) and isinstance(n.target, ast.Name):
            names.add(n.target.id)
    return names


def _has_lock_guard(tfn, cls) -> bool:
    for n in _walk_body(tfn):
        if isinstance(n, (ast.With, ast.AsyncWith)):
            for item in n.items:
                if lock_key(item.context_expr, cls) is not None:
                    return True
    return False


def _mutated_captures(tfn, outer_names: set[str]) -> set[str]:
    local = _assigned_names(tfn)
    nonlocals: set[str] = set()
    for n in _walk_body(tfn):
        if isinstance(n, ast.Nonlocal):
            nonlocals.update(n.names)
    out: set[str] = set()
    for n in _walk_body(tfn):
        if isinstance(n, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = n.targets if isinstance(n, ast.Assign) else [n.target]
            for t in targets:
                for leaf in t.elts if isinstance(t, (ast.Tuple, ast.List)) else [t]:
                    if isinstance(leaf, ast.Name) and leaf.id in nonlocals:
                        out.add(leaf.id)
                    elif isinstance(leaf, (ast.Attribute, ast.Subscript)):
                        root = _name_root(leaf)
                        if root in outer_names and root not in local:
                            out.add(root)
        elif isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute):
            if n.func.attr in MUTATOR_ATTRS:
                root = _name_root(n.func.value)
                if root in outer_names and root not in local:
                    out.add(root)
    return out


def _lambda_mutations(lam: ast.Lambda, outer_names: set[str]) -> set[str]:
    defaults = {a.arg for a in lam.args.args + lam.args.kwonlyargs}
    out: set[str] = set()
    for n in ast.walk(lam.body):
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute):
            if n.func.attr in MUTATOR_ATTRS:
                root = _name_root(n.func.value)
                if root is not None and root in outer_names and root not in defaults:
                    out.add(root)
    return out


# ---------------------------------------------------------------------------
# CCR006: lexical lock-ordering cycles (absorbed TPL004)
# ---------------------------------------------------------------------------
class _OrderVisitor(ast.NodeVisitor):
    """Collect outer->inner edges with the location of the inner acquire."""

    def __init__(self):
        self.edges: dict[tuple[str, str], ast.AST] = {}
        self._held: list[str] = []
        self._cls: list[str] = []

    def visit_ClassDef(self, node):
        self._cls.append(node.name)
        self.generic_visit(node)
        self._cls.pop()

    def _visit_fn(self, node):
        # a new function body starts with nothing lexically held: `with`
        # nesting does not cross call boundaries (that's the dynamic
        # sanitizer's job)
        held, self._held = self._held, []
        self.generic_visit(node)
        self._held = held

    visit_FunctionDef = _visit_fn
    visit_AsyncFunctionDef = _visit_fn

    def _visit_with(self, node):
        cls = self._cls[-1] if self._cls else None
        keys = []
        for item in node.items:
            k = lock_key(item.context_expr, cls)
            if k is not None:
                keys.append(k)
                for outer in self._held + keys[:-1]:
                    if outer != k:
                        self.edges.setdefault((outer, k), item.context_expr)
        self._held.extend(keys)
        for stmt in node.body:
            self.visit(stmt)
        if keys:
            del self._held[-len(keys):]

    visit_With = _visit_with
    visit_AsyncWith = _visit_with


def _cycles(edges: dict[tuple[str, str], ast.AST]) -> list[list[str]]:
    graph: dict[str, set[str]] = {}
    for a, b in edges:
        graph.setdefault(a, set()).add(b)
    out: list[list[str]] = []
    seen_cycles: set[tuple[str, ...]] = set()

    def dfs(start: str, node: str, path: list[str], visited: set[str]):
        for nxt in sorted(graph.get(node, ())):
            if nxt == start:
                cyc = path[:]
                # canonicalize rotation so each cycle reports once
                i = cyc.index(min(cyc))
                canon = tuple(cyc[i:] + cyc[:i])
                if canon not in seen_cycles:
                    seen_cycles.add(canon)
                    out.append(list(canon))
            elif nxt not in visited and len(path) < 8:
                visited.add(nxt)
                dfs(start, nxt, path + [nxt], visited)
                visited.discard(nxt)

    for start in sorted(graph):
        dfs(start, start, [start], {start})
    return out


class LockOrderCycle(Rule):
    id = "CCR006"
    name = "lock-order-cycle"
    summary = "lexical `with` nesting acquires module locks in inconsistent order (potential ABBA deadlock; alias: TPL004)"

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        v = _OrderVisitor()
        v.visit(ctx.tree)
        for cyc in _cycles(v.edges):
            # anchor the report at the acquire site of the first inverted
            # edge; every consecutive cycle pair is an edge key by
            # construction, so index directly — drift should fail loudly,
            # not anchor the finding (and its suppression point) elsewhere
            a, b = cyc[0], cyc[1 % len(cyc)]
            node = v.edges[(a, b)]
            order = " -> ".join(cyc + [cyc[0]])
            yield self.finding(
                ctx, node,
                f"lock ordering cycle {order}: two paths acquire these locks in "
                "opposite order; pick one global order (see core/lock_sanitizer.py)",
                context="",
            )


CONCUR_RULES = (
    BlockingUnderLock,
    HotPathDeviceSync,
    GuardedByViolation,
    AcquireWithoutRelease,
    ThreadUnguardedCapture,
    LockOrderCycle,
)


def all_concur_rules(select: set[str] | None = None) -> list[Rule]:
    from ray_tpu.lint.engine import canonical_rule

    rules = [cls() for cls in CONCUR_RULES]
    if select:
        canon = {canonical_rule(s) for s in select}
        rules = [r for r in rules if r.id in canon or r.name in select]
    return rules


def concur_rule_catalog() -> list[tuple[str, str, str]]:
    return [(cls.id, cls.name, cls.summary) for cls in CONCUR_RULES]


def concur_rule_ids() -> set[str]:
    return {cls.id for cls in CONCUR_RULES}
