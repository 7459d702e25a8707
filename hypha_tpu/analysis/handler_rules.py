"""Whole-program protocol conformance (the CHECK half of the wire surface).

The runtime protocol family (:mod:`.proto_rules`) validates message
*shapes* against the live registry.  What it cannot see is the wire
surface's *usage*: nine protocols whose correctness hinges on every
declared message actually having a producer and a consumer somewhere in
the repo, every generation-stamped handler fencing staleness before it
mutates state, and every round-tagged send stamping a live round.  These
passes walk the :class:`~.graph.Project` index instead of one file:

  * ``proto-no-sender`` / ``proto-no-handler`` — every
    ``PROTOCOL_MESSAGES`` entry must have at least one construction site
    and at least one consumption site repo-wide.  A declared message with
    neither is dead wire surface — it rots unreviewed until someone
    "re-uses" it wrong.
  * ``handler-mutates-before-guard`` — a handler registered for a
    generation-carrying message (``generation`` /
    ``scheduler_generation`` / ``ps_generation`` fields) must perform a
    staleness comparison before its first state mutation, or a zombie
    predecessor's traffic mutates live state before anyone checks who
    sent it (the double-applied broadcasts and zombie-scheduler traffic
    PRs 11-16 caught by hand).
  * ``round-tag-not-live`` — a wire-message constructor passing
    ``round=``/``epoch=``/``round_num=`` must derive the value from live
    state (a variable, attribute, call or parameter), not a literal
    constant — directly or through a constant-only local (taint-lite
    provenance) — or the message folds into whichever round the receiver
    happens to have open.

Evidence model for coverage (deliberately structural, not type-inferred):

  sender   — any constructor call ``Msg(...)`` outside the message's own
             class body (factories like ``from_header`` are consumer-side
             decode, not production);
  consumer — a handler registration ``node.on(PROTO, Msg)``, an
             ``isinstance(x, Msg)`` / ``match``-case class pattern, a
             parameter/variable/field/return annotation naming ``Msg``,
             or reply position (constructed inside a registered handler
             function, or as the argument of a ``respond(...)`` call)
             provided the protocol has at least one ``.request(...)``
             site awaiting the reply.

``WAIVERS`` documents deliberate exceptions by message name; each entry
carries a reason, shows up in the coverage table (``waived``), and goes
stale loudly: a waiver for a name no longer in the manifest is itself a
violation (``proto-unused-waiver``), same philosophy as
``unused-suppression``.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field

from .core import Violation, dotted_name
from .graph import ModuleInfo, Project

__all__ = [
    "check",
    "coverage",
    "WAIVERS",
    "GENERATION_FIELDS",
    "ROUND_KWARGS",
]

GENERATION_FIELDS = {"generation", "scheduler_generation", "ps_generation"}
ROUND_KWARGS = {"round", "epoch", "round_num"}

# Attribute-method calls that mutate the receiver in place — counted as
# state mutations by the generation-guard pass when the receiver is an
# attribute (``self.seen.add(...)``), not a bare local.
_MUTATOR_METHODS = {
    "append",
    "add",
    "update",
    "pop",
    "remove",
    "discard",
    "clear",
    "extend",
    "insert",
    "setdefault",
}

# Documented waivers for the sender/handler coverage pass: message name ->
# reason.  These live in reviewable code (not inline comments), count
# nowhere against the suppression budget, and fail the build when stale.
WAIVERS: dict[str, str] = {
    # Wire-parity surface with the Rust fabric library (SURVEY: "unused in
    # current flow"): parameters move over the dedicated "ps" byte stream,
    # not the control-plane API protocol, but the frames stay declared so
    # both codecs agree on the full message space.
    "ParameterPull": "Rust lib wire parity; params ride the ps byte stream",
    "ParameterPush": "Rust lib wire parity; params ride the ps byte stream",
}

# The global waiver table is judged for staleness only when the canonical
# wire-surface module is part of the linted tree: a fixture package or a
# run over scripts declares none of the waived names, and that absence says
# nothing about whether the waiver went stale.  Explicitly-passed waivers
# (``check(project, waivers=...)``) are always enforced.
WAIVER_ANCHOR = "hypha_tpu.messages"


def _guardish(name: str) -> bool:
    """Does this dotted name look like generation state?"""
    low = name.lower()
    return (
        "generation" in low
        or low.endswith("_gen")
        or any(seg == "gen" for seg in low.split("."))
    )


# --------------------------------------------------------------------------
# Collection
# --------------------------------------------------------------------------


@dataclass(slots=True)
class _Evidence:
    senders: list[tuple[str, int]] = field(default_factory=list)
    handlers: list[tuple[str, int]] = field(default_factory=list)
    isinstance_sites: list[tuple[str, int]] = field(default_factory=list)
    annotations: list[tuple[str, int]] = field(default_factory=list)
    replies: list[tuple[str, int]] = field(default_factory=list)

    def has_sender(self) -> bool:
        return bool(self.senders)

    def has_consumer(self, proto_requested: bool) -> bool:
        if self.handlers or self.isinstance_sites or self.annotations:
            return True
        return bool(self.replies) and proto_requested


@dataclass(slots=True)
class _Index:
    evidence: dict[str, _Evidence] = field(default_factory=dict)
    # protocol id -> [(module key, line)] of .request()/.publish() sites
    request_sites: dict[str, list[tuple[str, int]]] = field(
        default_factory=dict
    )
    # handler fn qualname -> (protocol, msg name, registration line, module)
    handler_fns: dict[str, tuple[str, str, int, str]] = field(
        default_factory=dict
    )
    # constructor sites: (msg name, module key, line, enclosing fn qualname)
    ctor_sites: list[tuple[str, str, int, str | None]] = field(
        default_factory=list
    )
    # round-kwarg violations found during the walk
    round_violations: list[Violation] = field(default_factory=list)

    def ev(self, name: str) -> _Evidence:
        return self.evidence.setdefault(name, _Evidence())


def _msg_name(node: ast.expr | None, wire: set[str]) -> str | None:
    if node is None:
        return None
    name = dotted_name(node)
    if not name:
        return None
    tail = name.rsplit(".", 1)[-1]
    return tail if tail in wire else None


def _find_on_call(node: ast.expr) -> ast.Call | None:
    """Descend a fluent chain (``.match(...).concurrency(8)``) to the
    innermost ``.on(proto, Type)`` call."""
    while isinstance(node, ast.Call):
        func = node.func
        if not isinstance(func, ast.Attribute):
            return None
        if func.attr == "on":
            return node
        node = func.value
    return None


def _annotation_names(node: ast.expr) -> set[str]:
    """Every bare/dotted name mentioned by an annotation expression,
    including inside string annotations and subscripts."""
    out: set[str] = set()
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name):
            out.add(sub.id)
        elif isinstance(sub, ast.Attribute):
            out.add(sub.attr)
        elif isinstance(sub, ast.Constant) and isinstance(sub.value, str):
            for tok in (
                sub.value.replace("[", " ")
                .replace("]", " ")
                .replace("|", " ")
                .replace(",", " ")
                .split()
            ):
                out.add(tok.rsplit(".", 1)[-1])
    return out


def _constant_only_locals(fn_node: ast.AST) -> set[str]:
    """Names whose every assignment in this function is a literal constant
    (the taint-lite half of round provenance).  Loop targets, augmented
    assignments and parameters make a name live."""
    params = {
        a.arg
        for a in ast.walk(fn_node)
        if isinstance(a, ast.arg)
    }
    assigns: dict[str, list[bool]] = {}
    for node in ast.walk(fn_node):
        if isinstance(node, ast.Assign):
            is_const = isinstance(node.value, ast.Constant)
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    assigns.setdefault(tgt.id, []).append(is_const)
        elif isinstance(node, ast.AugAssign) and isinstance(
            node.target, ast.Name
        ):
            assigns.setdefault(node.target.id, []).append(False)
        elif isinstance(node, (ast.For, ast.AsyncFor)) and isinstance(
            node.target, ast.Name
        ):
            assigns.setdefault(node.target.id, []).append(False)
        elif isinstance(node, ast.withitem) and isinstance(
            node.optional_vars, ast.Name
        ):
            assigns.setdefault(node.optional_vars.id, []).append(False)
        elif isinstance(node, (ast.comprehension,)) and isinstance(
            node.target, ast.Name
        ):
            assigns.setdefault(node.target.id, []).append(False)
    return {
        n
        for n, consts in assigns.items()
        if all(consts) and n not in params
    }


class _ModuleIndexer(ast.NodeVisitor):
    """One source-order walk of a module, feeding the conformance index."""

    def __init__(self, project: Project, mod: ModuleInfo, index: _Index) -> None:
        self.project = project
        self.mod = mod
        self.index = index
        self.wire = set(project.wire_classes)
        self._fn_stack: list[str] = []  # graph-style qualnames
        self._class_stack: list[str] = []
        self._const_locals_stack: list[set[str]] = []

    # ------------------------------------------------------------ scoping

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._class_stack.append(node.name)
        self.generic_visit(node)
        self._class_stack.pop()

    def _qual(self, name: str) -> str:
        if self._fn_stack:
            return f"{self._fn_stack[-1]}.<locals>.{name}"
        if self._class_stack:
            return f"{self.mod.key}:{'.'.join(self._class_stack)}.{name}"
        return f"{self.mod.key}:{name}"

    def _visit_fn(self, node) -> None:
        for a in list(node.args.args) + list(node.args.kwonlyargs):
            if a.annotation is not None:
                self._note_annotation(a.annotation, node.lineno)
        if node.returns is not None:
            # `-> GenerateResponse` on a handler is the reply contract the
            # requester awaits — consumer evidence for response types that
            # are never `.on`-registered themselves.
            self._note_annotation(node.returns, node.lineno)
        self._fn_stack.append(self._qual(node.name))
        self._const_locals_stack.append(_constant_only_locals(node))
        self.generic_visit(node)
        self._const_locals_stack.pop()
        self._fn_stack.pop()

    visit_FunctionDef = _visit_fn
    visit_AsyncFunctionDef = _visit_fn

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        self._note_annotation(node.annotation, node.lineno)
        self.generic_visit(node)

    def _note_annotation(self, ann: ast.expr, line: int) -> None:
        for name in _annotation_names(ann) & self.wire:
            self.index.ev(name).annotations.append((self.mod.key, line))

    def visit_Match(self, node: ast.Match) -> None:
        for case in node.cases:
            for sub in ast.walk(case.pattern):
                if isinstance(sub, ast.MatchClass):
                    name = _msg_name(sub.cls, self.wire)
                    if name:
                        self.index.ev(name).isinstance_sites.append(
                            (self.mod.key, sub.cls.lineno)
                        )
        self.generic_visit(node)

    # -------------------------------------------------------------- calls

    def visit_Call(self, node: ast.Call) -> None:
        name = dotted_name(node.func)
        # dotted_name() is None for chained receivers like
        # `node.on(...).respond_with(fn)` (the receiver is a Call, not a
        # Name), so take the method name straight off the Attribute.
        if isinstance(node.func, ast.Attribute):
            tail = node.func.attr
        else:
            tail = name.rsplit(".", 1)[-1] if name else None

        # Constructor site (sender / round-provenance evidence).  A
        # construction inside the message's OWN class body (`from_header`
        # and friends) is consumer-side decode, not production.
        ctor = _msg_name(node.func, self.wire)
        if ctor is not None:
            enclosing = self._fn_stack[-1] if self._fn_stack else None
            if ctor not in self._class_stack:
                self.index.ctor_sites.append(
                    (ctor, self.mod.key, node.lineno, enclosing)
                )
            self._check_round_kwargs(ctor, node)

        if tail == "isinstance" and len(node.args) == 2:
            types = node.args[1]
            elts = types.elts if isinstance(types, ast.Tuple) else [types]
            for e in elts:
                n = _msg_name(e, self.wire)
                if n:
                    self.index.ev(n).isinstance_sites.append(
                        (self.mod.key, node.lineno)
                    )
        elif tail == "respond":
            # into_stream loops: `respond(Ack(...))` — reply position.
            for a in node.args:
                if isinstance(a, ast.Call):
                    n = _msg_name(a.func, self.wire)
                    if n:
                        self.index.ev(n).replies.append(
                            (self.mod.key, a.lineno)
                        )
        elif tail == "on" and isinstance(node.func, ast.Attribute) and node.args:
            proto = self.project.resolve_constant(self.mod, node.args[0])
            if proto is not None and len(node.args) >= 2:
                n = dotted_name(node.args[1])
                if n:
                    msg = n.rsplit(".", 1)[-1]
                    self.index.ev(msg).handlers.append(
                        (self.mod.key, node.lineno)
                    )
        elif tail == "respond_with" and isinstance(node.func, ast.Attribute):
            on_call = _find_on_call(node.func.value)
            if on_call is not None and len(on_call.args) >= 2:
                proto = self.project.resolve_constant(self.mod, on_call.args[0])
                msg = (dotted_name(on_call.args[1]) or "?").rsplit(".", 1)[-1]
                if proto is not None and node.args:
                    hq = self._resolve_handler(node.args[0])
                    if hq is not None:
                        self.index.handler_fns[hq] = (
                            proto,
                            msg,
                            node.lineno,
                            self.mod.key,
                        )
        elif tail == "request" and isinstance(node.func, ast.Attribute):
            if len(node.args) >= 2:
                proto = self.project.resolve_constant(self.mod, node.args[1])
                if proto is not None:
                    self.index.request_sites.setdefault(proto, []).append(
                        (self.mod.key, node.lineno)
                    )
        elif tail == "publish" and isinstance(node.func, ast.Attribute):
            if node.args:
                topic = self.project.resolve_constant(self.mod, node.args[0])
                if topic is not None:
                    self.index.request_sites.setdefault(
                        f"gossip:{topic}", []
                    ).append((self.mod.key, node.lineno))
        self.generic_visit(node)

    def _resolve_handler(self, arg: ast.expr) -> str | None:
        """A respond_with argument to a project function qualname —
        local closure first, then module scope, then self-methods."""
        name = dotted_name(arg)
        if not name:
            return None
        if "." not in name:
            for q in (
                (
                    f"{self._fn_stack[-1]}.<locals>.{name}"
                    if self._fn_stack
                    else None
                ),
                f"{self.mod.key}:{name}",
            ):
                if q and q in self.project.functions:
                    return q
            return None
        head, _, meth = name.rpartition(".")
        if head in ("self", "cls") and self._class_stack:
            q = f"{self.mod.key}:{self._class_stack[-1]}.{meth}"
            if q in self.project.functions:
                return q
        return self.project.resolve_callable(
            self.mod, name, self._class_stack[-1] if self._class_stack else None
        )

    # ------------------------------------------------- round provenance

    def _check_round_kwargs(self, ctor: str, node: ast.Call) -> None:
        const_locals = (
            self._const_locals_stack[-1] if self._const_locals_stack else set()
        )
        for kw in node.keywords:
            if kw.arg not in ROUND_KWARGS:
                continue
            bad: str | None = None
            v = kw.value
            if isinstance(v, ast.Constant) and v.value is not None:
                bad = f"literal {v.value!r}"
            elif isinstance(v, ast.Name) and v.id in const_locals:
                bad = f"`{v.id}` (assigned only constants here)"
            elif isinstance(v, ast.UnaryOp) and isinstance(
                v.operand, ast.Constant
            ):
                bad = "literal"
            if bad is not None:
                self.index.round_violations.append(
                    self.mod.src.violation(
                        "round-tag-not-live",
                        node,
                        f"{ctor}(..., {kw.arg}=...) stamps {bad}, not a "
                        f"live round variable — the receiver folds this "
                        f"into whichever round it has open; derive the "
                        f"tag from the round actually being processed",
                    )
                )


# --------------------------------------------------------------------------
# Generation-guard pass
# --------------------------------------------------------------------------


def _stmt_has_guard(stmt: ast.stmt | ast.expr) -> bool:
    for node in ast.walk(stmt):
        if isinstance(node, ast.Compare):
            for sub in ast.walk(node):
                n = dotted_name(sub)
                if n and _guardish(n):
                    return True
                if (
                    isinstance(sub, ast.Call)
                    and dotted_name(sub.func) == "getattr"
                    and len(sub.args) >= 2
                    and isinstance(sub.args[1], ast.Constant)
                    and _guardish(str(sub.args[1].value))
                ):
                    return True
    return False


def _stmt_mutation(stmt: ast.stmt) -> ast.AST | None:
    """The first state mutation in a SIMPLE statement: a store through an
    attribute (``self.x = ..``, ``obj.seq[0] = ..``), an augmented
    attribute assign, or a mutator-method call on an attribute."""
    if isinstance(stmt, ast.Assign):
        for tgt in stmt.targets:
            for sub in ast.walk(tgt):
                if isinstance(sub, ast.Attribute):
                    return stmt
    elif isinstance(stmt, ast.AugAssign):
        for sub in ast.walk(stmt.target):
            if isinstance(sub, ast.Attribute):
                return stmt
    elif isinstance(stmt, ast.Expr):
        for node in ast.walk(stmt):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _MUTATOR_METHODS
                and isinstance(node.func.value, ast.Attribute)
            ):
                return node
    return None


def _first_unguarded_mutation(body: list[ast.stmt]) -> ast.AST | None:
    """Source-order scan: the first state mutation not preceded by a
    generation comparison.  An ``if`` whose TEST is a guard counts from
    that statement on (the early-exit shape); a guard buried in one branch
    does not guard the statements after the branch."""

    def scan(stmts: list[ast.stmt], guarded: bool) -> tuple[bool, ast.AST | None]:
        for stmt in stmts:
            if isinstance(
                stmt,
                (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef),
            ):
                continue
            if isinstance(stmt, (ast.If, ast.While)):
                if _stmt_has_guard(stmt.test):
                    guarded = True
                if not guarded:
                    _, bad = scan(stmt.body, guarded)
                    if bad is not None:
                        return guarded, bad
                    _, bad = scan(stmt.orelse, guarded)
                    if bad is not None:
                        return guarded, bad
                continue
            if isinstance(stmt, (ast.For, ast.AsyncFor)):
                if not guarded:
                    _, bad = scan(stmt.body + stmt.orelse, guarded)
                    if bad is not None:
                        return guarded, bad
                continue
            if isinstance(stmt, ast.Try):
                if not guarded:
                    inner = (
                        stmt.body
                        + [s for h in stmt.handlers for s in h.body]
                        + stmt.orelse
                        + stmt.finalbody
                    )
                    _, bad = scan(inner, guarded)
                    if bad is not None:
                        return guarded, bad
                continue
            if isinstance(stmt, (ast.With, ast.AsyncWith)):
                if _stmt_has_guard(stmt):
                    guarded = True
                if not guarded:
                    _, bad = scan(stmt.body, guarded)
                    if bad is not None:
                        return guarded, bad
                continue
            if _stmt_has_guard(stmt):
                guarded = True
                continue
            if not guarded:
                bad = _stmt_mutation(stmt)
                if bad is not None:
                    return guarded, bad
        return guarded, None

    _, bad = scan(body, False)
    return bad


def _check_generation_guards(
    project: Project, index: _Index
) -> list[Violation]:
    out: list[Violation] = []
    for hq, (proto, msg, _line, _mod) in sorted(index.handler_fns.items()):
        fields = project.wire_classes.get(msg)
        if not fields or not fields & GENERATION_FIELDS:
            continue
        fn = project.functions.get(hq)
        if fn is None:
            continue
        mod = project.modules.get(fn.module)
        if mod is None:
            continue
        bad = _first_unguarded_mutation(list(getattr(fn.node, "body", [])))
        if bad is not None:
            out.append(
                mod.src.violation(
                    "handler-mutates-before-guard",
                    bad,
                    f"handler `{hq.rsplit(':', 1)[-1]}` for "
                    f"generation-stamped {msg} (on {proto}) mutates state "
                    f"before comparing generations — a zombie "
                    f"predecessor's message lands here unfenced; hoist "
                    f"the staleness check above the first mutation",
                )
            )
    return out


# --------------------------------------------------------------------------
# Coverage + entry points
# --------------------------------------------------------------------------


def _build_index(project: Project) -> _Index:
    index = _Index()
    for mod in project.modules.values():
        if ".analysis" in f".{mod.key}":
            continue
        _ModuleIndexer(project, mod, index).visit(mod.src.tree)
    # Sender evidence from constructor sites; reply evidence for ctors
    # inside registered handler bodies.
    handler_prefixes = tuple(index.handler_fns)
    for ctor, mkey, line, enclosing in index.ctor_sites:
        index.ev(ctor).senders.append((mkey, line))
        if enclosing is not None and (
            enclosing in index.handler_fns
            or any(
                enclosing.startswith(h + ".<locals>")
                for h in handler_prefixes
            )
        ):
            index.ev(ctor).replies.append((mkey, line))
    return index


def coverage(project: Project) -> dict[str, dict[str, dict]]:
    """Per-protocol, per-message sender/handler coverage table."""
    index = _build_index(project)
    table: dict[str, dict[str, dict]] = {}
    for proto in sorted(project.manifest):
        requested = proto in index.request_sites
        row: dict[str, dict] = {}
        for msg in project.manifest[proto]:
            ev = index.ev(msg)
            row[msg] = {
                "senders": len(ev.senders),
                "handlers": len(ev.handlers),
                "isinstance": len(ev.isinstance_sites),
                "annotations": len(ev.annotations),
                "replies": len(ev.replies),
                "covered": ev.has_sender() and ev.has_consumer(requested),
                "waived": msg in WAIVERS,
            }
        table[proto] = row
    return table


def check(project: Project, waivers: dict[str, str] | None = None) -> list[Violation]:
    enforce_stale = waivers is not None or any(
        k == WAIVER_ANCHOR or k.endswith("." + WAIVER_ANCHOR)
        for k in project.modules
    )
    waivers = WAIVERS if waivers is None else waivers
    index = _build_index(project)
    out: list[Violation] = list(index.round_violations)
    declared: set[str] = set()
    for proto in sorted(project.manifest):
        requested = proto in index.request_sites
        for msg in project.manifest[proto]:
            declared.add(msg)
            if msg in waivers:
                continue
            site = project.wire_sites.get(msg)
            mod = project.modules.get(site[0]) if site else None
            if mod is None:
                continue  # declared but defined outside the linted tree
            anchor_line = site[1]
            ev = index.ev(msg)
            if not ev.has_sender():
                out.append(
                    Violation(
                        rule="proto-no-sender",
                        path=mod.src.path,
                        line=anchor_line,
                        message=(
                            f"{msg} is declared on {proto} but never "
                            f"constructed outside its own class body — "
                            f"dead wire surface (or the sender lives "
                            f"outside the linted tree: waive it in "
                            f"handler_rules.WAIVERS with a reason)"
                        ),
                        suppressed=mod.src.suppressed_at(anchor_line, "proto-no-sender"),
                    )
                )
            if not ev.has_consumer(requested):
                out.append(
                    Violation(
                        rule="proto-no-handler",
                        path=mod.src.path,
                        line=anchor_line,
                        message=(
                            f"{msg} is declared on {proto} but no handler "
                            f"registration, isinstance/match, annotation "
                            f"or requested-reply site consumes it — "
                            f"nothing can receive this message"
                        ),
                        suppressed=mod.src.suppressed_at(anchor_line, "proto-no-handler"),
                    )
                )
    # Stale waivers fail loudly, like unused-suppression.
    for name in sorted(waivers) if enforce_stale else []:
        if name not in declared:
            anchor = next(iter(project.modules.values()), None)
            out.append(
                Violation(
                    rule="proto-unused-waiver",
                    path=anchor.src.path if anchor else "<project>",
                    line=1,
                    message=(
                        f"handler_rules.WAIVERS entry {name!r} matches no "
                        f"declared protocol message — delete it"
                    ),
                )
            )
    out.extend(_check_generation_guards(project, index))
    return out
