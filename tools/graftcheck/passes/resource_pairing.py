"""Resource-pairing checker: begin/end pairs must balance on every
exit path of their owning scope, or ownership must visibly transfer.

Three rules:

``pair-span``
    Every ``span_begin(...)`` handle must be ``span_end(...)``-ed in
    the same function, or *escape* (stored on an object/container,
    returned, or passed to another call — ownership transferred).  A
    discarded handle (bare expression statement) can never be ended:
    the span leaks open and its trace is never recorded.

``pair-acquire``
    Every explicit ``<lock>.acquire()`` (on a lock-named receiver:
    ``*lock*``, ``*_cv*``, ``*sem*``, ``*slots*``) needs a matching
    ``<lock>.release()`` on the same receiver in the same function,
    and at least one such release must sit in a ``finally`` block —
    an exception between acquire and a straight-line release leaves
    the lock held forever (prefer ``with``).  Conditional acquires
    (``if not x.acquire(timeout=...)``) follow the same contract.

``pair-refcount``
    ``pool.alloc()`` / ``pool.incref(pages)`` bookkeeping: a
    discarded ``alloc()`` result leaks a page outright; an
    ``alloc()``/``incref()`` whose pages stay in a local that neither
    escapes nor is ``decref``-ed in the function leaks on every path.
    Class-level balance: a class that increfs/allocs must decref
    *somewhere* (a class that only ever takes references cannot give
    them back).

``pair-draft``
    Speculative-decode draft-page discipline: a function that calls
    ``acquire_draft_pages`` (``serving/kv_cache.py`` ``KVCache``:
    provisional KV pages for an unverified draft) must also call
    ``rollback_draft_pages`` or ``release_pages`` in the same function —
    a rejected draft whose pages are never rolled back (or a fault path
    that skips the slot-release) strands refcounts the pool can only
    leak.  A leading underscore on any of the three names is the same
    name.
"""
from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Set, Tuple

from ..core import SourceFile, Violation, call_name, register_pass

_LOCKISH_RE = re.compile(r"lock|_cv\b|cv$|sem|slots|mutex", re.I)
_POOLISH_RE = re.compile(r"pool", re.I)


def _recv_repr(node: ast.AST) -> str:
    """Canonical text of a call receiver ('self._lock', '_ring_lock',
    'slot.pages', ...) for same-receiver matching."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return f"{_recv_repr(node.value)}.{node.attr}"
    return ast.dump(node)


_func_name = call_name


def _functions(sf: SourceFile):
    """(qualname, node) for every function/method, outermost only
    (nested defs analyzed as their own scopes)."""
    if sf.tree is None:
        return
    stack: List[Tuple[str, ast.AST]] = [("", sf.tree)]
    while stack:
        prefix, node = stack.pop()
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qn = f"{prefix}.{child.name}" if prefix else child.name
                yield qn, child
                stack.append((qn, child))
            elif isinstance(child, ast.ClassDef):
                qn = f"{prefix}.{child.name}" if prefix else child.name
                stack.append((qn, child))


def _own_nodes(fn: ast.AST):
    """AST nodes of this function, EXCLUDING nested function bodies
    (each nested scope is analyzed separately)."""
    todo = list(ast.iter_child_nodes(fn))
    while todo:
        n = todo.pop()
        yield n
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda)):
            continue
        todo.extend(ast.iter_child_nodes(n))


def _in_finally_lines(fn: ast.AST) -> Set[int]:
    lines: Set[int] = set()
    for n in _own_nodes(fn):
        if isinstance(n, ast.Try):
            for st in n.finalbody:
                for sub in ast.walk(st):
                    if hasattr(sub, "lineno"):
                        lines.add(sub.lineno)
    return lines


def _name_escapes(fn: ast.AST, name: str, after_line: int,
                  skip_call_attrs: Tuple[str, ...] = ()) -> bool:
    """Does ``name`` visibly leave this scope after ``after_line``?
    Escape = used as a call argument (any call whose method is not in
    ``skip_call_attrs``), returned/yielded, stored into an attribute /
    subscript / container literal, or captured in a closure."""
    for n in _own_nodes(fn):
        line = getattr(n, "lineno", 0)
        if line < after_line:
            continue
        if isinstance(n, ast.Call):
            fname = _func_name(n)
            if fname in skip_call_attrs:
                continue
            for arg in list(n.args) + [kw.value for kw in n.keywords]:
                for sub in ast.walk(arg):
                    if isinstance(sub, ast.Name) and sub.id == name:
                        return True
        if isinstance(n, (ast.Return, ast.Yield, ast.YieldFrom)) \
                and n.value is not None:
            for sub in ast.walk(n.value):
                if isinstance(sub, ast.Name) and sub.id == name:
                    return True
        if isinstance(n, ast.Assign):
            rhs_has = any(isinstance(s, ast.Name) and s.id == name
                          for s in ast.walk(n.value))
            if rhs_has and any(
                    not isinstance(t, ast.Name) for t in n.targets):
                return True
            if rhs_has and any(isinstance(t, ast.Name) and t.id != name
                               for t in n.targets):
                # aliased to another local: give up tracking, assume ok
                return True
    return False


@register_pass(
    "resource-pairing", ("pair-span", "pair-acquire", "pair-refcount",
                         "pair-draft"),
    doc="span_begin/span_end, lock acquire/release (exception-safe), "
        "PagePool alloc/incref/decref pairing, and speculative "
        "draft-page acquire/rollback pairing")
def run(files: List[SourceFile]) -> List[Violation]:
    out: List[Violation] = []
    for sf in files:
        if sf.tree is None:
            continue
        # cheap textual prefilter: most files contain none of the
        # paired APIs, and per-function AST walks are the hot path
        has_span = "span_begin" in sf.text
        has_acq = ".acquire(" in sf.text
        has_ref = "incref" in sf.text or ".alloc(" in sf.text
        has_draft = "acquire_draft_pages" in sf.text
        if not (has_span or has_acq or has_ref or has_draft):
            continue
        for qn, fn in _functions(sf):
            if has_span:
                out += _check_spans(sf, qn, fn)
            if has_acq:
                out += _check_acquires(sf, qn, fn)
            if has_ref:
                out += _check_refcounts_fn(sf, qn, fn)
            if has_draft:
                out += _check_draft_pages(sf, qn, fn)
        if has_ref:
            out += _check_refcounts_class(sf)
    return out


# -- pair-span ---------------------------------------------------------------

def _check_spans(sf: SourceFile, qn: str, fn: ast.AST) -> List[Violation]:
    out: List[Violation] = []
    # name -> line of span_begin assignment
    begun: Dict[str, int] = {}
    ended: Set[str] = set()
    for n in _own_nodes(fn):
        if isinstance(n, ast.Expr) and isinstance(n.value, ast.Call) \
                and _func_name(n.value) == "span_begin":
            out.append(Violation(
                "pair-span", sf.path, n.lineno, f"{qn}:discard",
                "span_begin() handle discarded — nothing can ever "
                "span_end() it; keep the handle or use trace_span()"))
        if isinstance(n, ast.Assign) and isinstance(n.value, ast.Call) \
                and _func_name(n.value) == "span_begin":
            t = n.targets[0]
            if isinstance(t, ast.Name):
                begun[t.id] = n.lineno
            # assignment to an attribute/subscript IS the escape
        if isinstance(n, ast.Call) and _func_name(n) == "span_end":
            for arg in n.args:
                for sub in ast.walk(arg):
                    if isinstance(sub, ast.Name):
                        ended.add(sub.id)
    for name, line in sorted(begun.items()):
        if name in ended:
            continue
        if _name_escapes(fn, name, line, skip_call_attrs=("span_begin",)):
            continue
        out.append(Violation(
            "pair-span", sf.path, line, f"{qn}:{name}",
            f"span handle {name!r} from span_begin() is neither "
            f"span_end()-ed nor handed off in this function — the "
            f"span leaks open"))
    return out


# -- pair-acquire ------------------------------------------------------------

def _check_acquires(sf: SourceFile, qn: str, fn: ast.AST) -> List[Violation]:
    out: List[Violation] = []
    acquires: List[Tuple[str, int]] = []
    releases: List[Tuple[str, int]] = []
    for n in _own_nodes(fn):
        if not isinstance(n, ast.Call) or \
                not isinstance(n.func, ast.Attribute):
            continue
        recv = _recv_repr(n.func.value)
        if not _LOCKISH_RE.search(recv):
            continue
        if n.func.attr == "acquire":
            acquires.append((recv, n.lineno))
        elif n.func.attr == "release":
            releases.append((recv, n.lineno))
    if not acquires:
        return out
    finally_lines = _in_finally_lines(fn)
    for recv, line in acquires:
        same = [ln for r, ln in releases if r == recv]
        if not same:
            out.append(Violation(
                "pair-acquire", sf.path, line, f"{qn}:{recv}",
                f"{recv}.acquire() has no matching {recv}.release() in "
                f"this function — use `with {recv}:` or pair it"))
        elif not any(ln in finally_lines for ln in same):
            out.append(Violation(
                "pair-acquire", sf.path, line, f"{qn}:{recv}",
                f"{recv}.release() is not on the exception path (no "
                f"finally) — an exception after acquire leaves "
                f"{recv} held forever; use `with` or try/finally"))
    return out


# -- pair-draft --------------------------------------------------------------

def _check_draft_pages(sf: SourceFile, qn: str,
                       fn: ast.AST) -> List[Violation]:
    """A caller of acquire_draft_pages holds provisional page refs
    for a draft that may be rejected; without a rollback_draft_pages
    (or a whole-slot release_pages) in the same function there is no
    path that gives the rejected rows' pages back."""
    out: List[Violation] = []
    acquire_line = None
    has_rollback = False
    for n in _own_nodes(fn):
        if not isinstance(n, ast.Call):
            continue
        name = (_func_name(n) or "").lstrip("_")
        if name == "acquire_draft_pages":
            acquire_line = acquire_line or n.lineno
        elif name in ("rollback_draft_pages", "release_pages"):
            has_rollback = True
    if acquire_line is not None and not has_rollback \
            and getattr(fn, "name", "").lstrip("_") != "acquire_draft_pages":
        # the acquire helper itself rolls back internally on the
        # exhaustion path; every OTHER caller owes an explicit pair
        out.append(Violation(
            "pair-draft", sf.path, acquire_line, f"{qn}:draft-pages",
            "acquire_draft_pages() without rollback_draft_pages() "
            "or release_pages() in this function — rejected-draft "
            "pages have no give-back path and leak refcounts"))
    return out


# -- pair-refcount -----------------------------------------------------------

def _check_refcounts_fn(sf: SourceFile, qn: str,
                        fn: ast.AST) -> List[Violation]:
    out: List[Violation] = []
    has_decref = any(isinstance(n, ast.Call)
                     and isinstance(n.func, ast.Attribute)
                     and n.func.attr in ("decref", "free")
                     for n in _own_nodes(fn))
    for n in _own_nodes(fn):
        # discarded alloc() on a pool-ish receiver
        if isinstance(n, ast.Expr) and isinstance(n.value, ast.Call) \
                and isinstance(n.value.func, ast.Attribute) \
                and n.value.func.attr == "alloc" \
                and _POOLISH_RE.search(_recv_repr(n.value.func.value)):
            out.append(Violation(
                "pair-refcount", sf.path, n.lineno, f"{qn}:alloc-discard",
                "pool.alloc() result discarded — the page's refcount "
                "is 1 with no holder; it leaks"))
        # p = pool.alloc() where p never escapes and no decref here
        if isinstance(n, ast.Assign) and isinstance(n.value, ast.Call) \
                and isinstance(n.value.func, ast.Attribute) \
                and n.value.func.attr == "alloc" \
                and _POOLISH_RE.search(_recv_repr(n.value.func.value)):
            t = n.targets[0]
            if isinstance(t, ast.Name) and not has_decref and \
                    not _name_escapes(fn, t.id, n.lineno,
                                      skip_call_attrs=("alloc",)):
                out.append(Violation(
                    "pair-refcount", sf.path, n.lineno,
                    f"{qn}:{t.id}",
                    f"page handle {t.id!r} from alloc() neither "
                    f"escapes nor is decref'd in this function — "
                    f"leaks on every path"))
        # incref(name) with no decref and no ownership transfer
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute) \
                and n.func.attr == "incref" and n.args:
            arg = n.args[0]
            if isinstance(arg, ast.Name) and not has_decref and \
                    not _name_escapes(fn, arg.id, n.lineno,
                                      skip_call_attrs=("incref",)):
                out.append(Violation(
                    "pair-refcount", sf.path, n.lineno,
                    f"{qn}:{arg.id}",
                    f"incref({arg.id}) without a decref or visible "
                    f"ownership transfer of {arg.id!r} in this "
                    f"function — the references leak"))
    return out


def _check_refcounts_class(sf: SourceFile) -> List[Violation]:
    """A class that takes references must be able to give them back."""
    out: List[Violation] = []
    if sf.tree is None:
        return out
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.ClassDef):
            continue
        takes = gives = None
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call) and \
                    isinstance(sub.func, ast.Attribute):
                if sub.func.attr in ("incref", "alloc") and \
                        _poolish_call(sub):
                    takes = takes or sub.lineno
                if sub.func.attr in ("decref", "free"):
                    gives = gives or sub.lineno
        if takes and not gives:
            out.append(Violation(
                "pair-refcount", sf.path, takes,
                f"{node.name}:class-balance",
                f"class {node.name} increfs/allocs pool pages but "
                f"never decrefs anywhere — references can only leak"))
    return out


def _poolish_call(call: ast.Call) -> bool:
    if call.func.attr == "incref":
        return True
    return bool(_POOLISH_RE.search(_recv_repr(call.func.value)))
