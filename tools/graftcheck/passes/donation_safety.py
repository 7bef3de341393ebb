"""Donation-safety checker for buffer-aliasing ops.

The decode op whose output aliases an input variable
(``kv_pool_write`` — the mutated-persistable contract in
``ops/decode_ops.py``) makes the
executor *donate* the input buffer to XLA: after the call, the
Python-side variable the caller passed in refers to a buffer XLA has
already overwritten (or freed).  The only safe patterns are

* rebinding in the same statement::

      pool_k = layers.kv_pool_write(pool_k, k, positions, table, lens)

* never touching the donated name again.

Rule ``donation-use-after-alias`` flags any *later read* of the
donated first argument in the same function (statement order by line
— an approximation of control flow, which is exactly right for the
straight-line graph-builder code these ops live in).  A re-assignment
of the name re-arms it.

The same rule also tracks *donating callables*: a name or attribute
assigned from ``jax.jit(..., donate_argnums=(...))`` — the verify
program's aliased pool arg and the segment-adoption scatter
(``self._adopt_scatter``) live behind exactly this pattern — donates
the listed positional arguments at every later call through it, with
the same rebind-or-never-read contract::

    self._adopt_scatter = jax.jit(lambda pool, i, r: ...,
                                  donate_argnums=(0,))
    pool = self._adopt_scatter(pool, idx, rows)   # ok: rebound
    self._adopt_scatter(pool, idx, rows)          # pool is now dead

Index harvesting is conservative: every int constant inside the
``donate_argnums`` expression counts (so ``(1,) if flag else ()``
tracks index 1 — MAY-donate is the safe reading).
"""
from __future__ import annotations

import ast
from typing import Dict, List, Set

from ..core import SourceFile, Violation, call_name, register_pass
from .resource_pairing import _functions, _own_nodes, _recv_repr

# op name -> index of the donated positional argument / keyword name
ALIAS_OPS: Dict[str, tuple] = {
    "kv_pool_write": (0, "pool"),
}


_op_name = call_name


def _jit_donated_indices(call: ast.Call) -> Set[int]:
    """For a ``jax.jit(...)`` / ``jit(...)`` call, the positional
    indices its ``donate_argnums`` may donate (empty when absent).
    Conservative: harvests every non-negative int constant in the
    keyword's expression, so conditional specs still track."""
    if _op_name(call) != "jit":
        return set()
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            return {sub.value for sub in ast.walk(kw.value)
                    if isinstance(sub, ast.Constant)
                    and isinstance(sub.value, int)
                    and not isinstance(sub.value, bool)
                    and sub.value >= 0}
    return set()


def _donating_callables(sf: SourceFile) -> Dict[str, Set[int]]:
    """File-level map of canonical assignment target ('fn',
    'self._adopt_scatter', ...) -> donated positional indices, for
    every target assigned a jit-with-donation callable anywhere in
    the file (the build site and the call sites are often different
    methods of the same class)."""
    donors: Dict[str, Set[int]] = {}
    for n in ast.walk(sf.tree):
        if not isinstance(n, (ast.Assign, ast.AnnAssign)):
            continue
        value = n.value
        if not isinstance(value, ast.Call):
            continue
        idxs = _jit_donated_indices(value)
        if not idxs:
            continue
        targets = n.targets if isinstance(n, ast.Assign) else [n.target]
        for t in targets:
            if isinstance(t, (ast.Name, ast.Attribute)):
                donors.setdefault(_recv_repr(t), set()).update(idxs)
    return donors


@register_pass(
    "donation-safety", ("donation-use-after-alias",),
    doc="a variable donated to an output-aliasing op (kv_pool_write) "
        "or through a jax.jit(donate_argnums=...) callable "
        "must be rebound or never read again")
def run(files: List[SourceFile]) -> List[Violation]:
    out: List[Violation] = []
    for sf in files:
        if sf.tree is None:
            continue
        if not (any(op in sf.text for op in ALIAS_OPS)
                or "donate_argnums" in sf.text):
            continue  # cheap prefilter: few files touch aliasing ops
        donors = _donating_callables(sf)
        for qn, fn in _functions(sf):
            out += _check_fn(sf, qn, fn, donors)
    return out


def _check_fn(sf: SourceFile, qn: str, fn: ast.AST,
              donors: Dict[str, Set[int]] = {}) -> List[Violation]:
    out: List[Violation] = []
    # every Store to each name, by line (rebinding re-arms the name)
    stores: Dict[str, List[int]] = {}
    loads: Dict[str, List[int]] = {}
    donations: List[tuple] = []  # (name, call_line, op, rebound_same_stmt)

    assigns = [n for n in _own_nodes(fn)
               if isinstance(n, (ast.Assign, ast.AnnAssign))]

    def _target_names(a) -> set:
        """Every Name bound by an assignment, through tuple/starred
        nesting (`cache_k, cache_v = ...` rebinds both)."""
        targets = a.targets if isinstance(a, ast.Assign) else [a.target]
        names = set()
        for t in targets:
            for sub in ast.walk(t):
                if isinstance(sub, ast.Name):
                    names.add(sub.id)
        return names

    for n in _own_nodes(fn):
        if isinstance(n, ast.Name):
            book = stores if isinstance(n.ctx, (ast.Store, ast.Del)) \
                else loads
            book.setdefault(n.id, []).append(n.lineno)
        if isinstance(n, ast.Call):
            # (donated arg node, label) pairs this call consumes:
            # aliasing-op first args plus every donate_argnums index
            # of a tracked jit callable
            consumed = []
            op = _op_name(n)
            if op in ALIAS_OPS:
                idx, kw_name = ALIAS_OPS[op]
                donated = None
                if len(n.args) > idx:
                    donated = n.args[idx]
                else:
                    for kw in n.keywords:
                        if kw.arg == kw_name:
                            donated = kw.value
                consumed.append((donated, op))
            elif isinstance(n.func, (ast.Name, ast.Attribute)):
                callee = _recv_repr(n.func)
                for idx in sorted(donors.get(callee, ())):
                    if len(n.args) > idx:
                        consumed.append((n.args[idx], callee))
            for donated, label in consumed:
                if not isinstance(donated, ast.Name):
                    continue
                rebound = any(
                    (a.value is not None
                     and (a.value is n or _contains(a.value, n)))
                    and donated.id in _target_names(a)
                    for a in assigns)
                # the call's END line: a multi-line call's own
                # argument loads must not read as use-after-donation
                call_end = getattr(n, "end_lineno", None) or n.lineno
                donations.append((donated.id, call_end, label, rebound))

    for name, call_line, op, rebound in donations:
        if rebound:
            continue
        # a Store strictly after the call re-arms the name; any Load
        # after the call and before the next Store is use-after-alias
        next_store = min((ln for ln in stores.get(name, [])
                          if ln > call_line), default=None)
        for use in sorted(loads.get(name, [])):
            if use <= call_line:
                continue
            if next_store is not None and use >= next_store:
                break
            out.append(Violation(
                "donation-use-after-alias", sf.path, use,
                f"{qn}:{name}",
                f"{name!r} was donated to {op}() at line {call_line}; "
                f"its buffer is aliased/dead — rebind "
                f"(`{name} = {op}({name}, ...)`) or use the op's "
                f"output variable"))
            break  # one finding per donation is enough signal
    return out


def _contains(root: ast.AST, target: ast.AST) -> bool:
    return any(sub is target for sub in ast.walk(root))
