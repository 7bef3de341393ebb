"""Flag hygiene: the FLAGS_* registry stays live and documented.

Three rules over the ``register_flag`` registry
(``paddle_tpu/flags.py``) and every flag-API call site:

``flag-undefined``
    A literal flag name passed to ``flag_value`` / ``get_flags`` /
    ``set_flags`` (dict keys) that no ``register_flag`` defines — the
    typo catch: the registry raises at runtime, but only on the code
    path that actually executes.

``flag-unused``
    A registered flag that no code anywhere (paddle_tpu/, tools/,
    tests/, __graft_entry__.py) ever reads through the flag
    APIs — dead configuration surface an operator can set with no
    effect.  Reference-API-compat flags that are intentionally
    advisory carry baseline waivers.

``flag-undocumented``
    A registered flag whose backtick-quoted name does not appear in
    README.md — a knob that cannot be operated.  This subsumes the
    per-prefix serving/router/fleet README lints.
"""
from __future__ import annotations

import ast
import os
from typing import Dict, List, Set, Tuple

from ..core import (REPO, SourceFile, Violation, call_name,
                    register_pass, walk_files)

# extra roots consulted for read evidence (a flag only tests read is
# still read; violations are only ever attached to the registry file)
READ_EVIDENCE_ROOTS = ("tests", "__graft_entry__.py")
FLAG_READ_FUNCS = {"flag_value", "get_flags"}
# module-level so tests can point the pass at a fixture README
README_PATH = os.path.join(REPO, "README.md")
# read-evidence scans are pure functions of the evidence roots — cache
# per process so repeated core.run() calls (the test suite runs
# several) don't re-read+re-parse the ~100-file tests/ tree each time
_EVIDENCE_CACHE: dict = {}


def _literal_str(node: ast.AST):
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


_fn_name = call_name


def scan_file(sf: SourceFile):
    """(defs, reads) from one file: defs = {name: line} from
    register_flag; reads = [(name, line)] from flag_value/get_flags/
    set_flags literal usage."""
    defs: Dict[str, int] = {}
    reads: List[Tuple[str, int]] = []
    if sf.tree is None:
        return defs, reads
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.Call):
            continue
        fn = _fn_name(node)
        if fn == "register_flag" and node.args:
            name = _literal_str(node.args[0])
            if name is not None:
                defs.setdefault(name, node.lineno)
        elif fn in FLAG_READ_FUNCS and node.args:
            name = _literal_str(node.args[0])
            if name is not None:
                reads.append((name, node.lineno))
            elif isinstance(node.args[0], (ast.List, ast.Tuple)):
                for e in node.args[0].elts:
                    nm = _literal_str(e)
                    if nm is not None:
                        reads.append((nm, e.lineno))
        elif fn == "set_flags" and node.args \
                and isinstance(node.args[0], ast.Dict):
            for k in node.args[0].keys:
                nm = _literal_str(k)
                if nm is not None and nm.startswith("FLAGS_"):
                    reads.append((nm, k.lineno))
    return defs, reads


@register_pass(
    "flag-hygiene", ("flag-undefined", "flag-unused",
                     "flag-undocumented"),
    doc="every FLAGS_* defined is read and README-documented; every "
        "FLAGS_* read is defined (typo catch)")
def run(files: List[SourceFile]) -> List[Violation]:
    out: List[Violation] = []
    defs: Dict[str, Tuple[str, int]] = {}   # name -> (path, line)
    reads: List[Tuple[str, str, int]] = []  # (name, path, line)

    scanned_paths = {sf.path for sf in files}
    for sf in files:
        d, r = scan_file(sf)
        for name, line in d.items():
            defs.setdefault(name, (sf.path, line))
        reads += [(n, sf.path, ln) for n, ln in r]

    # the registry file is ALWAYS consulted for definitions, even when
    # the scan roots exclude it — otherwise a subset-root run
    # (`graftcheck paddle_tpu/serving`) reports every real flag read
    # as flag-undefined (violations still attach only to scanned files)
    registry = os.path.join(REPO, "paddle_tpu", "flags.py")
    reg_rel = "paddle_tpu/flags.py"
    if reg_rel not in scanned_paths and os.path.exists(registry):
        sf = SourceFile(registry, reg_rel)
        d, r = scan_file(sf)
        for name, line in d.items():
            defs.setdefault(name, (sf.path, line))
        reads += [(n, sf.path, ln) for n, ln in r]

    # read evidence from tests/bench without attaching violations
    # there (absolute paths: the cwd-first root resolution must not
    # pick up some other project's tests/ directory)
    extra_roots = tuple(
        os.path.join(REPO, r) for r in READ_EVIDENCE_ROOTS
        if os.path.exists(os.path.join(REPO, r)))
    evidence = _EVIDENCE_CACHE.get(extra_roots)
    if evidence is None:
        evidence = []
        for sf in walk_files(extra_roots, repo=REPO):
            d, r = scan_file(sf)
            evidence.append((sf.path, d, r))
        _EVIDENCE_CACHE[extra_roots] = evidence
    for path, d, r in evidence:
        if path in scanned_paths:
            continue
        for name, line in d.items():
            defs.setdefault(name, (path, line))
        reads += [(n, path, ln) for n, ln in r]

    read_names: Set[str] = {n for n, _, _ in reads}

    # flag-undefined: a read of a name the registry never defines,
    # reported only in the scanned tree (tests mint fake flags freely)
    for name, path, line in sorted(set(reads)):
        if name.startswith("FLAGS_") and name not in defs \
                and path in scanned_paths:
            out.append(Violation(
                "flag-undefined", path, line, name,
                f"{name} is not registered in paddle_tpu/flags.py — "
                f"typo, or a flag that was removed"))

    # flag-unused / flag-undocumented, attached to the registration
    readme_path = README_PATH
    documented: Set[str] = set()
    if os.path.exists(readme_path):
        with open(readme_path, encoding="utf-8") as f:
            text = f.read()
        documented = {m for m in _backticked(text)}
    for name, (path, line) in sorted(defs.items()):
        if path not in scanned_paths:
            continue
        if name not in read_names:
            out.append(Violation(
                "flag-unused", path, line, name,
                f"{name} is registered but never read through "
                f"flag_value/get_flags anywhere (paddle_tpu, tools, "
                f"tests, bench) — dead knob; remove it or wire it up"))
        if name not in documented:
            out.append(Violation(
                "flag-undocumented", path, line, name,
                f"{name} is not documented (backtick-quoted) in "
                f"README.md — a knob that cannot be operated"))
    return out


def _backticked(text: str):
    import re
    return re.findall(r"`(FLAGS_[A-Za-z0-9_]+)`", text)
