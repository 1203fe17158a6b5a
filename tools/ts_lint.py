"""Static tuple-space protocol lint (PR 6).

Walks Python sources with ``ast``, extracts every *literal* key/pattern
handed to a tuple-space operation (``put``/``put_many``/``read``/
``try_read``/``get``/``try_get``/``take_batch``/``wait_count``/
``count``/``keys``/``delete`` on a receiver named ``ts``/``space``/
``_ts``/``root``), and resolves it against the declared
:class:`~repro.core.space.schema.KeySchema` registry — the same source
of truth the runtime :class:`~repro.core.space.checked.CheckedBackend`
sanitizer enforces. Reported findings:

- **unknown-subject** — a fixed subject no schema in the file's scope
  declares;
- **arity-mismatch** — a literal key/pattern whose length disagrees with
  the schema;
- **wildcard-in-put** — ``ANY`` or a lambda inside a ``put`` key (keys
  must be concrete);
- **bad-literal-type** — a literal field constant outside the schema's
  declared types;
- **role-violation** — a put/read/take/delete from a file (or function)
  whose attributed role is not among the schema's declared
  producers/consumers/deleters;
- **widened-delete** — a delete whose *subject* is a wildcard/predicate
  (the PR 4 cross-tenant corruption class; runtime namespace scoping
  confines it, but no first-party call site should need one).

Role attribution mirrors the runtime tags: a file map (manager.py →
manager, handler.py → handler, …), a per-function override — any
function whose first parameter (after ``self``) is named ``ctx`` is an
op kernel and runs as ``executor`` — and an explicit module-level
``TS_LINT_ROLE = "<role>"`` assignment. Files with no attributed role
skip role checks, exactly like untagged threads at runtime.

Non-literal keys (variables, helper calls) are skipped — the runtime
sanitizer covers those. A ``("done",) + content_key(t)`` concatenation
is resolved by subject only.

Usage::

    python -m tools.ts_lint [paths...]        # default: src/repro
    python -m tools.ts_lint --doc-table       # print the key table
    python -m tools.ts_lint --write-doc README.md
    python -m tools.ts_lint --check-doc README.md

Exit status: 0 clean, 1 findings (or doc drift), 2 usage error.
"""

from __future__ import annotations

import argparse
import ast
import sys
from dataclasses import dataclass
from pathlib import Path

_REPO = Path(__file__).resolve().parent.parent
if str(_REPO / "src") not in sys.path:
    sys.path.insert(0, str(_REPO / "src"))

from repro.core.space.schema import CONTROL_SCHEMAS, KeySchema  # noqa: E402

if str(_REPO) not in sys.path:
    sys.path.insert(0, str(_REPO))

# The AST resolver moved to tools._astlib (PR 9) — shared with dag_lint
# and crash_lint; re-exported here for backward compatibility.
from tools._astlib import (OPS, RECEIVERS, ROLE_BY_FILE,  # noqa: E402,F401
                           _field_value, _fold, _is_wild_node, _key_expr,
                           _module_consts, _module_role, _resolve_key,
                           _Unknown, _Wild)


def _program_schemas() -> dict[str, tuple[KeySchema, ...]]:
    """Each built-in program's declared data-plane schemas, keyed by the
    module basename the scope map matches on."""
    from repro.programs import jax_sgd, mlp, moe
    return {
        "mlp": tuple(mlp.KEY_SCHEMAS),
        "moe": tuple(moe.KEY_SCHEMAS),
        "jax_sgd": tuple(jax_sgd.KEY_SCHEMAS),
    }


def _scope_for(path: str, progs: dict[str, tuple[KeySchema, ...]]
               ) -> dict[str, KeySchema]:
    """subject -> schema visible from this file. Program modules see the
    control plane plus their own data plane; core sees the control plane;
    anything else sees the union (lenient — cross-module helpers)."""
    p = path.replace("\\", "/")
    table: dict[str, KeySchema] = {s.subject: s for s in CONTROL_SCHEMAS}
    if "/core/" in p or p.endswith("core/__init__.py"):
        return table
    for name, schemas in progs.items():
        if p.endswith(f"programs/{name}.py"):
            table.update({s.subject: s for s in schemas})
            return table
    for schemas in progs.values():
        table.update({s.subject: s for s in schemas})
    return table


@dataclass(frozen=True)
class Finding:
    path: str
    line: int
    kind: str
    op: str
    key: str
    detail: str

    def __str__(self) -> str:
        return (f"{self.path}:{self.line}: [{self.kind}] {self.op} "
                f"{self.key}: {self.detail}")


class _Linter(ast.NodeVisitor):
    def __init__(self, path: str, scope: dict[str, KeySchema],
                 file_role: str | None,
                 env: dict[str, object] | None = None) -> None:
        self.path = path
        self.scope = scope
        self.env = env or {}
        self.findings: list[Finding] = []
        self.sites = 0           # TS-op call sites with a key expression
        self.resolved = 0        # ... whose subject folded to a fixed str
        self._role_stack: list[str | None] = [file_role]

    # ------------------------------------------------------------ roles
    def _function_role(self, node) -> str | None:
        args = node.args.posonlyargs + node.args.args
        names = [a.arg for a in args]
        if names and names[0] == "self":
            names = names[1:]
        if names and names[0] == "ctx":
            return "executor"          # op kernel: runs on handler threads
        return self._role_stack[-1]

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._role_stack.append(self._function_role(node))
        self.generic_visit(node)
        self._role_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    # ------------------------------------------------------------ calls
    def _emit(self, node: ast.Call, kind: str, op: str, key: str,
              detail: str) -> None:
        self.findings.append(Finding(self.path, node.lineno, kind, op,
                                     key, detail))

    def visit_Call(self, node: ast.Call) -> None:
        self.generic_visit(node)
        fn = node.func
        if not isinstance(fn, ast.Attribute) or fn.attr not in OPS:
            return
        recv = fn.value
        recv_name = (recv.id if isinstance(recv, ast.Name)
                     else recv.attr if isinstance(recv, ast.Attribute)
                     else None)
        if recv_name not in RECEIVERS:
            return
        op = OPS[fn.attr]
        key_node = _key_expr(node, fn.attr)
        if key_node is None:
            return
        subject, fields = _resolve_key(key_node, self.env)
        self.sites += 1
        if isinstance(subject, str):
            self.resolved += 1
        key_repr = ast.unparse(key_node)
        role = self._role_stack[-1]
        if subject is _Wild:
            if op == "delete":
                self._emit(node, "widened-delete", op, key_repr,
                           "subject-widened delete can cross subjects/"
                           "namespaces — confine it to a fixed subject")
            return                     # wild-subject reads are structural
        if subject is None:
            return                     # not statically resolvable
        schema = self.scope.get(subject)
        if schema is None:
            self._emit(node, "unknown-subject", op, key_repr,
                       f"subject {subject!r} has no declared KeySchema "
                       f"in this file's scope")
            return
        if fields is not None and 1 + len(fields) != schema.arity:
            self._emit(node, "arity-mismatch", op, key_repr,
                       f"{subject!r} expects arity {schema.arity}, "
                       f"got {1 + len(fields)}")
            return
        if op == "put" and fields is not None:
            for fs, val in zip(schema.fields, fields):
                if val is _Wild:
                    self._emit(node, "wildcard-in-put", op, key_repr,
                               f"field {fs.name!r} of {subject!r} is a "
                               f"wildcard/predicate — keys must be "
                               f"concrete")
                elif (val is not _Unknown and fs.types is not None
                        and not isinstance(val, fs.types)):
                    self._emit(node, "bad-literal-type", op, key_repr,
                               f"field {fs.name!r} of {subject!r} expects "
                               f"{'/'.join(t.__name__ for t in fs.types)},"
                               f" got {type(val).__name__}")
        if role is None:
            return
        allowed = {"put": schema.producers, "read": schema.consumers,
                   "take": schema.consumers, "delete": schema.deleters}[op]
        if role not in allowed:
            self._emit(node, "role-violation", op, key_repr,
                       f"{role} may not {op} {subject!r} "
                       f"(declared: {sorted(allowed)})")


def lint_file(path: Path,
              progs: dict[str, tuple[KeySchema, ...]]) -> list[Finding]:
    rel = str(path)
    try:
        tree = ast.parse(path.read_text(), filename=rel)
    except SyntaxError as exc:            # pragma: no cover - defensive
        return [Finding(rel, exc.lineno or 0, "syntax-error", "-", "-",
                        str(exc))]
    linter = _Linter(rel, _scope_for(rel, progs), _module_role(tree, rel),
                     _module_consts(tree))
    linter.visit(tree)
    return linter.findings


def lint_paths(paths: list[Path]) -> list[Finding]:
    progs = _program_schemas()
    findings: list[Finding] = []
    for root in paths:
        files = sorted(root.rglob("*.py")) if root.is_dir() else [root]
        for f in files:
            findings.extend(lint_file(f, progs))
    return findings


def resolution_stats(paths: list[Path], fold: bool = True) -> dict[str, int]:
    """How many TS-op call sites the linter sees, and how many of their
    subjects resolve to a fixed string. Constant folding (PR 8) must only
    ever *increase* ``resolved`` — asserted by the tests via
    ``resolution_stats(..., fold=False)``."""
    progs = _program_schemas()
    sites = resolved = 0
    for root in paths:
        files = sorted(root.rglob("*.py")) if root.is_dir() else [root]
        for f in files:
            try:
                tree = ast.parse(f.read_text(), filename=str(f))
            except SyntaxError:           # pragma: no cover - defensive
                continue
            env = _module_consts(tree) if fold else {}
            linter = _Linter(str(f), _scope_for(str(f), progs),
                             _module_role(tree, str(f)), env)
            linter.visit(tree)
            sites += linter.sites
            resolved += linter.resolved
    return {"sites": sites, "resolved": resolved}


# --------------------------------------------------------------- doc table
DOC_START = "<!-- ts-schema-table:start -->"
DOC_END = "<!-- ts-schema-table:end -->"


def doc_table() -> str:
    """The executor key table, generated from the registry (single source
    of truth — README drift is a CI failure)."""
    progs = _program_schemas()
    lines = [
        "| scope | key shape | lifecycle | producers | consumers | "
        "description |",
        "|---|---|---|---|---|---|",
    ]

    def fmt(scope: str, s: KeySchema) -> str:
        return (f"| {scope} | `{s.key_shape}` | {s.lifecycle} "
                f"| {', '.join(sorted(s.producers))} "
                f"| {', '.join(sorted(s.consumers))} "
                f"| {s.description} |")

    for s in CONTROL_SCHEMAS:
        lines.append(fmt("control", s))
    for name in sorted(progs):
        for s in progs[name]:
            lines.append(fmt(name, s))
    return "\n".join(lines)


def _splice_doc(text: str) -> str:
    start = text.find(DOC_START)
    end = text.find(DOC_END)
    if start < 0 or end < 0 or end < start:
        raise SystemExit(
            f"doc file lacks the {DOC_START!r} / {DOC_END!r} markers")
    head = text[: start + len(DOC_START)]
    tail = text[end:]
    return f"{head}\n{doc_table()}\n{tail}"


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.ts_lint",
        description="Static tuple-space protocol lint over the declared "
                    "KeySchema registry.")
    ap.add_argument("paths", nargs="*", default=None,
                    help="files/directories to lint (default: src/repro)")
    ap.add_argument("--doc-table", action="store_true",
                    help="print the generated key table and exit")
    ap.add_argument("--write-doc", metavar="FILE",
                    help="splice the key table between the doc markers")
    ap.add_argument("--check-doc", metavar="FILE",
                    help="fail (exit 1) if FILE's spliced table is stale")
    args = ap.parse_args(argv)

    if args.doc_table:
        print(doc_table())
        return 0
    if args.write_doc:
        p = Path(args.write_doc)
        p.write_text(_splice_doc(p.read_text()))
        print(f"wrote key table to {p}")
        return 0
    if args.check_doc:
        p = Path(args.check_doc)
        text = p.read_text()
        if _splice_doc(text) != text:
            print(f"{p}: key table is stale — regenerate with "
                  f"`python -m tools.ts_lint --write-doc {p}`")
            return 1
        print(f"{p}: key table up to date")
        return 0

    paths = [Path(p) for p in (args.paths or [_REPO / "src" / "repro"])]
    missing = [p for p in paths if not p.exists()]
    if missing:
        print(f"no such path(s): {missing}", file=sys.stderr)
        return 2
    findings = lint_paths(paths)
    for f in findings:
        print(f)
    n_files = sum(len(sorted(p.rglob('*.py'))) if p.is_dir() else 1
                  for p in paths)
    print(f"ts-lint: {len(findings)} finding(s) across {n_files} file(s)")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
