"""Crash-site coverage lint (PR 9): the sources must classify clean
(every TS mutation site carries a provable crash-recovery protection),
every seeded fixture must fail with exactly its one finding kind, site
IDs must be stable unique addresses, and the README crash-site table
must be current.
"""

import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools.crash_lint import (CLASSES, DOC_END, DOC_START,  # noqa: E402
                              _splice_doc, doc_table, main, scan_paths,
                              site_registry)

FIXTURES = REPO / "tools" / "crash_lint_fixtures"

#: fixture file -> the single finding kind it seeds
EXPECTED = {
    "fx_fence_after_write.py": "fence-after-write",
    "fx_unclassified_site.py": "unclassified-site",
    "fx_unprotected_site.py": "unprotected-site",
}

#: PR 9 site-count floor: the registry shrinking silently would mean the
#: lint stopped seeing mutation sites, not that the code got safer.
SITE_FLOOR = 70


def test_sources_classify_clean():
    sites, findings = scan_paths([REPO / "src" / "repro"])
    assert findings == [], "\n".join(str(f) for f in findings)
    assert len(sites) >= SITE_FLOOR


def test_every_protection_class_is_used():
    used = {s.protection for s in site_registry()}
    assert set(CLASSES) <= used, used


def test_every_fixture_flagged_with_expected_kind():
    _, findings = scan_paths([FIXTURES])
    by_file = {}
    for f in findings:
        by_file.setdefault(Path(f.path).name, []).append(f)
    assert set(by_file) == set(EXPECTED)
    for name, kind in EXPECTED.items():
        kinds = [f.kind for f in by_file[name]]
        assert kinds == [kind], f"{name}: {kinds}"


def test_site_ids_are_unique_stable_addresses():
    sites = site_registry()
    ids = [s.site_id for s in sites]
    assert len(ids) == len(set(ids)), "duplicate site IDs"
    for s in sites:
        assert s.site_id.startswith(f"{s.role}:")
        assert f":{s.method}[" in s.site_id
        assert 1 <= s.line <= s.end_line
        assert s.path.startswith("src/repro/")


def test_fixed_sites_pinned():
    """Regression pins for the crash windows PR 9 closed: the
    capability-miss store re-put is compensated, the commit path re-puts
    without a preceding delete (no absence window), and the executor's
    effect batch is declared fenced by its caller."""
    sites = {s.site_id: s for s in site_registry()}
    assert sites["handler:handler.Handler._run_event:put[?]#2"
                 ].protection == "compensated"
    assert sites["manager:mlp.MLPProgram._commit_update:put[w]#0"
                 ].protection == "checkpoint-ordered"
    assert not any(
        sid.startswith("manager:mlp.MLPProgram._commit_update:delete[w]")
        or sid.startswith("manager:mlp.MLPProgram._commit_update:delete[b]#")
        or sid.startswith("manager:mlp.MLPProgram._commit_update:delete[wver]")
        for sid in sites), "commit path grew a delete+put absence window back"
    assert sites["executor:executor.TaskExecutor._run_group:put_many[?]#0"
                 ].protection == "frontier-fenced"


def test_handler_store_reputs_all_compensated_or_fenced():
    """Every handler-side put must be compensated (store re-puts) or
    frontier-fenced (result/done writes) — the satellite-3 invariant,
    statically."""
    puts = [s for s in site_registry()
            if s.path == "src/repro/core/handler.py"
            and s.op == "put"]
    assert len(puts) >= 6
    for s in puts:
        assert s.protection in ("compensated", "frontier-fenced"), s


def test_cli_exit_codes():
    assert main([str(REPO / "src" / "repro")]) == 0
    assert main([str(FIXTURES)]) == 1


def test_doc_table_row_per_site():
    table = doc_table()
    # header + separator + one row per site
    assert table.count("\n") + 1 == len(site_registry()) + 2
    for cls in CLASSES:
        assert cls in table


def test_readme_table_is_current():
    readme = REPO / "README.md"
    text = readme.read_text()
    assert DOC_START in text and DOC_END in text
    assert _splice_doc(text) == text, (
        "README crash-site table is stale — regenerate with "
        "`python -m tools.crash_lint --write-doc README.md`")


def test_shared_resolver_keeps_ts_lint_site_counts():
    """Satellite 1: moving the AST resolver to tools._astlib must not
    lose call sites — the ts_lint resolution stats keep their floor."""
    from tools.ts_lint import resolution_stats
    st = resolution_stats([REPO / "src" / "repro"])
    assert st["sites"] >= 154
    assert st["resolved"] >= 107
