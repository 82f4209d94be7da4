"""Doc-sync tests: the documentation layer must track the code it describes.

Three contracts, one per document:

* README.md's backend capability table matches ``BACKEND_REGISTRY``
  cell-by-cell — every registered backend has a row, and the row's
  exact/tolerance and yes/no cells agree with the registry flags;
* every ``solve_*`` entry point named in docs/architecture.md is a real
  attribute of ``repro.core`` (docs never name a function that does not
  exist);
* every top-level row-list section of BENCH_pivot_work.json has a matching
  ``### `section` `` heading in benchmarks/README.md, and vice versa.

These run in the tier-1 suite and in the CI ``docs`` leg, so a PR that
adds a backend, renames an entry point, or adds a benchmark section fails
until the docs move with it.
"""
import json
import re
from pathlib import Path

import pytest

from repro.core.lp import BACKEND_REGISTRY

REPO = Path(__file__).resolve().parent.parent
README = REPO / "README.md"
ARCHITECTURE = REPO / "docs" / "architecture.md"
BENCH_README = REPO / "benchmarks" / "README.md"
BENCH_JSON = REPO / "BENCH_pivot_work.json"


def _readme_backend_rows():
    """Parse README's capability table into {backend: [cell, ...]}."""
    rows = {}
    for line in README.read_text().splitlines():
        m = re.match(r"\|\s*`(\w+)`\s*\|(.*)\|\s*$", line)
        if m and m.group(1) in BACKEND_REGISTRY:
            cells = [c.strip().lower() for c in m.group(2).split("|")]
            rows[m.group(1)] = cells
    return rows


def test_readme_exists_with_required_sections():
    text = README.read_text()
    for needle in ("## Solver backends", "## Quickstart",
                   "python -m pytest -x -q", "scripts/check.sh",
                   "BENCH_pivot_work.json"):
        assert needle in text, f"README.md lost required content: {needle!r}"


def test_readme_backend_table_matches_registry():
    rows = _readme_backend_rows()
    missing = set(BACKEND_REGISTRY) - set(rows)
    assert not missing, \
        f"backends registered but absent from README table: {sorted(missing)}"
    for name, spec in BACKEND_REGISTRY.items():
        cells = rows[name]
        # column order: solutions, pallas, compaction, sparse, safe bound
        assert len(cells) == 5, \
            f"README row for {name} has {len(cells)} cells, expected 5"
        solutions, pallas, compaction, sparse, safe = cells
        assert solutions == ("exact" if spec.exact else "tolerance"), \
            f"README says {name} is {solutions!r}; registry exact={spec.exact}"
        for label, cell, flag in (
                ("Pallas", pallas, spec.supports_pallas),
                ("compaction", compaction, spec.supports_compaction),
                ("sparse", sparse, spec.supports_sparse),
                ("safe bound", safe, spec.supports_safe_bound)):
            assert cell == ("yes" if flag else "no"), \
                f"README {label} cell for {name} is {cell!r}; " \
                f"registry says {flag}"


def test_architecture_entry_points_exist():
    import repro.core as core
    names = sorted(set(re.findall(r"\bsolve_\w+", ARCHITECTURE.read_text())))
    assert names, "docs/architecture.md names no solve_* entry points"
    ghosts = [n for n in names if not hasattr(core, n)]
    assert not ghosts, \
        f"docs/architecture.md names entry points missing from " \
        f"repro.core: {ghosts}"


def test_architecture_registry_solvers_are_documented():
    # the per-backend table in architecture.md must name the registry's
    # actual solve targets (the attr half of each "module:attr" spec)
    text = ARCHITECTURE.read_text()
    for name, spec in BACKEND_REGISTRY.items():
        for field in ("solve", "solve_compacted", "solve_sparse"):
            target = getattr(spec, field)
            if not target:
                continue
            attr = target.split(":")[1]
            assert attr in text, \
                f"registry {name}.{field} -> {attr} not named in " \
                f"docs/architecture.md"


def test_architecture_mentions_interpret_only_kernel_status():
    # the Pallas kernel status section must describe the platform-derived
    # interpret rule, name the compile and chip checks that exist, and
    # list every kernel module in kernels/
    import repro.kernels.ops as ops
    text = ARCHITECTURE.read_text()
    assert "## Pallas kernel status" in text
    status = text.split("## Pallas kernel status", 1)[1].split("\n## ", 1)[0]
    assert "`kernels.ops.default_interpret`" in status
    assert hasattr(ops, "default_interpret")
    for path in ("tests/test_tpu_compile.py", "chip_smoke.py"):
        assert path in status and (REPO / path).exists(), path
    # LP kernels: every kernels/ module that launches one, bar the Mamba
    # model's scan kernel (ssm_scan.py)
    kernels = sorted(p.name for p in (REPO / "src/repro/kernels").glob("*.py")
                     if "pl.pallas_call(" in p.read_text()
                     and p.name != "ssm_scan.py")
    assert len(kernels) == 4, kernels
    missing = [k for k in kernels if f"`kernels/{k}`" not in status]
    assert not missing, \
        f"kernels missing from the Pallas kernel status table: {missing}"


def test_architecture_observability_documents_every_lane():
    # the Observability section's lane table must name every counter the
    # telemetry plane actually collects — a new lane fails until documented
    from repro.obs.telemetry import ALL_LANES
    text = ARCHITECTURE.read_text()
    assert "## Observability" in text, \
        "docs/architecture.md lost its Observability section"
    obs = text.split("## Observability", 1)[1]
    ghosts = [lane for lane in ALL_LANES if f"`{lane}`" not in obs]
    assert not ghosts, \
        f"telemetry lanes missing from the docs/architecture.md " \
        f"Observability section: {ghosts}"


def test_readme_telemetry_quickstart_is_real():
    # README's telemetry snippet must reflect the actual API surface
    text = README.read_text()
    for needle in ("telemetry=True", "res.stats", "SolveReport",
                   "examples/serve_batched.py"):
        assert needle in text, \
            f"README.md telemetry quickstart lost: {needle!r}"
    from repro.obs import SolveReport
    for method in ("render", "summary"):
        assert hasattr(SolveReport, method), \
            f"README documents SolveReport.{method}() but it is gone"


@pytest.mark.skipif(not BENCH_JSON.exists(),
                    reason="no committed benchmark baseline")
def test_bench_readme_sections_match_json():
    d = json.loads(BENCH_JSON.read_text())
    json_sections = {k for k, v in d.items() if isinstance(v, list)}
    doc_sections = set(re.findall(r"^### `(\w+)`", BENCH_README.read_text(),
                                  flags=re.M))
    assert json_sections == doc_sections, \
        f"benchmarks/README.md sections {sorted(doc_sections)} != " \
        f"BENCH_pivot_work.json sections {sorted(json_sections)}"
