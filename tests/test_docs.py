"""Documentation consistency: the docs the code cites must exist and agree.

* Every ``DESIGN.md §<section>`` reference in source/test/example
  docstrings must name a section heading that actually exists in
  DESIGN.md.
* README's verify command must be exactly ROADMAP's tier-1 command.
* docs/api.md must only name public symbols that actually resolve.
"""

import re
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

REF_RE = re.compile(r"DESIGN\.md\s+§([0-9A-Za-z.\-]+)")
HEADING_RE = re.compile(r"^#+\s.*§([0-9A-Za-z.\-]+)", re.MULTILINE)


def _design_sections():
    text = (ROOT / "DESIGN.md").read_text()
    return {m.rstrip(".") for m in HEADING_RE.findall(text)}


def _cited_refs():
    refs = {}
    for sub in ("src", "tests", "examples", "benchmarks"):
        for path in (ROOT / sub).rglob("*.py"):
            for m in REF_RE.findall(path.read_text()):
                refs.setdefault(m.rstrip("."), []).append(
                    str(path.relative_to(ROOT)))
    return refs


def test_design_md_exists_and_has_sections():
    sections = _design_sections()
    # the sections the tree has cited since the seed, plus the device
    # DBHT spec (§11, PR 3) whose every subsection is cited from code
    for must in ("1", "2", "4.2", "4.3", "4.4", "5", "6", "9",
                 "10", "10.1", "10.2", "10.3", "10.4",
                 "11", "11.1", "11.2", "11.3", "11.4",
                 "12", "12.1", "12.2", "12.3", "12.4",
                 "13", "13.1", "13.2", "13.3", "13.4", "13.5",
                 "14", "14.1", "14.2", "14.3", "14.4", "14.5", "14.6",
                 "15", "15.1", "15.2", "15.3", "15.4",
                 "16", "16.1", "16.2", "16.3", "16.4",
                 "17", "17.1", "17.2", "17.3", "17.4",
                 "18", "18.1", "18.2", "18.3", "18.4", "18.5",
                 "Arch-applicability"):
        assert must in sections, f"DESIGN.md lost §{must}"


def test_device_dbht_sections_are_cited_from_code():
    """§11's spec stays honest: each §11.x must actually be cited by at
    least one docstring in src/tests (the citation invariant the issue
    extends to the device DBHT spec)."""
    refs = _cited_refs()
    for sub in ("11", "11.1", "11.2", "11.3", "11.4"):
        assert sub in refs, f"DESIGN.md §{sub} is cited from no code"


def test_fused_pipeline_sections_are_cited_from_code():
    """§12's spec stays honest the same way (ISSUE 4): the config
    object, the fused program, the bounded executable cache and the
    staged timing mode must each be cited from at least one docstring
    in src/tests/benchmarks."""
    refs = _cited_refs()
    for sub in ("12", "12.1", "12.2", "12.3", "12.4"):
        assert sub in refs, f"DESIGN.md §{sub} is cited from no code"


def test_sparse_similarity_sections_are_cited_from_code():
    """§13's spec stays honest the same way (ISSUE 5): candidate
    generation, the rescoring kernel, the sparse gain scan's fallback
    semantics, the quality harness and the fused-path limitation must
    each be cited from at least one docstring in
    src/tests/benchmarks."""
    refs = _cited_refs()
    for sub in ("13", "13.1", "13.2", "13.3", "13.4", "13.5"):
        assert sub in refs, f"DESIGN.md §{sub} is cited from no code"


def test_sparse_apsp_sections_are_cited_from_code():
    """§14's spec stays honest the same way (ISSUE 6): the relaxation
    kernel, the hub reuse + threshold, the D~ composition contract, the
    tree fallback, the parity contract and the host-orchestration
    boundary must each be cited from at least one docstring in
    src/tests/benchmarks."""
    refs = _cited_refs()
    for sub in ("14", "14.1", "14.2", "14.3", "14.4", "14.5", "14.6"):
        assert sub in refs, f"DESIGN.md §{sub} is cited from no code"


def test_obs_sections_are_cited_from_code():
    """§15's spec stays honest the same way (ISSUE 7): the span tracer
    and fencing contract, the compile counters + recompile watchdog,
    the metrics registry and the export/row-schema layer must each be
    cited from at least one docstring in src/tests/benchmarks."""
    refs = _cited_refs()
    for sub in ("15", "15.1", "15.2", "15.3", "15.4"):
        assert sub in refs, f"DESIGN.md §{sub} is cited from no code"


def test_admission_sections_are_cited_from_code():
    """§16's spec stays honest the same way (ISSUE 8): the bounded
    queue + idempotent submit, the per-tenant quotas, the breaker +
    degraded lane and the load/fault acceptance layer must each be
    cited from at least one docstring in src/tests/benchmarks."""
    refs = _cited_refs()
    for sub in ("16", "16.1", "16.2", "16.3", "16.4"):
        assert sub in refs, f"DESIGN.md §{sub} is cited from no code"


def test_fused_approx_sections_are_cited_from_code():
    """§17's spec stays honest the same way (ISSUE 9): the in-program
    panel sweep, the device Euler tour/direction sums, the slot-grid
    HAC and the sharded funnel must each be cited from at least one
    docstring in src/tests/benchmarks."""
    refs = _cited_refs()
    for sub in ("17", "17.1", "17.2", "17.3", "17.4"):
        assert sub in refs, f"DESIGN.md §{sub} is cited from no code"


def test_filter_sections_are_cited_from_code():
    """§18's spec stays honest the same way (ISSUE 10): the filter
    matrix, the RMT derivation, the PMFG host boundary, the generic
    hierarchy tail (the DBHT-on-MST caveat) and the keys/quality/
    backtest layer must each be cited from at least one docstring in
    src/tests/benchmarks/examples."""
    refs = _cited_refs()
    for sub in ("18", "18.1", "18.2", "18.3", "18.4", "18.5"):
        assert sub in refs, f"DESIGN.md §{sub} is cited from no code"


def test_readme_and_api_document_fused_approx():
    """The fused approx surface stays documented: README's quickstart
    runs `.approx()` through the fused default (no staged-only caveat),
    docs/api.md covers the sharded funnel and the fused
    `run_pipeline_device` topk acceptance."""
    readme = (ROOT / "README.md").read_text()
    assert "PipelineConfig.approx" in readme
    assert "staged-only" not in readme, \
        "README still carries the retired staged-only approx caveat"
    api = (ROOT / "docs" / "api.md").read_text()
    for name in ("topk_pearson_sharded", "run_pipeline_sharded",
                 "fused_approx"):
        assert name in api, f"docs/api.md lost {name}"


def test_readme_and_api_document_admission():
    """The serving front door stays documented: README carries the
    serving-under-load quickstart (AdmissionConfig + tenant submits +
    healthz), docs/api.md covers `repro.stream.admission`."""
    readme = (ROOT / "README.md").read_text()
    for name in ("AdmissionConfig", "tenant", "healthz"):
        assert name in readme, f"README lost {name}"
    api = (ROOT / "docs" / "api.md").read_text()
    for name in ("repro.stream.admission", "AdmissionConfig",
                 "CircuitBreaker", "TokenBucket", "Ticket",
                 "shed_total", "degraded_total"):
        assert name in api, f"docs/api.md lost {name}"


def test_readme_and_api_document_obs():
    """The observability layer stays documented: docs/api.md covers
    `repro.obs` (spans, the watch, the registry, the exporters) and
    docs/benchmarks.md records the compile_s/run_s row schema that
    --check-schema gates in CI."""
    api = (ROOT / "docs" / "api.md").read_text()
    assert "repro.obs" in api
    for name in ("watch_recompiles", "compile_s", "snapshot",
                 "healthz"):
        assert name in api, f"docs/api.md lost {name}"
    bench = (ROOT / "docs" / "benchmarks.md").read_text()
    assert "--check-schema" in bench and "replay_recompiles" in bench


def test_readme_and_api_document_approx():
    """The `.approx` entry points stay documented: README quickstart
    names the constructor, docs/api.md covers the subsystem."""
    readme = (ROOT / "README.md").read_text()
    assert "PipelineConfig.approx" in readme
    api = (ROOT / "docs" / "api.md").read_text()
    assert "`repro.approx`" in api or "repro.approx" in api
    assert "sim_k" in api and "ops.topk" in api


def test_every_design_citation_resolves():
    sections = _design_sections()
    missing = {ref: files for ref, files in _cited_refs().items()
               if ref not in sections}
    assert not missing, (
        f"docstrings cite DESIGN.md sections that don't exist: {missing}; "
        f"have {sorted(sections)}")


def test_readme_verify_matches_roadmap():
    roadmap = (ROOT / "ROADMAP.md").read_text()
    m = re.search(r"\*\*Tier-1 verify:\*\*\s+`([^`]+)`", roadmap)
    assert m, "ROADMAP.md lost its tier-1 verify line"
    cmd = m.group(1)
    readme = (ROOT / "README.md").read_text()
    assert cmd in readme, (
        f"README verify command drifted from ROADMAP's tier-1: {cmd!r}")


def test_api_md_names_resolve():
    """Every backticked repro.* dotted name in docs/api.md must import."""
    import importlib

    text = (ROOT / "docs" / "api.md").read_text()
    names = set(re.findall(r"`(repro(?:\.\w+)+)`", text))
    assert names, "docs/api.md should reference repro.* modules"
    for name in sorted(names):
        parts = name.split(".")
        for split in range(len(parts), 0, -1):
            try:
                obj = importlib.import_module(".".join(parts[:split]))
            except ImportError:
                continue
            for attr in parts[split:]:
                obj = getattr(obj, attr)  # raises if the doc lies
            break
        else:
            raise AssertionError(f"docs/api.md names unimportable {name}")


def test_markdown_relative_links_resolve():
    """Every relative link in every tracked *.md must point at a file
    that exists (tools/check_links.py is the standalone CI entry)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_links", ROOT / "tools" / "check_links.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.broken_links(ROOT) == []


def test_readme_documents_all_variants():
    from repro.core.pipeline import VARIANTS

    readme = (ROOT / "README.md").read_text()
    for v in VARIANTS:
        assert f"`{v}`" in readme, f"README variant table lost {v!r}"
