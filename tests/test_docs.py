"""Docs exist and don't drift: the README kernel-inventory table must track
src/repro/kernels/*/ (scripts/check_docs.py), and the first-class docs
surface must be present."""
import importlib.util
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def _load_check_docs():
    spec = importlib.util.spec_from_file_location(
        "check_docs", REPO / "scripts" / "check_docs.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_docs_surface_exists():
    for rel in ("README.md", "docs/ARCHITECTURE.md", "docs/BENCHMARKS.md",
                "docs/SERVING.md", "docs/OBSERVABILITY.md"):
        path = REPO / rel
        assert path.exists(), f"missing {rel}"
        assert path.stat().st_size > 500, f"{rel} is a stub"


def test_kernel_inventory_in_sync():
    mod = _load_check_docs()
    errors = mod.check()
    assert not errors, "\n".join(errors)


def test_check_docs_detects_drift():
    """The checker actually fails when a family is undocumented (guards
    against a regex rot that silently matches nothing)."""
    mod = _load_check_docs()
    documented = mod.documented_families((REPO / "README.md").read_text())
    assert "residual_flush" in documented
    assert "paged_bitdecode" in documented
    broken = (REPO / "README.md").read_text().replace("`residual_flush`", "`x`")
    assert mod.documented_families(broken) != documented


def test_serving_doc_symbols_resolve_and_drift_detected():
    """docs/SERVING.md's dotted repro.* references resolve; a bogus symbol,
    flag, or counter is caught (guards the new serving-doc checks against
    regex rot)."""
    mod = _load_check_docs()
    text = (REPO / "docs" / "SERVING.md").read_text()
    syms = mod.serving_symbols(text)
    assert "repro.serve.scheduler.PrefixIndex" in syms
    assert "repro.core.qcache.copy_pages" in syms
    assert not mod.check_serving(text)
    assert mod.check_serving(text + "\nsee `repro.serve.engine.NoSuchThing`")
    assert mod.check_serving(
        text.replace("| `share_prefix` |", "| `share_prefixes` |"))
    assert mod.check_serving(
        text.replace("| `cow_copies` |", "| `cow_copy_total` |"))


def test_serving_doc_flags_match_engine_signature():
    """Every ServeEngine sharing-related flag is documented: the doc's flag
    table must include the knobs the tests exercise."""
    mod = _load_check_docs()
    text = (REPO / "docs" / "SERVING.md").read_text()
    flags = mod.table_rows(text, "Engine flags")
    assert {"share_prefix", "spec_tail", "page_affine", "n_pages",
            "trace", "metrics_every"} <= flags


def test_observability_doc_in_sync_and_drift_detected():
    """docs/OBSERVABILITY.md's metric catalog and event schema track the
    code: a renamed metric, a ghost event, or an undocumented engine
    counter all fail (guards the checker itself against regex rot)."""
    mod = _load_check_docs()
    text = (REPO / "docs" / "OBSERVABILITY.md").read_text()
    assert not mod.check_observability(text)
    metrics = mod.table_rows(text, "Metric catalog")
    assert {"ttft_s", "tpot_s", "cycle_s", "phase_device_wait_s",
            "pool_occupancy", "sched_backpressure_events",
            "faults_injected"} <= metrics
    events = mod.table_rows(text, "Event schema")
    assert {"queue", "prefill", "decode", "preempt", "cow", "fault",
            "spec_verify"} <= events
    # a documented metric the code never emits
    assert mod.check_observability(
        text.replace("| `ttft_s` |", "| `ttft_seconds_total` |"))
    # a documented event the code never emits
    assert mod.check_observability(
        text.replace("| `cow` |", "| `copy_on_write` |"))
    # an engine counter dropped from the catalog
    assert mod.check_observability(
        text.replace("| `preempted` |", "| |"))
    # a bogus dotted symbol
    assert mod.check_observability(
        text + "\nsee `repro.serve.telemetry.NoSuchThing`")
