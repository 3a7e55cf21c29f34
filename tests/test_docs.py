"""docs/ARCHITECTURE.md stays honest: its plan-kind table is cross-checked
against the actual kind registry (``sched/compile.PLAN_KINDS``) and its
"replayed by" / "planless reference" columns against the real symbols, so
the architecture doc cannot silently rot as the runtime grows."""
import os
import re

import pytest

DOC = os.path.join(os.path.dirname(__file__), "..", "docs", "ARCHITECTURE.md")
ROADMAP = os.path.join(os.path.dirname(__file__), "..", "ROADMAP.md")


def _doc_text():
    assert os.path.exists(DOC), "docs/ARCHITECTURE.md is missing"
    with open(DOC) as f:
        return f.read()


def _plan_kind_rows():
    """Rows of the '## Plan kinds' markdown table as lists of cell texts."""
    text = _doc_text()
    m = re.search(r"^## Plan kinds\n(.*?)(?=^## )", text,
                  re.MULTILINE | re.DOTALL)
    assert m, "ARCHITECTURE.md has no '## Plan kinds' section"
    rows = []
    for line in m.group(1).splitlines():
        if not line.startswith("|") or re.match(r"^\|[\s\-|]+\|$", line):
            continue
        cells = [c.strip() for c in line.strip("|").split("|")]
        if cells and cells[0] != "kind":  # skip header
            rows.append(cells)
    assert rows, "plan-kind table has no data rows"
    return rows


def test_plan_kind_table_matches_registry():
    """Every kind in sched/compile.PLAN_KINDS appears in the doc table and
    vice versa — adding a kind without documenting it (or documenting a
    kind that does not exist) fails tier-1."""
    from repro.sched.compile import PLAN_KINDS

    doc_kinds = {re.sub(r"`", "", r[0]) for r in _plan_kind_rows()}
    assert doc_kinds == set(PLAN_KINDS), (
        f"docs/ARCHITECTURE.md plan-kind table {sorted(doc_kinds)} != "
        f"sched/compile.PLAN_KINDS {sorted(PLAN_KINDS)}")


def test_plan_kind_registry_compilers_are_real():
    """Registry values are the actual compiler callables exported by
    sched (the doc's 'compiles' column is backed by code)."""
    from repro import sched
    from repro.sched.compile import PLAN_KINDS

    for kind, fn in PLAN_KINDS.items():
        assert callable(fn), kind
        assert getattr(sched, fn.__name__) is fn, (
            f"PLAN_KINDS[{kind!r}] = {fn.__name__} is not exported from "
            f"repro.sched")


_ALIASES = {"sched": "repro.sched", "core": "repro.core",
            "optim": "repro.optim", "serve": "repro.serve",
            "sync": "repro.sync"}


@pytest.mark.parametrize("column", [2, 3], ids=["replayed_by", "planless"])
def test_plan_kind_table_symbols_resolve(column):
    """The 'replayed by' and 'planless reference' columns name importable
    symbols (first backticked dotted path per cell)."""
    import importlib

    for row in _plan_kind_rows():
        m = re.search(r"`([\w.]+)", row[column])
        assert m, row
        parts = m.group(1).split(".")
        mod_path = _ALIASES[parts[0]]
        obj = importlib.import_module(mod_path)
        for attr in parts[1:]:
            try:
                obj = getattr(obj, attr)
            except AttributeError:
                obj = importlib.import_module(
                    f"{mod_path}.{attr}")  # submodule hop (e.g. core.split_send)
                mod_path = f"{mod_path}.{attr}"
        assert obj is not None, row


def test_roadmap_links_architecture_doc():
    with open(ROADMAP) as f:
        text = f.read()
    assert "docs/ARCHITECTURE.md" in text, (
        "ROADMAP.md must link docs/ARCHITECTURE.md")


def test_doc_covers_all_subsystems():
    """The subsystem map names every package under src/repro (no new
    subsystem lands undocumented)."""
    text = _doc_text()
    src = os.path.join(os.path.dirname(__file__), "..", "src", "repro")
    pkgs = sorted(d for d in os.listdir(src)
                  if os.path.isdir(os.path.join(src, d))
                  and not d.startswith("_"))
    missing = [p for p in pkgs if f"`{p}" not in text and f"{p}/" not in text]
    assert not missing, f"ARCHITECTURE.md does not mention: {missing}"


# ---------------------------------------------------------------------------
# Observability section: the metric table IS obs.names.METRICS
# ---------------------------------------------------------------------------

def _obs_section():
    text = _doc_text()
    m = re.search(r"^## Observability\n(.*?)(?=^## )", text,
                  re.MULTILINE | re.DOTALL)
    assert m, "ARCHITECTURE.md has no '## Observability' section"
    return m.group(1)


def _metric_rows():
    rows = []
    for line in _obs_section().splitlines():
        if not line.startswith("|") or re.match(r"^\|[\s\-|]+\|$", line):
            continue
        cells = [c.strip() for c in line.strip("|").split("|")]
        if cells and cells[0] != "name":  # skip header
            rows.append(cells)
    assert rows, "Observability metric table has no data rows"
    return rows


def test_obs_metric_table_matches_registry():
    """Every canonical metric appears in the doc with its exact type,
    label set and emitting module — and the doc lists nothing the code
    does not emit (the plan-kind-table pattern applied to telemetry)."""
    from repro.obs.names import METRICS

    doc = {re.sub(r"`", "", r[0]): r for r in _metric_rows()}
    specs = {s.name: s for s in METRICS}
    assert set(doc) == set(specs), (
        f"doc-only: {sorted(set(doc) - set(specs))}, "
        f"code-only: {sorted(set(specs) - set(doc))}")
    for name, spec in specs.items():
        row = doc[name]
        assert row[1] == spec.kind, (name, row[1], spec.kind)
        doc_labels = tuple(re.findall(r"`([\w]+)`", row[2]))
        assert doc_labels == spec.labels, (name, doc_labels, spec.labels)
        assert re.sub(r"`", "", row[3]) == spec.module, (name, row[3])


def test_obs_span_convention_documented():
    """Every canonical span name appears in the Observability section."""
    from repro.obs.names import SPANS

    section = _obs_section()
    missing = [n for n, _, _ in SPANS if f"`{n}`" not in section]
    assert not missing, (
        f"Observability section does not mention spans: {missing}")


# ---------------------------------------------------------------------------
# Broadcast-schedule section: the kind table IS sched.plan.BROADCAST_KINDS
# ---------------------------------------------------------------------------

def _broadcast_section():
    text = _doc_text()
    m = re.search(r"^## Broadcast schedules\n(.*?)(?=^## )", text,
                  re.MULTILINE | re.DOTALL)
    assert m, "ARCHITECTURE.md has no '## Broadcast schedules' section"
    return m.group(1)


def test_broadcast_kind_table_matches_registry():
    """Every broadcast kind is a documented table row and vice versa —
    the plan-kind-table pattern applied to the fan-out topologies."""
    from repro.sched.plan import BROADCAST_KINDS

    rows = []
    for line in _broadcast_section().splitlines():
        if not line.startswith("|") or re.match(r"^\|[\s\-|]+\|$", line):
            continue
        cells = [c.strip() for c in line.strip("|").split("|")]
        if cells and cells[0] != "kind":
            rows.append(cells)
    doc_kinds = {re.sub(r"`", "", r[0]) for r in rows}
    assert doc_kinds == set(BROADCAST_KINDS), (
        f"broadcast table {sorted(doc_kinds)} != "
        f"BROADCAST_KINDS {sorted(BROADCAST_KINDS)}")


def test_broadcast_section_symbols_are_real():
    """The forwarding-invariant and re-parenting machinery the section
    promises exists and is exported where the doc says it is."""
    import importlib

    section = _broadcast_section()
    for ref in ("BroadcastSchedule", "RoutedUpdate", "route_for",
                "verify_bitexact", "integrity_ledger", "wsync_hop_perms",
                "execute_wsync_broadcast", "broadcast_weights",
                "fleet_reparents_total", "fleet:forward"):
        assert ref in section, f"Broadcast section does not mention {ref}"
    sched = importlib.import_module("repro.sched")
    sync = importlib.import_module("repro.sync")
    for mod, attrs in [(sched, ("BroadcastSchedule", "BROADCAST_KINDS",
                                "compile_broadcast_schedule",
                                "wsync_hop_perms",
                                "execute_wsync_broadcast")),
                       (sync, ("RoutedUpdate", "broadcast_weights"))]:
        for a in attrs:
            assert hasattr(mod, a), a
    from repro.sched.plan import BroadcastSchedule, CommPlan

    assert hasattr(BroadcastSchedule("tree", 2, 4), "route_for")
    assert "broadcast" in {f.name for f in
                           __import__("dataclasses").fields(CommPlan)}


def test_broadcast_metrics_documented_in_obs_table():
    """The per-hop accounting series named by the broadcast section are
    canonical metrics (present in obs.names.METRICS and the doc table)."""
    from repro.obs.names import SPECS

    section = _broadcast_section()
    for name in ("fleet_trainer_egress_bytes_total", "fleet_forwards_total",
                 "fleet_forwarded_bytes_total", "fleet_hop_depth",
                 "fleet_reparents_total"):
        assert name in SPECS, name
        assert name in section, f"Broadcast section does not cite {name}"


# ---------------------------------------------------------------------------
# Failure model section: the fault taxonomy IS runtime.faults.FAULT_KINDS
# ---------------------------------------------------------------------------

def _failure_section():
    text = _doc_text()
    m = re.search(r"^## Failure model[^\n]*\n(.*?)(?=^## )", text,
                  re.MULTILINE | re.DOTALL)
    assert m, "ARCHITECTURE.md has no '## Failure model' section"
    return m.group(1)


def test_failure_model_covers_every_fault_kind():
    """Every injectable fault kind is documented in the failure-model
    section — extending the taxonomy without documenting the recovery
    story fails tier-1 (the plan-kind-table pattern applied to chaos)."""
    from repro.runtime.faults import FAULT_KINDS

    section = _failure_section()
    missing = [k for k in FAULT_KINDS if f"`{k}`" not in section]
    assert not missing, (
        f"Failure-model section does not document fault kinds: {missing}")


def test_failure_model_names_the_defense_layers():
    """The recovery machinery the section promises actually exists."""
    import importlib

    section = _failure_section()
    for ref in ("core/integrity.py", "sync/fleet.py", "runtime/faults.py"):
        assert ref in section.replace("`", ""), (
            f"Failure-model section does not reference {ref}")
    for mod, attrs in [("repro.core.integrity",
                        ("crc32_tree", "WireIntegrityError")),
                       ("repro.runtime.faults",
                        ("FaultPlan", "FaultyWire", "FAULT_KINDS")),
                       ("repro.sync.fleet",
                        ("SyncFleet", "FleetConfig"))]:
        m = importlib.import_module(mod)
        for a in attrs:
            assert hasattr(m, a), (mod, a)
