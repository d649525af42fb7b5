"""Metrics hygiene lint (tier-1): scrape a booted single-binary app's
/metrics and fail on exposition rot — empty help text, duplicate
registration, malformed family names, bad label names.

The reference enforces this socially (promtool lint in CI + naming
conventions in review); here the rules are executable so a PR that adds
`tempo_foo-bar` or help-less metrics fails before it merges:

- family names match  tempo(db|_tpu)?_[a-z0-9_]+
- every family has non-empty HELP
- no family declares TYPE twice (duplicate registration)
- label names match the Prometheus data model
- sample lines belong to a declared family (histograms may emit
  _bucket/_sum/_count; counters emit their own name)
- no family exceeds its declared series-cardinality budget (the
  per-tenant labels ISSUE 10 added must never explode /metrics —
  idle-tenant eviction keeps tenant series bounded, this guard keeps
  everyone honest about it)
"""

import re
import urllib.request

import pytest

from tempo_tpu.app import App, AppConfig
from tempo_tpu.api.server import TempoServer
from tempo_tpu.db import DBConfig

NAME_RE = re.compile(r"tempo(db|_tpu)?_[a-z0-9_]+\Z")
LABEL_RE = re.compile(r"[a-zA-Z_][a-zA-Z0-9_]*\Z")
SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{(.*)\})?\s+(\S+)$"
)
LABEL_PAIR_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


@pytest.fixture(scope="module")
def exposition(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("hygiene")
    app = App(AppConfig(
        db=DBConfig(backend="local", backend_path=str(tmp / "blocks"),
                    wal_path=str(tmp / "wal")),
        generator_enabled=False,
    ))
    srv = TempoServer(app).start()
    try:
        with urllib.request.urlopen(srv.url + "/metrics") as r:
            assert r.status == 200
            yield r.read().decode()
    finally:
        srv.stop()
        app.shutdown()


def _parse(text):
    helps: dict[str, str] = {}
    types: list[tuple[str, str]] = []
    samples: list[tuple[str, str]] = []
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            rest = line[len("# HELP "):]
            name, _, help_ = rest.partition(" ")
            helps[name] = help_
        elif line.startswith("# TYPE "):
            rest = line[len("# TYPE "):]
            name, _, kind = rest.partition(" ")
            types.append((name, kind.strip()))
        elif line.startswith("#"):
            continue
        else:
            m = SAMPLE_RE.match(line)
            assert m, f"unparseable sample line: {line!r}"
            samples.append((m.group(1), m.group(3) or ""))
    return helps, types, samples


def test_family_names_match_convention(exposition):
    helps, types, _ = _parse(exposition)
    bad = [n for n, _ in types if not NAME_RE.fullmatch(n)]
    assert not bad, f"metric names outside tempo(db|_tpu)?_* convention: {bad}"


def test_no_empty_help(exposition):
    helps, types, _ = _parse(exposition)
    missing = [n for n, _ in types if not helps.get(n, "").strip()]
    assert not missing, f"metrics with empty help text: {missing}"


def test_no_duplicate_registration(exposition):
    _, types, _ = _parse(exposition)
    seen: set = set()
    dups = []
    for name, _kind in types:
        if name in seen:
            dups.append(name)
        seen.add(name)
    assert not dups, f"families declared twice: {dups}"


def test_samples_belong_to_declared_families(exposition):
    _, types, samples = _parse(exposition)
    families = {n for n, _ in types}
    kinds = dict(types)
    allowed: set = set()
    for name in families:
        allowed.add(name)
        if kinds[name] == "histogram":
            allowed.update({f"{name}_bucket", f"{name}_sum", f"{name}_count"})
    orphans = sorted({n for n, _ in samples if n not in allowed})
    assert not orphans, f"sample lines with no declared family: {orphans}"


def test_label_names_valid(exposition):
    _, _, samples = _parse(exposition)
    bad = []
    for name, labelstr in samples:
        if not labelstr:
            continue
        for lname, _v in LABEL_PAIR_RE.findall(labelstr):
            if not LABEL_RE.fullmatch(lname) or lname.startswith("__"):
                bad.append((name, lname))
    assert not bad, f"invalid label names: {bad}"


# -- series-cardinality budgets ------------------------------------------
#
# Budget = max label sets (series) one family may expose, `le` excluded
# (histogram buckets are geometry, not cardinality). The default covers
# label-less and small-enum families; anything labelled by tenant/route/
# kernel must DECLARE its budget here — adding an unbounded label without
# declaring (and defending) a budget is exactly the regression this
# guard exists to catch. Budgets assume bounded-tenant deployments with
# idle-tenant eviction armed (distributor + usage accountant + scanner).
DEFAULT_SERIES_BUDGET = 24
FAMILY_SERIES_BUDGETS = {
    # method x route x status on the HTTP server
    "tempo_request_duration_seconds_total": 600,
    "tempo_request_duration_seconds": 200,
    # stage x kind waterfall (11 stages; kinds: the five query kinds,
    # flush, standing, push — no kind records every stage)
    "tempo_tpu_query_stage_seconds": 80,
    "tempo_tpu_query_device_dispatches_total": 8,
    # kernel-labelled device timing + the data-movement plane
    # (direction enum x kernel labels; kernels are code-literal strings)
    "tempo_tpu_device_dispatch_seconds": 32,
    "tempo_tpu_device_dispatches_total": 32,
    "tempo_tpu_device_transfer_bytes_total": 96,
    # what device profiler captures found (util/profiling): kernels are
    # code-literal strings, and `host` is an annotation's name: span
    # names are code literals over kernel labels and ROUTE TEMPLATES
    # (never a raw path, a tenant or a block id), `seam` its prefix
    "tempo_tpu_profile_device_idle_seconds_total": 192,
    "tempo_tpu_profile_dispatch_wall_seconds_total": 32,
    "tempo_tpu_profile_dispatch_device_seconds_total": 32,
    "tempo_tpu_profile_dispatches_total": 32,
    # page-heat ledger: label-less totals + a bounded budget-fraction
    # enum on the what-if gauges (block/column must NEVER become labels
    # here; per-page data belongs on /status/device)
    "tempo_tpu_pageheat_miss_ratio": 8,
    "tempo_tpu_pageheat_budget_bytes": 8,
    # component x reason sheds
    "tempo_tpu_shed_total": 32,
    # tenant-labelled families (eviction-bounded: ~T active tenants,
    # x reason / kind / codec where applicable)
    "tempo_distributor_spans_received_total": 64,
    "tempo_distributor_bytes_received_total": 64,
    "tempo_discarded_spans_total": 192,
    "tempo_ingester_blocks_flushed_total": 64,
    "tempo_ingester_blocks_dropped_total": 64,
    "tempo_ingester_live_traces": 64,
    "tempo_ingester_pressure_cuts_total": 64,
    "tempo_ingester_pushes_refused_total": 64,
    "tempodb_blocklist_length": 64,
    "tempodb_inspected_bytes_total": 64,
    "tempodb_decoded_bytes_total": 64,
    # codec (rle | dct | dbp) x source (cached | parsed) enums: blocks,
    # columns and tenants must NEVER become labels here
    "tempodb_gathers_total": 6,
    "tempodb_compaction_runs_total": 64,
    "tempodb_compaction_errors_total": 64,
    "tempodb_compaction_blocks_compacted_total": 64,
    "tempodb_compaction_objects_written_total": 64,
    "tempodb_compaction_slow_jobs_total": 64,
    "tempodb_compaction_pages_copied_verbatim_total": 64,
    "tempodb_compaction_pages_reencoded_total": 64,
    "tempodb_orphan_blocks_swept_total": 64,
    "tempodb_blocklist_quarantined_blocks": 64,
    "tempodb_zonemap_coverage_ratio": 64,
    "tempodb_compaction_debt_row_groups": 64,
    "tempodb_compaction_debt_ratio": 64,
    "tempodb_compaction_debt_payoff": 64,
    "tempodb_storage_compression_ratio": 64,
    "tempodb_storage_codec_stored_bytes": 16,  # codec enum
    # continuous-verification plane: type x tier / check x tier enums
    "tempo_vulture_check_total": 32,
    "tempo_vulture_error_total": 32,
    "tempo_vulture_freshness_seconds": 8,
    # SLO engine: objective x window (objectives are config-bounded)
    "tempo_tpu_slo_burn_rate": 64,
    "tempo_tpu_slo_error_budget_remaining": 16,
    "tempo_tpu_slo_sli_events": 16,
    "tempo_tpu_slo_sli_good_events": 16,
    "tempo_tpu_slo_burning": 32,
    # query-insights capture counter: kind x reason enums
    "tempo_tpu_query_insights_total": 32,
    # standing-query plane: per-tenant registration gauge (bounded by
    # registration caps + tenant count) and a per-query-id alert gauge
    # (bounded by standing.max_queries_per_tenant x tenants; ids are
    # dropped at deregistration)
    "tempo_tpu_standing_queries": 64,
    "tempo_tpu_standing_alert_firing": 64,
    # seasonal-deviation detector: per-query-id gauges/counters, same
    # bound and same drop-at-deregistration discipline as alert_firing
    "tempo_tpu_standing_deviation_firing": 64,
    "tempo_tpu_standing_deviation_fires_total": 64,
    # auto-RCA plane: trigger / cause / reason enums only — incident
    # ids, tenants, and services must NEVER become labels here; the
    # ranked detail lives on /api/rca/{incidentID}
    "tempo_tpu_rca_incidents_total": 4,
    "tempo_tpu_rca_attributed_total": 8,   # bounded by CAUSES
    "tempo_tpu_rca_suppressed_total": 2,
    "tempo_tpu_rca_triggers_dropped_total": 4,
    "tempo_tpu_rca_open_incidents": 2,
    "tempo_tpu_rca_time_to_attribution_seconds": 2,
    # compiled-query tier: label-less cache totals — shapes/programs
    # must NEVER become labels here; per-shape data belongs on
    # /api/query-insights
    "tempo_tpu_compiled_hits_total": 2,
    "tempo_tpu_compiled_misses_total": 2,
    "tempo_tpu_compiled_compiles_total": 2,
    "tempo_tpu_compiled_errors_total": 2,
    "tempo_tpu_compiled_evictions_total": 2,
    # trace-graph analytics plane: label-less totals + a small kind enum
    # (dependencies | critical_path | walks) — edges/services must NEVER
    # become labels here; per-edge data belongs in query responses
    "tempo_tpu_graph_edges_total": 2,
    "tempo_tpu_graph_unpaired_spans_total": 2,
    "tempo_tpu_graph_walk_steps_total": 2,
    "tempo_tpu_graph_queries_total": 8,
    # device-native ingest plane: decode path enum (columnar | object) and
    # codec enums (rle | dct | dbp) — tenants/columns must NEVER become
    # labels here; per-tenant ingest cost lives in the usage counters
    "tempo_tpu_ingest_spans_decoded_total": 4,
    # which OTLP scanner answered a body: native, or python with the reason
    # the native scan declined (native.OTLP_DECLINED + no_library, closed)
    "tempo_tpu_ingest_decode_requests_total": 12,
    "tempo_tpu_ingest_device_encode_pages_total": 8,
    "tempo_tpu_ingest_encode_fallback_total": 8,
    # tenant x kind cost counters (usage accountant eviction bounds tenant)
    **{f"tempo_tpu_usage_{f}_total": 448 for f in (
        "ingested_bytes", "ingested_spans", "flushed_bytes",
        "inspected_bytes", "decoded_bytes", "pages_fetched",
        "ranged_reads", "cache_hits", "cache_misses",
        "device_seconds", "device_dispatches", "transfer_bytes")},
}


def _series_per_family(text):
    _, types, samples = _parse(text)
    fam_of = {}
    for name, kind in types:
        fam_of[name] = name
        if kind == "histogram":
            for sfx in ("_bucket", "_sum", "_count"):
                fam_of[name + sfx] = name
    series: dict[str, set] = {}
    for name, labelstr in samples:
        fam = fam_of.get(name)
        if fam is None:
            continue
        labels = tuple(sorted(
            (k, v) for k, v in LABEL_PAIR_RE.findall(labelstr or "")
            if k != "le"
        ))
        series.setdefault(fam, set()).add(labels)
    return series


def test_series_cardinality_within_budget(exposition):
    """Every family fits its declared label-cardinality budget. A family
    growing past the default must declare (and justify) a budget above —
    'I added a label' is not a license for unbounded series."""
    series = _series_per_family(exposition)
    over = {
        fam: (len(s), FAMILY_SERIES_BUDGETS.get(fam, DEFAULT_SERIES_BUDGET))
        for fam, s in series.items()
        if len(s) > FAMILY_SERIES_BUDGETS.get(fam, DEFAULT_SERIES_BUDGET)
    }
    assert not over, (
        f"families over their series budget (series, budget): {over} — "
        "either the label set is unbounded (fix the code: eviction / "
        "enum labels only) or the budget must be raised HERE with a "
        "justification"
    )


def test_budgeted_families_exist_or_are_future(exposition):
    """Typo guard: every explicitly budgeted family must be a registered
    metric (budgets for dead names rot silently). Requests the booted-app
    fixture so the registry's import set is deterministic even when this
    test runs alone."""
    del exposition  # only needed for its boot side effect
    from tempo_tpu.util.metrics import REGISTRY

    with REGISTRY._lock:
        known = set(REGISTRY._metrics)
    dead = [f for f in FAMILY_SERIES_BUDGETS if f not in known]
    assert not dead, f"budgets declared for unregistered families: {dead}"


def test_registry_wide_help_nonempty():
    """Belt-and-braces beyond the scrape: any metric object anywhere in
    the process registry (including ones with no samples yet) must carry
    help text and a conventional name."""
    from tempo_tpu.util.metrics import REGISTRY

    with REGISTRY._lock:
        metrics = dict(REGISTRY._metrics)
    no_help = [n for n, m in metrics.items() if not getattr(m, "help", "").strip()]
    bad_name = [n for n in metrics if not NAME_RE.fullmatch(n)]
    assert not no_help, f"registered metrics with empty help: {no_help}"
    assert not bad_name, f"registered metrics violating naming: {bad_name}"
