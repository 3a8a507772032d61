"""CI self-gate: the analyzer turned on its own codebase.

`pio check predictionio_tpu/` must run clean against the checked-in
baseline (`.pio-check-baseline.json`): any NEW finding — at any severity —
fails this test, so a regression like reintroducing the microbatch
busy-wait (PIO-CONC002) or an unlocked write to guarded state
(PIO-CONC003) is caught in tier-1, not in production.  Baseline entries
must carry real justifications, and the baseline must not accumulate
stale entries for code that no longer trips a rule.
"""

from __future__ import annotations

from collections import Counter
from pathlib import Path

from predictionio_tpu.analysis import (
    Baseline,
    Severity,
    analyze_paths,
)

REPO_ROOT = Path(__file__).resolve().parents[1]
PACKAGE = REPO_ROOT / "predictionio_tpu"
BASELINE = REPO_ROOT / ".pio-check-baseline.json"


def _report():
    return analyze_paths([PACKAGE], root=REPO_ROOT)


def test_package_parses_clean():
    report = _report()
    assert report.errors == []
    assert report.files_scanned > 50  # sanity: the walk found the package


def test_no_unbaselined_findings():
    """The acceptance gate: zero non-baselined findings at ANY severity."""
    report = _report()
    remaining, _ = Baseline.load(BASELINE).filter(report.findings)
    highs = [f for f in remaining if f.severity >= Severity.HIGH]
    assert highs == [], "new HIGH findings:\n" + "\n".join(
        f.text() for f in highs
    )
    assert remaining == [], "new findings (fix or baseline with " \
        "justification):\n" + "\n".join(f.text() for f in remaining)


def test_baseline_entries_are_justified():
    baseline = Baseline.load(BASELINE)
    assert baseline.entries, "self-run produced findings; baseline missing?"
    for e in baseline.entries:
        assert e.justification.strip(), f"unjustified baseline entry: {e}"
        assert not e.justification.lower().startswith("todo"), (
            f"placeholder justification: {e}"
        )


def test_baseline_did_not_grow():
    """Each obs subsystem (model quality in PR 4, device efficiency in
    PR 6) landed with ZERO new baseline entries.  PR 12's async-dispatch
    refactor then DELETED three of the 13 entries PR 2 curated — the
    ecommerce per-query factor pull now hides behind the device-resident
    cache, and the ALS wave's d2h syncs moved behind the finalize fence.
    The whole-program pass (PIO-LOCK/JAX008) swept the package and added
    exactly ONE justified entry: np.generic.item() in the external
    engine's JSON conversion, a host-side scalar with no device buffer.
    So the baseline was 11 through the provenance PR.  The multi-tenant
    PR's PIO-CONC004 (module-level singletons of per-tenant state) then
    added exactly TWO justified entries — the deliberate process-default
    getters default_quality() and default_ledger(), which multi-tenant
    replicas bypass via the TenantRegistry — and new rules remain the
    only allowed growth."""
    assert len(Baseline.load(BASELINE).entries) == 13


def test_baseline_has_no_stale_entries():
    """Every baseline entry still matches a real finding — entries for
    since-fixed code must be deleted, not accumulate."""
    report = _report()
    live = Counter((f.rule, f.file, f.source) for f in report.findings)
    stale = [e for e in Baseline.load(BASELINE).entries if not live[e.key]]
    assert stale == [], "stale baseline entries:\n" + "\n".join(
        f"{e.file}: {e.rule}: {e.source}" for e in stale
    )


def test_busy_wait_fix_stays_fixed():
    """Regression anchor for the defect the first self-run surfaced: the
    10 ms polling loop in MicroBatcher.close() (server/microbatch.py).  The
    file must stay free of PIO-CONC002 without any suppression."""
    report = analyze_paths(
        [PACKAGE / "server" / "microbatch.py"], root=REPO_ROOT
    )
    assert [f for f in report.findings if f.rule == "PIO-CONC002"] == []
    assert report.pragma_suppressed == 0


def test_obs_modules_lint_clean():
    """The request-lifecycle observability modules (logging, flight, slo,
    profiler, http, tracing, metrics) must be clean under `pio check` with
    no pragma suppressions — telemetry code runs on every request and gets
    no lint exemptions.  The ONLY tolerated findings are the two baselined
    PIO-CONC004 process-default getters (default_quality/default_ledger),
    which multi-tenant replicas bypass via the TenantRegistry."""
    report = analyze_paths([PACKAGE / "obs"], root=REPO_ROOT)
    assert report.errors == []
    remaining, _ = Baseline.load(BASELINE).filter(report.findings)
    assert remaining == [], "\n".join(f.text() for f in remaining)
    assert sorted((f.rule, f.file) for f in report.findings) == [
        ("PIO-CONC004", "predictionio_tpu/obs/costs.py"),
        ("PIO-CONC004", "predictionio_tpu/obs/quality.py"),
    ]
    assert report.pragma_suppressed == 0


def test_quality_module_lint_clean_with_zero_pragmas():
    """The online model-quality module runs on the serving hot path
    (observe_prediction per request) and the ingest path (observe_feedback
    per event): it must be `pio check`-clean with NO pragma suppressions.
    Its single baseline entry is the PIO-CONC004 process-default getter
    default_quality() — deliberate, justified, and bypassed by the
    TenantRegistry's per-tenant monitors — and it must stay the only one."""
    report = analyze_paths([PACKAGE / "obs" / "quality.py"], root=REPO_ROOT)
    assert report.errors == []
    remaining, _ = Baseline.load(BASELINE).filter(report.findings)
    assert remaining == [], "\n".join(f.text() for f in remaining)
    assert report.pragma_suppressed == 0
    quality_file = "predictionio_tpu/obs/quality.py"
    entries = [
        e for e in Baseline.load(BASELINE).entries if e.file == quality_file
    ]
    assert [(e.rule,) for e in entries] == [("PIO-CONC004",)]


def test_provenance_module_lint_clean_with_zero_pragmas():
    """Decision provenance runs inside EVERY answered request (capture)
    and rebinds model generations offline (replay): it must be `pio
    check`-clean with NO pragma suppressions and NO baseline entries —
    the baseline stays frozen at its pre-provenance size."""
    report = analyze_paths(
        [PACKAGE / "obs" / "provenance.py"], root=REPO_ROOT
    )
    assert report.errors == []
    assert report.findings == [], "\n".join(f.text() for f in report.findings)
    assert report.pragma_suppressed == 0
    prov_file = "predictionio_tpu/obs/provenance.py"
    baselined = [
        e for e in Baseline.load(BASELINE).entries if e.file == prov_file
    ]
    assert baselined == []


def test_lifecycle_modules_lint_clean_with_zero_pragmas():
    """The model-lifecycle package (generation store, canary, controller)
    decides what model serves production traffic: it must be `pio
    check`-clean — including the new PIO-RES003 direct-persistence-write
    rule — with NO pragma suppressions and NO baseline entries."""
    report = analyze_paths([PACKAGE / "lifecycle"], root=REPO_ROOT)
    assert report.errors == []
    assert report.findings == [], "\n".join(f.text() for f in report.findings)
    assert report.pragma_suppressed == 0
    baselined = [
        e
        for e in Baseline.load(BASELINE).entries
        if e.file.startswith("predictionio_tpu/lifecycle/")
    ]
    assert baselined == []


def test_storage_modules_satisfy_res003():
    """Every data/storage backend honors the tmp-write + atomic-rename
    contract (PIO-RES003) with zero pragmas — the crash-safety floor the
    lifecycle generation manifest is built on."""
    report = analyze_paths([PACKAGE / "data" / "storage"], root=REPO_ROOT)
    res003 = [f for f in report.findings if f.rule == "PIO-RES003"]
    assert res003 == [], "\n".join(f.text() for f in res003)


def test_device_module_lint_clean_with_zero_pragmas():
    """The device-efficiency module runs on the serving hot path (wave
    timeline marks, signature accounting per wave) and is imported by every
    daemon through obs.http: it must be `pio check`-clean with NO pragma
    suppressions and NO baseline entries — same bar as the rest of obs/."""
    report = analyze_paths([PACKAGE / "obs" / "device.py"], root=REPO_ROOT)
    assert report.errors == []
    assert report.findings == [], "\n".join(f.text() for f in report.findings)
    assert report.pragma_suppressed == 0
    device_file = "predictionio_tpu/obs/device.py"
    baselined = [
        e for e in Baseline.load(BASELINE).entries if e.file == device_file
    ]
    assert baselined == []


def test_disttrace_modules_lint_clean_with_zero_pragmas():
    """The distributed-tracing pair — disttrace.py (fragment collection on
    every finished root span) and timeline.py (the assembler) — runs on
    every traced request and inside the collector tooling: it must be `pio
    check`-clean with NO pragma suppressions and NO baseline entries —
    same bar as the rest of obs/."""
    files = [
        PACKAGE / "obs" / "disttrace.py",
        PACKAGE / "obs" / "timeline.py",
    ]
    report = analyze_paths(files, root=REPO_ROOT)
    assert report.errors == []
    assert report.findings == [], "\n".join(f.text() for f in report.findings)
    assert report.pragma_suppressed == 0
    names = {
        "predictionio_tpu/obs/disttrace.py",
        "predictionio_tpu/obs/timeline.py",
    }
    baselined = [
        e for e in Baseline.load(BASELINE).entries if e.file in names
    ]
    assert baselined == []


def test_hostprofile_modules_lint_clean_with_zero_pragmas():
    """The host-profiling layer — sampling.py (a pass per period over
    every thread), contention.py (wrapping the process's hottest locks),
    hotpath.py (per-request stage attribution), capacity.py (the scrape-
    time headroom join) — must be `pio check`-clean with NO pragma
    suppressions and NO baseline entries — same bar as the rest of obs/."""
    files = [
        PACKAGE / "obs" / "sampling.py",
        PACKAGE / "obs" / "contention.py",
        PACKAGE / "obs" / "hotpath.py",
        PACKAGE / "obs" / "capacity.py",
    ]
    report = analyze_paths(files, root=REPO_ROOT)
    assert report.errors == []
    assert report.findings == [], "\n".join(f.text() for f in report.findings)
    assert report.pragma_suppressed == 0
    names = {
        "predictionio_tpu/obs/sampling.py",
        "predictionio_tpu/obs/contention.py",
        "predictionio_tpu/obs/hotpath.py",
        "predictionio_tpu/obs/capacity.py",
    }
    baselined = [
        e for e in Baseline.load(BASELINE).entries if e.file in names
    ]
    assert baselined == []


def test_fleet_modules_lint_clean_with_zero_pragmas():
    """The fleet layer — membership.py (replica registry + prober),
    router.py (the proxy hot path), autoscaler.py (the capacity-loop
    controller) — must be `pio check`-clean with NO pragma suppressions
    and NO baseline entries: the router forwards every serving request,
    so a busy-wait, an un-timed socket, or an unlocked mutation here is a
    fleet-wide defect, not a module-local one."""
    files = [
        PACKAGE / "fleet" / "__init__.py",
        PACKAGE / "fleet" / "membership.py",
        PACKAGE / "fleet" / "router.py",
        PACKAGE / "fleet" / "autoscaler.py",
    ]
    report = analyze_paths(files, root=REPO_ROOT)
    assert report.errors == []
    assert report.findings == [], "\n".join(f.text() for f in report.findings)
    assert report.pragma_suppressed == 0
    names = {
        "predictionio_tpu/fleet/__init__.py",
        "predictionio_tpu/fleet/membership.py",
        "predictionio_tpu/fleet/router.py",
        "predictionio_tpu/fleet/autoscaler.py",
    }
    baselined = [
        e for e in Baseline.load(BASELINE).entries if e.file in names
    ]
    assert baselined == []


def test_fast_path_modules_lint_clean_with_zero_pragmas():
    """PR 12's hot-path layer — ops/topk.py (the fused kernel serving
    every wave), parallel/device_cache.py (consulted per query under the
    serving locks), and server/microbatch.py (the pipelined dispatcher) —
    must be `pio check`-clean with NO pragma suppressions and NO baseline
    entries: a pre-fence sync (PIO-JAX007), a busy-wait, or an unlocked
    mutation here taxes every request in the process."""
    files = [
        PACKAGE / "ops" / "topk.py",
        PACKAGE / "parallel" / "device_cache.py",
        PACKAGE / "server" / "microbatch.py",
    ]
    report = analyze_paths(files, root=REPO_ROOT)
    assert report.errors == []
    assert report.findings == [], "\n".join(f.text() for f in report.findings)
    assert report.pragma_suppressed == 0
    names = {
        "predictionio_tpu/ops/topk.py",
        "predictionio_tpu/parallel/device_cache.py",
        "predictionio_tpu/server/microbatch.py",
    }
    baselined = [
        e for e in Baseline.load(BASELINE).entries if e.file in names
    ]
    assert baselined == []


def test_conc003_recognizes_contended_lock_wrappers():
    """Adopting ContendedLock/ContendedCondition on a hot lock must NOT
    silently retire the unlocked-mutation check for the state it guards:
    the wrappers count as lock constructors for PIO-CONC003, and the real
    adopters (MicroBatcher, admission, quality, generations, disttrace)
    stay clean under the stricter rule."""
    from predictionio_tpu.analysis.analyzer import analyze_source

    src = (
        "from predictionio_tpu.obs.contention import ContendedLock\n"
        "\n"
        "\n"
        "class Box:\n"
        "    def __init__(self):\n"
        "        self._lock = ContendedLock('box')\n"
        "        self.items = []\n"
        "\n"
        "    def add(self, x):\n"
        "        with self._lock:\n"
        "            self.items.append(x)\n"
        "\n"
        "    def sneaky(self, x):\n"
        "        self.items.append(x)\n"
    )
    findings = analyze_source(src, "contended_box.py")
    assert [(f.rule, f.line) for f in findings] == [("PIO-CONC003", 14)]

    adopters = [
        PACKAGE / "server" / "microbatch.py",
        PACKAGE / "resilience" / "admission.py",
        PACKAGE / "obs" / "quality.py",
        PACKAGE / "obs" / "disttrace.py",
        PACKAGE / "lifecycle" / "generations.py",
    ]
    report = analyze_paths(adopters, root=REPO_ROOT)
    assert report.errors == []
    remaining, _ = Baseline.load(BASELINE).filter(report.findings)
    assert remaining == [], "\n".join(f.text() for f in remaining)


def test_trace_assemble_smoke():
    """Tier-1 smoke of the trace assembler's CI-gateable entry point:
    `pio trace --json` round-trips the recorded two-process fragment set in
    tests/fixtures/disttrace/ — deterministic, no servers needed.  The full
    CLI contract lives in tests/test_disttrace.py."""
    import contextlib
    import io
    import json

    from predictionio_tpu.tools.cli import main

    fixture = (
        REPO_ROOT / "tests" / "fixtures" / "disttrace" / "fragments.json"
    )
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = main(["trace", "fixture01", "--file", str(fixture), "--json"])
    assert rc == 0
    body = json.loads(out.getvalue())
    assert body["span_count"] == 5
    assert body["processes"] == [
        "predictionserver:4242", "storage-server:4243",
    ]
    # the daemon's root hangs under the serving process's call-site span
    root = body["spans"][0]
    mb = root["children"][0]
    storage = next(
        c for c in mb["children"] if c["name"] == "storage.remote"
    )
    assert [c["name"] for c in storage["children"]] == [
        "http.storage-server"
    ]
    # an unknown trace id is a loud exit-1, not an empty render
    with contextlib.redirect_stdout(io.StringIO()), \
            contextlib.redirect_stderr(io.StringIO()):
        assert (
            main(["trace", "nope", "--file", str(fixture), "--json"]) == 1
        )


def test_profiler_capture_runs_off_request_thread():
    """PIO-CONC-aware gate for /debug/profile: the profiler module must be
    free of concurrency findings (no busy-waits, no blocking calls hidden in
    async defs), and the capture wait must structurally live on a dedicated
    background thread — the HTTP handler only arms the trace.  A profiler
    that sleeps N seconds on a request thread would pin an executor slot for
    the whole capture."""
    import ast

    report = analyze_paths([PACKAGE / "obs" / "profiler.py"], root=REPO_ROOT)
    conc = [f for f in report.findings if f.rule.startswith("PIO-CONC")]
    assert conc == [], "\n".join(f.text() for f in conc)
    # structural: start() hands the wait to a thread and never waits itself,
    # _finish (the waiter) runs nowhere but on that thread.  Asserted on the
    # AST of ProfilerController so unrelated edits can't false-positive.
    tree = ast.parse((PACKAGE / "obs" / "profiler.py").read_text())
    cls = next(
        n
        for n in ast.walk(tree)
        if isinstance(n, ast.ClassDef) and n.name == "ProfilerController"
    )
    methods = {
        n.name: n for n in cls.body if isinstance(n, ast.FunctionDef)
    }
    start_src = ast.unparse(methods["start"])
    assert "threading.Thread" in start_src and "daemon=True" in start_src
    assert "_finish" in start_src  # the thread target is the waiter
    assert ".wait(" not in start_src  # start() itself never blocks
    assert ".wait(" in ast.unparse(methods["_finish"])  # the thread does


def test_bundled_engine_contracts_gate():
    """DASE pre-flight part of the gate: every bundled engine factory
    passes the contract check."""
    from predictionio_tpu.analysis.contract import check_engine_contract
    from predictionio_tpu.core.engine import engine_registry
    from predictionio_tpu.tools.cli import _load_engine_modules

    _load_engine_modules()
    names = engine_registry.names()
    assert set(names) >= {
        "classification",
        "ecommerce",
        "ncf",
        "recommendation",
        "similarproduct",
    }
    for name in names:
        findings = check_engine_contract(name)
        assert findings == [], f"{name}:\n" + "\n".join(
            f.text() for f in findings
        )


def test_alert_modules_lint_clean_with_zero_pragmas():
    """PR 14's watch loop — obs/alerts.py (the evaluator ticking against
    the hot registries), obs/incident.py (the black-box recorder writing
    under the serving process), fleet/federation.py (the router-side
    fan-in blocking a serving thread per aggregation) — must be
    `pio check`-clean with NO pragma suppressions and NO baseline entries:
    a busy-wait, an un-timed fetch, or an unlocked mutation in the layer
    that RUNS DURING INCIDENTS would fail exactly when it matters."""
    files = [
        PACKAGE / "obs" / "alerts.py",
        PACKAGE / "obs" / "incident.py",
        PACKAGE / "fleet" / "federation.py",
    ]
    report = analyze_paths(files, root=REPO_ROOT)
    assert report.errors == []
    assert report.findings == [], "\n".join(f.text() for f in report.findings)
    assert report.pragma_suppressed == 0
    names = {
        "predictionio_tpu/obs/alerts.py",
        "predictionio_tpu/obs/incident.py",
        "predictionio_tpu/fleet/federation.py",
    }
    baselined = [
        e for e in Baseline.load(BASELINE).entries if e.file in names
    ]
    assert baselined == []


def test_incident_cli_smoke():
    """Tier-1 smoke of the incident verb against the committed fixture
    bundle: `pio incident list|show|export` all work offline, `show`
    renders the exemplar waterfall from the recorded fragments, and
    `pio trace --file <bundle>` assembles the same trace — the full
    contract lives in tests/test_alerts.py."""
    import contextlib
    import io
    import json

    from predictionio_tpu.tools.cli import main

    fdir = REPO_ROOT / "tests" / "fixtures" / "incidents"

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = main(["incident", "list", "--dir", str(fdir)])
    assert rc == 0
    assert "inc-fixture01-breaker-open-001" in out.getvalue()
    assert "rule=breaker_open" in out.getvalue()

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = main(
            ["incident", "show", "inc-fixture01", "--dir", str(fdir)]
        )
    assert rc == 0
    text = out.getvalue()
    assert "breaker_open{storage:127.0.0.1:7070}" in text
    assert "severity=critical" in text
    assert "storage.remote" in text  # the offline waterfall rendered
    assert "injected fault" in text

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = main(
            [
                "incident", "export", "inc-fixture01",
                "--dir", str(fdir), "--perfetto", "-",
            ]
        )
    assert rc == 0
    chrome = json.loads(out.getvalue())
    names = {e.get("name") for e in chrome["traceEvents"]}
    assert "storage.remote" in names

    # the bundle doubles as a disttrace fragment file
    bundle = fdir / "inc-fixture01-breaker-open-001.json"
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = main(["trace", "fixture01", "--file", str(bundle), "--json"])
    assert rc == 0
    assert json.loads(out.getvalue())["span_count"] == 3


# -- whole-program concurrency gate (PIO-LOCK*, PIO-JAX008) -------------------


def _package_program():
    """The package's call/lock graph, built once per test run."""
    from predictionio_tpu.analysis.analyzer import iter_python_files
    from predictionio_tpu.analysis.callgraph import build_program
    from predictionio_tpu.analysis.rules import parse_module

    mods = []
    for path in iter_python_files([PACKAGE]):
        rel = path.resolve().relative_to(REPO_ROOT).as_posix()
        mods.append(parse_module(path, rel, path.read_text()))
    return build_program(mods)


def test_whole_program_analysis_modules_lint_clean_with_zero_pragmas():
    """The analyzer's own whole-program layer — callgraph.py (the engine),
    rules_locks.py (the deadlock rules), cache.py (the check-result
    cache) — must be `pio check`-clean with NO pragma suppressions and NO
    baseline entries: the tool that gates the package gets no exemptions
    from itself."""
    files = [
        PACKAGE / "analysis" / "callgraph.py",
        PACKAGE / "analysis" / "rules_locks.py",
        PACKAGE / "analysis" / "cache.py",
    ]
    report = analyze_paths(files, root=REPO_ROOT)
    assert report.errors == []
    assert report.findings == [], "\n".join(f.text() for f in report.findings)
    assert report.pragma_suppressed == 0
    names = {
        "predictionio_tpu/analysis/callgraph.py",
        "predictionio_tpu/analysis/rules_locks.py",
        "predictionio_tpu/analysis/cache.py",
    }
    baselined = [
        e for e in Baseline.load(BASELINE).entries if e.file in names
    ]
    assert baselined == []


def test_no_lock_order_findings_package_wide():
    """The deadlock gate: zero PIO-LOCK001/PIO-LOCK002 findings across the
    whole package — not even baselined ones.  A justified baseline entry
    is acceptable for a sync heuristic (JAX008's one host-side .item()),
    never for a lock-order inversion or a blocking call under a lock."""
    report = _report()
    lock = [f for f in report.findings if f.rule.startswith("PIO-LOCK")]
    assert lock == [], "\n".join(f.text() for f in lock)
    baselined = [
        e
        for e in Baseline.load(BASELINE).entries
        if e.rule.startswith("PIO-LOCK")
    ]
    assert baselined == []


def test_jax008_package_findings_all_justified():
    """PIO-JAX008 over the package: every finding is the single curated
    baseline entry (the external engine's host-side .item()), nothing
    unexplained."""
    report = _report()
    jax8 = [f for f in report.findings if f.rule == "PIO-JAX008"]
    remaining, _ = Baseline.load(BASELINE).filter(jax8)
    assert remaining == [], "\n".join(f.text() for f in remaining)
    entries = [
        e for e in Baseline.load(BASELINE).entries if e.rule == "PIO-JAX008"
    ]
    assert [e.file for e in entries] == [
        "predictionio_tpu/models/external/engine.py"
    ]


def test_static_lock_graph_is_acyclic_on_the_package():
    """The package's own acquisition graph has no 2-cycles and no larger
    SCC cycles — the property PIO-LOCK001 enforces, asserted directly on
    the graph so a report-formatting bug cannot mask a real inversion."""
    program = _package_program()
    edges = {(e.src, e.dst) for e in program.lock_edges()}
    assert edges, "lock graph empty: the builder stopped seeing the package"
    inverted = [(a, b) for a, b in edges if (b, a) in edges]
    assert inverted == []


def test_witness_e2e_serving_exercise_zero_violations():
    """Chaos-adjacent e2e for the runtime witness: with the witness
    enabled, hammer the ContendedLock adopters the serving process runs
    per request — microbatch waves from many concurrent callers, quality
    observations, admission decisions, metrics scrapes — then assert the
    witness saw ZERO lock-order inversions and that every executed edge
    lies inside the static acquisition graph's witness allowlist."""
    import asyncio
    import threading

    from predictionio_tpu.obs import contention
    from predictionio_tpu.obs.metrics import MetricsRegistry
    from predictionio_tpu.obs.quality import QualityMonitor
    from predictionio_tpu.resilience.admission import AdmissionController
    from predictionio_tpu.server.microbatch import MicroBatcher

    w = contention.enable_witness()
    try:
        reg = MetricsRegistry()
        quality = QualityMonitor(registry=reg)
        admission = AdmissionController(max_inflight=8, registry=reg)

        def batch_fn(items):
            return [x * 2 for x in items]

        async def one_caller(b, n):
            return [await b.submit(i) for i in range(n)]

        def run_loop():
            async def main():
                b = MicroBatcher(batch_fn, max_batch=4, registry=reg)
                got = await asyncio.gather(
                    *(one_caller(b, 8) for _ in range(4))
                )
                b.close()
                return got

            asyncio.run(main())

        callers = [threading.Thread(target=run_loop) for _ in range(2)]
        for t in callers:
            t.start()
        for i in range(200):
            quality.observe_prediction(f"e2e-{i}", {"q": i}, {"p": i})
            if admission.try_acquire():
                admission.release()
        for t in callers:
            t.join()
        reg.render_prometheus()  # a scrape walks the registry under its lock

        snap = w.snapshot()
        assert snap["violations"] == [], snap["violations"]
        allow = _package_program().witness_edge_allowlist()
        assert w.edge_set() <= allow, (
            f"runtime edges {sorted(w.edge_set() - allow)} not in the "
            f"static allowlist {sorted(allow)}"
        )
    finally:
        contention.disable_witness()
