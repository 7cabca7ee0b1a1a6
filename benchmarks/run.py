"""Benchmark driver.

Entry points::

    python benchmarks/run.py [bench]            # paper-figure CSV suite
    python benchmarks/run.py dse [...]          # architecture DSE sweep
    python benchmarks/run.py serve-dse [...]    # one mapping-service request
    python benchmarks/run.py serve-http [...]   # the same service over HTTP
    python benchmarks/run.py dse-worker [...]   # join a distributed sweep
    python benchmarks/run.py dse-coordinator [...]  # drive one
    python benchmarks/run.py obs-report [...]   # render saved telemetry
    python benchmarks/run.py obs-profile [...]  # analyze a span trace

All also work as ``python -m benchmarks.run`` with ``PYTHONPATH=src``;
run as a plain script the repo root and ``src/`` are bootstrapped onto
``sys.path``. The ``bench`` suite prints ``name,us_per_call,derived`` CSV
(set ``BENCH_FULL=1`` for paper-scale budgets); perf-relevant rows are
mirrored into ``BENCH_search.json``. The ``dse`` subcommand co-searches
PIM architectures x overlap mappings (``repro.dse``), prints the Pareto
frontier and writes a resumable JSONL journal — re-running a finished
sweep performs zero new mapping searches. ``dse --distributed N`` runs
the same sweep through the shared-dir work-stealing subsystem
(``repro.dse.distrib``) with N local worker processes; the
``dse-worker``/``dse-coordinator`` pair does the same across real
processes or machines sharing one directory (DESIGN.md Section 10).
``serve-dse`` answers one deployment request through the mapping
service (``repro.serve.MappingService``, DESIGN.md Section 11) — an
HTTP-less local client whose repeat invocations are served from the
service journal with zero new mapping searches. ``serve-http`` binds
the same service to a listening socket (``repro.serve.transport``,
DESIGN.md Section 13): POST /v1/mapping, GET /v1/metrics (Prometheus
text), GET /v1/healthz — with request coalescing, a shared
cross-request overlap engine, and 429 load-shed past ``--max-pending``
waiting requests. Every subcommand takes
``--trace-out PATH`` / ``--metrics-out PATH`` (``repro.obs``): spans go
to a JSONL trace, the end-of-run metrics snapshot to a JSON file that
``obs-report`` renders as cache hit rates, latency percentiles and
fleet/service counters (``--prometheus`` for scrape-format text).
``obs-profile`` analyzes the span JSONL a ``--trace-out`` run wrote:
self/total-time attribution per span name, the critical path, and
optional Chrome trace-event JSON (``--chrome-out``, loadable in
Perfetto / chrome://tracing) and folded-stack flamegraph text
(``--folded-out``).
"""
import argparse
import dataclasses
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _obs_flags(p: argparse.ArgumentParser) -> argparse.ArgumentParser:
    """Attach the shared observability flags (``repro.obs``) to a
    subcommand parser. Giving either path flag turns telemetry on for
    the run; with neither, the process keeps the zero-overhead no-op
    default."""
    g = p.add_argument_group("observability (repro.obs)")
    g.add_argument("--trace-out", default=None, metavar="PATH",
                   help="write tracing spans as JSONL to PATH "
                        "(enables telemetry for this run)")
    g.add_argument("--metrics-out", default=None, metavar="PATH",
                   help="write the end-of-run metrics snapshot as JSON "
                        "to PATH (enables telemetry; defaults to "
                        "dse_runs/obs_metrics.json whenever telemetry "
                        "is on) — render it with 'run.py obs-report'")
    g.add_argument("--obs-sample", type=int, default=1, metavar="N",
                   help="keep every Nth span per span name "
                        "(deterministic stride, never RNG; metrics "
                        "counters are always exact)")
    return p


DEFAULT_METRICS_OUT = os.path.join("dse_runs", "obs_metrics.json")


def _setup_obs(args):
    """Enable process-wide telemetry per the CLI flags; returns a
    finalizer that writes the registry snapshot to ``--metrics-out``
    and turns telemetry back off (pass ``extra=...`` to merge
    additional top-level keys — e.g. the serve flight recorder — into
    the saved snapshot). With no obs flags the finalizer is a no-op
    and telemetry stays disabled."""
    import json
    trace_out = getattr(args, "trace_out", None)
    metrics_out = getattr(args, "metrics_out", None)
    if not trace_out and not metrics_out:
        return lambda extra=None: None
    from repro import obs
    metrics_out = metrics_out or DEFAULT_METRICS_OUT
    obs.enable(trace_path=trace_out,
               sample_every=max(1, getattr(args, "obs_sample", 1)))

    def finish(extra=None) -> None:
        reg = obs.registry()
        snap = reg.snapshot() if reg is not None else {}
        if extra:
            snap.update(extra)
        obs.disable()          # flushes + closes the trace sink
        d = os.path.dirname(metrics_out)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(metrics_out, "w", encoding="utf-8") as fh:
            json.dump(snap, fh, sort_keys=True)
            fh.write("\n")
        msg = f"obs: metrics -> {metrics_out}"
        if trace_out:
            msg += f" trace -> {trace_out}"
        print(msg)

    return finish


def _print_fleet(stats) -> None:
    """One-line fleet-health summary after a distributed sweep (the
    worker counters used to die with the worker processes)."""
    fleet = (stats or {}).get("fleet")
    if not fleet:
        return

    def fmt(v):
        return f"{v:.4g}" if isinstance(v, float) else str(v)

    print("dse: fleet " + " ".join(f"{k}={fmt(v)}"
                                   for k, v in sorted(fleet.items())))


def obs_report_main(argv) -> None:
    """Render a saved metrics snapshot (``--metrics-out``) as the
    human-readable observability report, or as Prometheus text
    exposition for scraping."""
    import json
    from repro import obs

    p = argparse.ArgumentParser(
        prog="run.py obs-report",
        description="Render a repro.obs metrics snapshot (cache hit "
                    "rates, latency percentiles, fleet/service "
                    "counters) written by --metrics-out.")
    p.add_argument("--metrics", default=DEFAULT_METRICS_OUT,
                   metavar="PATH", help="snapshot JSON to render "
                   "(default: %(default)s)")
    p.add_argument("--prometheus", action="store_true",
                   help="emit Prometheus text exposition instead of "
                        "the text report")
    args = p.parse_args(argv)
    try:
        with open(args.metrics, "r", encoding="utf-8") as fh:
            snap = json.load(fh)
    except FileNotFoundError:
        print(f"obs-report: no snapshot at {args.metrics} — run a "
              "subcommand with --metrics-out/--trace-out first",
              file=sys.stderr)
        sys.exit(2)
    except ValueError as e:
        # empty or truncated snapshot (e.g. a crashed run) — report,
        # don't traceback
        print(f"obs-report: {args.metrics} is not a metrics snapshot "
              f"({e})", file=sys.stderr)
        sys.exit(2)
    if not isinstance(snap, dict):
        print(f"obs-report: {args.metrics} is not a metrics snapshot "
              "(expected a JSON object)", file=sys.stderr)
        sys.exit(2)
    render = obs.render_prometheus if args.prometheus else obs.render_report
    sys.stdout.write(render(snap))


def obs_profile_main(argv) -> None:
    """Analyze a span JSONL trace (``--trace-out``): per-span-name
    self/total-time attribution, the critical path, and optional
    Chrome trace-event / folded-flamegraph exports."""
    from repro.obs import profile as obs_profile

    p = argparse.ArgumentParser(
        prog="run.py obs-profile",
        description="Trace analytics for a repro.obs span JSONL: "
                    "where did the run's wall clock go (self-time "
                    "attribution, critical path), plus Chrome "
                    "trace-event JSON (Perfetto / chrome://tracing) "
                    "and folded-stack flamegraph exports.")
    p.add_argument("--trace", required=True, metavar="PATH",
                   help="span JSONL written by --trace-out")
    p.add_argument("--chrome-out", default=None, metavar="PATH",
                   help="write Chrome trace-event JSON to PATH")
    p.add_argument("--folded-out", default=None, metavar="PATH",
                   help="write folded stacks ('a;b;c <us>' lines, "
                        "flamegraph.pl-compatible) to PATH")
    p.add_argument("--top", type=int, default=15, metavar="N",
                   help="rows in the self-time table "
                        "(default: %(default)s)")
    args = p.parse_args(argv)
    if not os.path.exists(args.trace):
        print(f"obs-profile: no trace at {args.trace} — run a "
              "subcommand with --trace-out first", file=sys.stderr)
        sys.exit(2)
    trace = obs_profile.parse_trace(args.trace)
    sys.stdout.write(obs_profile.render_profile(trace, top=args.top))
    if args.chrome_out:
        obs_profile.write_chrome_trace(trace, args.chrome_out)
        print(f"obs-profile: chrome trace -> {args.chrome_out} "
              "(load in Perfetto or chrome://tracing)")
    if args.folded_out:
        obs_profile.write_folded(trace, args.folded_out)
        print(f"obs-profile: folded stacks -> {args.folded_out}")


def bench_main(argv=()) -> None:
    args = _obs_flags(argparse.ArgumentParser(
        prog="run.py bench",
        description="Paper-figure CSV suite.")).parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    finish_obs = _setup_obs(args)
    try:
        _bench_suite()
    finally:
        finish_obs()


def _bench_suite() -> None:
    # one function per paper table/figure
    from benchmarks import (bench_kernels, bench_search, bench_serve,
                            paper_figs)

    benches = [
        bench_search.scoring_throughput,
        bench_search.obs_overhead,
        bench_search.e2e_speedup,
        bench_search.search_wall,
        bench_search.objective_frontier,
        bench_search.worker_scaling,
        bench_serve.serve_latency,
        paper_figs.fig4_motivation,
        paper_figs.fig10_overall,
        paper_figs.fig11_vs_overlapim,
        paper_figs.fig12_perlayer,
        paper_figs.fig13_memcap,
        paper_figs.fig14_runtime,
        paper_figs.fig15_search_methods,
        paper_figs.fig16_reram,
        paper_figs.fig17_bert,
        paper_figs.sec4f_dataspace_generation,
        bench_kernels.kernels,
    ]
    print("name,us_per_call,derived")
    t0 = time.time()
    failures = 0
    for bench in benches:
        try:
            for row in bench():
                print(row, flush=True)
        except Exception as e:  # keep the suite going; report at the end
            failures += 1
            print(f"{bench.__name__},0.000,ERROR:{e!r}", flush=True)
    print(f"# total_wall_s={time.time() - t0:.1f} failures={failures}",
          flush=True)
    if failures:
        sys.exit(1)


def _dse_parser() -> argparse.ArgumentParser:
    from repro.dse import EXPLORERS, SPACES
    from repro.core.search import MODES, OBJECTIVES, STRATEGIES

    p = argparse.ArgumentParser(
        prog="run.py dse",
        description="Co-search PIM architectures x overlap mappings.")
    p.add_argument("--network", default="resnet18",
                   help="network name, a zoo scenario "
                        "('<arch>[:phase][@length][xblocks]', e.g. "
                        "deepseek_moe_16b:prefill@2048 — see 'run.py "
                        "workloads'), or 'all' for "
                        "resnet18/vgg16/bert_encoder x all modes")
    p.add_argument("--family", default="dram_pim", choices=sorted(SPACES))
    p.add_argument("--mode", default="transform", choices=MODES)
    p.add_argument("--strategy", default="forward", choices=STRATEGIES)
    p.add_argument("--objective", default="latency", choices=OBJECTIVES,
                   help="mapping-search objective (energy/edp/blend make "
                        "the sweep energy-aware)")
    p.add_argument("--blend-alpha", type=float, default=0.5,
                   help="energy weight of the 'blend' objective")
    p.add_argument("--explorer", default="evolve", choices=EXPLORERS)
    p.add_argument("--budget", type=int, default=64,
                   help="design points to propose (journal hits included)")
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--candidates", type=int, default=8,
                   help="mapping candidates per layer per point")
    p.add_argument("--max-steps", type=int, default=2048)
    p.add_argument("--workers", type=int, default=0,
                   help="process-pool size (0 = serial, shared engine)")
    p.add_argument("--journal", default=None,
                   help="JSONL journal path (default: "
                        "dse_runs/<family>_<network>_<mode>.jsonl)")
    p.add_argument("--distributed", type=int, default=0, metavar="N",
                   help="run the sweep through the distributed subsystem "
                        "with N local worker processes sharing a journal "
                        "directory (repro.dse.distrib)")
    p.add_argument("--shared-dir", default=None,
                   help="shared journal directory for --distributed / "
                        "dse-coordinator (default: <journal path with "
                        ".jsonl replaced by .shared>)")
    p.add_argument("--batch-size", type=int, default=1,
                   help="design points per distributed work batch")
    p.add_argument("--lease-ttl", type=float, default=60.0,
                   help="seconds before a silent worker's batch lease "
                        "expires and peers may steal it")
    p.add_argument("--compact-journal", action="store_true",
                   help="compact the journal (drop superseded later-wins "
                        "duplicates and any truncated tail) and exit")
    p.add_argument("--frontier-out", default=None, metavar="PATH",
                   help="also write the frontier's canonical JSON to "
                        "PATH (byte-comparable across runs/workers)")
    return _obs_flags(p)


def _dse_config_from_args(args):
    """THE args -> DSEConfig mapping — every scoring-relevant CLI flag
    is wired here once, so `dse`, `dse --distributed` and
    `dse-coordinator` can never score the same sweep under silently
    different configs (the bit-identical-frontier contract)."""
    from repro.dse import DSEConfig
    return DSEConfig(
        family=args.family, network=args.network, mode=args.mode,
        strategy=args.strategy, explorer=args.explorer,
        budget=args.budget, seed=args.seed, n_candidates=args.candidates,
        max_steps=args.max_steps, objective=args.objective,
        blend_alpha=args.blend_alpha, workers=args.workers)


def _compact_journal(journal_path=None, shared_dir=None) -> None:
    from repro.dse import RunJournal, SharedDirBackend
    if shared_dir is not None:
        j, where = RunJournal(backend=SharedDirBackend(shared_dir)), \
            shared_dir
    else:
        j, where = RunJournal(journal_path), journal_path
    before, after = j.compact()
    print(f"dse: compacted {where}: {before} lines -> {after}")


def _write_frontier(res, path) -> None:
    if not path:
        return
    with open(path, "w", encoding="utf-8") as fh:
        fh.write(res.frontier.canonical_json() + "\n")
    print(f"dse: frontier written to {path}")


def dse_main(argv) -> None:
    args = _dse_parser().parse_args(argv)
    from benchmarks import record
    from repro.dse import (best_arch_table, execute_sweep, frontier_table,
                           journal_template, network_token, objective_tag,
                           shared_dir_for, summarize, sweep_networks,
                           sweep_summary)

    # one journal-naming scheme for both branches (repro.dse.driver —
    # shared with the mapping service); a literal --journal path has no
    # {placeholders} and formats to itself
    obj_tag = objective_tag(args.objective, args.blend_alpha)
    template = args.journal or journal_template(
        args.family, args.objective, args.blend_alpha)

    base = _dse_config_from_args(args)

    # dse-journal key: objective-suffixed for non-latency sweeps so the
    # pre-energy entries keep tracking the latency trajectory
    def dse_key(net, mode) -> str:
        return f"{args.family}/{net}/{mode}" + (
            f"/{obj_tag}" if obj_tag else "")

    if args.network == "all":
        if args.distributed or args.compact_journal or args.frontier_out:
            print("--distributed/--compact-journal/--frontier-out need "
                  "a single --network, not 'all'", file=sys.stderr)
            sys.exit(2)
        base = dataclasses.replace(base, journal_path=template)
        results = sweep_networks(base)
        for (net, mode), res in sorted(results.items()):
            print(f"== {net} / {mode} ==")
            print(summarize(res))
            print(frontier_table(res.frontier))
            print()
            record.update_dse(dse_key(net, mode), sweep_summary(res))
        print(best_arch_table(results))
        return

    journal_path = template.format(network=network_token(args.network),
                                   mode=args.mode)
    shared_dir = args.shared_dir or shared_dir_for(journal_path)

    if args.compact_journal:
        if args.shared_dir or args.distributed:
            _compact_journal(shared_dir=shared_dir)
        else:
            _compact_journal(journal_path=journal_path)
        return

    cfg = dataclasses.replace(base, network=args.network,
                              journal_path=journal_path)
    finish_obs = _setup_obs(args)
    try:
        res = execute_sweep(cfg, distributed=args.distributed,
                            shared_dir=shared_dir if args.distributed
                            else None,
                            batch_size=args.batch_size,
                            lease_ttl_s=args.lease_ttl)
    finally:
        finish_obs()
    print(summarize(res))
    print(frontier_table(res.frontier))
    if args.distributed:
        print(f"dse: shared-dir={shared_dir} "
              f"workers={args.distributed} "
              f"batches={res.stats['batches']}")
        _print_fleet(res.stats)
    else:
        print(f"dse: journal={cfg.journal_path} entries={_journal_len(cfg)}")
    _write_frontier(res, args.frontier_out)
    record.update_dse(dse_key(args.network, args.mode),
                      sweep_summary(res))


def _journal_len(cfg) -> int:
    from repro.dse import RunJournal
    return len(RunJournal(cfg.journal_path))


def dse_worker_main(argv) -> None:
    """Join a distributed sweep knowing nothing but the shared dir."""
    from repro.dse.distrib import WorkerConfig, worker_loop

    p = argparse.ArgumentParser(
        prog="run.py dse-worker",
        description="Evaluate batches of a distributed DSE sweep until "
                    "the coordinator posts STOP. Point any number of "
                    "these (any machine) at one shared directory.")
    p.add_argument("--shared-dir", required=True)
    p.add_argument("--worker-id", default=None,
                   help="stable identity (default: pid + random)")
    p.add_argument("--lease-ttl", type=float, default=60.0)
    p.add_argument("--poll", type=float, default=0.05)
    p.add_argument("--max-idle", type=float, default=900.0,
                   help="exit after this many idle seconds even without "
                        "a STOP (default 900 — bounds orphaned workers "
                        "whose sweep finished before they started; pass "
                        "0 for a standing fleet that only STOP ends)")
    args = p.parse_args(argv)
    stats = worker_loop(WorkerConfig(
        root=args.shared_dir, worker_id=args.worker_id,
        poll_s=args.poll, lease_ttl_s=args.lease_ttl,
        max_idle_s=args.max_idle if args.max_idle > 0 else None))
    print("dse-worker: " + " ".join(f"{k}={v}"
                                    for k, v in sorted(stats.items())))


def dse_coordinator_main(argv) -> None:
    """Drive a sweep; external dse-worker processes supply the compute."""
    p = _dse_parser()
    p.prog = "run.py dse-coordinator"
    p.add_argument("--timeout", type=float, default=3600.0,
                   help="seconds to wait for external workers to finish "
                        "all outstanding evaluations")
    args = p.parse_args(argv)
    if args.network == "all":
        print("dse-coordinator needs a single --network", file=sys.stderr)
        sys.exit(2)
    if not args.shared_dir:
        print("dse-coordinator requires --shared-dir", file=sys.stderr)
        sys.exit(2)
    if args.distributed or args.workers:
        print("dse-coordinator spawns no local workers; start "
              "'dse-worker --shared-dir ...' processes instead of "
              "passing --distributed/--workers", file=sys.stderr)
        sys.exit(2)
    if args.compact_journal:
        _compact_journal(shared_dir=args.shared_dir)
        return
    from repro.dse import DistribConfig, run_coordinator
    from repro.dse.report import frontier_table, summarize
    dist = DistribConfig(root=args.shared_dir, batch_size=args.batch_size,
                         lease_ttl_s=args.lease_ttl,
                         timeout_s=args.timeout)
    finish_obs = _setup_obs(args)
    try:
        res = run_coordinator(_dse_config_from_args(args), dist)
    finally:
        finish_obs()
    print(summarize(res))
    print(frontier_table(res.frontier))
    _print_fleet(res.stats)
    _write_frontier(res, args.frontier_out)


def serve_dse_main(argv) -> None:
    """HTTP-less local client of the mapping service: build one
    ``MappingRequest`` from flags (or ``--request-json``), answer it
    through a ``MappingService`` over a persistent journal, and print
    the response. Re-running an identical request is served from the
    journal cache with zero new mapping searches (``served_from=journal
    evaluated=0``)."""
    import json
    from repro.core.search import MODES, OBJECTIVES, STRATEGIES
    from repro.dse import EXPLORERS, SPACES

    p = argparse.ArgumentParser(
        prog="run.py serve-dse",
        description="Answer one deployment request ('best (arch, "
                    "mapping) for this network under this budget') "
                    "through the mapping service (repro.serve).")
    p.add_argument("--network", default="resnet18",
                   help="network name or zoo scenario (see 'run.py "
                        "workloads')")
    p.add_argument("--family", default="dram_pim", choices=sorted(SPACES))
    p.add_argument("--mode", default="transform", choices=MODES)
    p.add_argument("--strategy", default="forward", choices=STRATEGIES)
    p.add_argument("--objective", default="latency", choices=OBJECTIVES)
    p.add_argument("--blend-alpha", type=float, default=0.5)
    p.add_argument("--explorer", default="evolve", choices=EXPLORERS)
    p.add_argument("--budget", type=int, default=16)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--candidates", type=int, default=8)
    p.add_argument("--max-steps", type=int, default=2048)
    p.add_argument("--area-budget", type=float, default=None,
                   metavar="MM2", help="only deploy archs within this "
                   "area proxy (iso-area constraint)")
    p.add_argument("--deadline", type=float, default=None, metavar="S",
                   help="wall-clock bound; the response is the "
                        "best-so-far frontier when it expires")
    p.add_argument("--distributed", type=int, default=0, metavar="N",
                   help="fan the sweep out over N local worker "
                        "processes (large budgets)")
    p.add_argument("--include-mapping", action="store_true",
                   help="materialize the winner's per-layer loop nests "
                        "into the response")
    p.add_argument("--journal", default=None,
                   help="service journal path (default: "
                        "dse_runs/service.jsonl) — the cross-request "
                        "result cache")
    p.add_argument("--request-json", default=None, metavar="JSON",
                   help="full request as a JSON object (overrides the "
                        "per-field flags)")
    p.add_argument("--json", action="store_true",
                   help="print the full MappingResponse as JSON")
    _obs_flags(p)
    args = p.parse_args(argv)

    from repro.dse.driver import JOURNAL_ROOT
    from repro.serve import MappingRequest, MappingService
    if args.request_json:
        req = MappingRequest.from_dict(json.loads(args.request_json))
    else:
        req = MappingRequest(
            network=args.network, family=args.family, mode=args.mode,
            strategy=args.strategy, objective=args.objective,
            blend_alpha=args.blend_alpha, explorer=args.explorer,
            budget=args.budget, seed=args.seed,
            n_candidates=args.candidates, max_steps=args.max_steps,
            area_budget_mm2=args.area_budget, deadline_s=args.deadline,
            distributed=args.distributed,
            include_mapping=args.include_mapping)
    journal = args.journal or os.path.join(JOURNAL_ROOT, "service.jsonl")
    # telemetry before the service: it binds its registry at construction
    finish_obs = _setup_obs(args)
    svc = MappingService(journal_path=journal)
    try:
        resp = svc.request(req)
    finally:
        svc.close()
        finish_obs()
    print(f"serve-dse: request={resp.request_key[:12]} "
          f"status={resp.status} served_from={resp.served_from} "
          f"evaluated={resp.evaluated} from_journal={resp.from_journal} "
          f"deadline_hit={resp.deadline_hit} wall_s={resp.wall_s:.1f}")
    if resp.best is not None:
        print(f"serve-dse: best {resp.best['arch_name']} "
              f"latency_ms={resp.best['total_ns'] / 1e6:.3f} "
              f"energy_J={resp.best['energy_pj'] / 1e12:.1f} "
              f"area_mm2={resp.best['area_mm2']:.2f}")
    else:
        print("serve-dse: no scored arch fits the area budget "
              f"({req.area_budget_mm2} mm2)")
    print(f"serve-dse: frontier={len(resp.frontier_points)} points, "
          f"journal={journal}")
    if resp.mapping:
        for lay in resp.mapping:
            print(f"serve-dse: mapping {lay['layer']}: "
                  f"latency_ns={lay['latency_ns']:.0f} "
                  f"transformed={lay['transformed']}")
    if args.json:
        print(resp.to_json(indent=2))


def serve_http_main(argv) -> None:
    """Run the mapping service as an HTTP server (``repro.serve.
    transport``, DESIGN.md Section 13): POST /v1/mapping answers
    deployment requests with the same wire forms ``serve-dse`` prints,
    GET /v1/metrics scrapes the ``serve.*``/``engine.*`` counters in
    Prometheus text format, GET /v1/healthz is liveness. Serves until
    interrupted; SIGINT drains in-flight sweeps before exiting."""
    p = argparse.ArgumentParser(
        prog="run.py serve-http",
        description="Serve mapping requests over HTTP "
                    "(repro.serve.MappingHTTPServer).")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8099,
                   help="listening port (0 = ephemeral, printed at "
                        "startup)")
    p.add_argument("--journal", default=None,
                   help="service journal path (default: "
                        "dse_runs/service.jsonl) — the cross-request "
                        "result cache")
    p.add_argument("--max-workers", type=int, default=1, metavar="N",
                   help="concurrent sweep threads")
    p.add_argument("--max-pending", type=int, default=32, metavar="N",
                   help="admission cap: shed (HTTP 429) once N distinct "
                        "requests are waiting (0 = unbounded)")
    p.add_argument("--memo-cap", type=int, default=256, metavar="N",
                   help="LRU size of the response memo (and the "
                        "loop-nest cache)")
    p.add_argument("--persist-dir", default=None, metavar="DIR",
                   help="write-through the memo/nest caches to DIR so "
                        "a restarted server starts warm")
    p.add_argument("--compact-every", type=float, default=None,
                   metavar="S", help="background maintenance cadence: "
                   "compact the journal and persisted caches every S "
                   "seconds")
    p.add_argument("--bundle-cap", type=int, default=8, metavar="N",
                   help="arch bundles the shared overlap engine "
                        "retains across requests (LRU)")
    p.add_argument("--flight-cap", type=int, default=256, metavar="N",
                   help="per-request flight-recorder ring size "
                        "(GET /v1/debug/requests; 0 disables)")
    p.add_argument("--slow-threshold", type=float, default=1.0,
                   metavar="S", help="requests at/above S seconds keep "
                   "full detail in the slow ring")
    p.add_argument("--window", type=float, default=60.0, metavar="S",
                   help="sliding window (seconds) behind the recent "
                        "p50/p99 latency gauges (0 disables)")
    p.add_argument("--slo-target", type=float, default=None, metavar="S",
                   help="latency SLO target in seconds: publishes "
                        "serve.slo.ok/breach counters and the windowed "
                        "burn-rate gauge")
    p.add_argument("--slo-goal", type=float, default=0.99,
                   help="SLO goal fraction (default: %(default)s)")
    _obs_flags(p)
    args = p.parse_args(argv)

    from repro.dse.driver import JOURNAL_ROOT
    from repro.serve import MappingHTTPServer, MappingService
    journal = args.journal or os.path.join(JOURNAL_ROOT, "service.jsonl")
    # telemetry before the service: it binds its registry at construction
    finish_obs = _setup_obs(args)
    svc = MappingService(
        journal_path=journal,
        max_workers=args.max_workers,
        max_pending=args.max_pending or None,
        memo_cap=args.memo_cap, nest_cap=args.memo_cap,
        persist_dir=args.persist_dir,
        compact_every_s=args.compact_every,
        engine_bundle_cap=args.bundle_cap,
        flight_cap=args.flight_cap,
        slow_threshold_s=args.slow_threshold,
        window_s=args.window,
        slo_target_s=args.slo_target,
        slo_goal=args.slo_goal)
    server = MappingHTTPServer(svc, host=args.host, port=args.port)
    print(f"serve-http: listening on {server.url} journal={journal} "
          f"workers={args.max_workers} max_pending={args.max_pending}",
          flush=True)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        print("serve-http: draining...", flush=True)
    finally:
        server.close()
        # the saved snapshot carries the flight ring so obs-report can
        # render the per-request section offline
        finish_obs(extra={"flight": svc.flight.snapshot()}
                   if svc.flight.enabled else None)


def workloads_main(argv) -> None:
    """List the zoo scenarios the lowering layer serves (per-block layer
    and MAC counts, plus the whole-model block multiplier)."""
    p = argparse.ArgumentParser(
        prog="run.py workloads",
        description="List LLM workload scenarios (repro.workloads): "
                    "every zoo arch x {prefill, decode} lowered to "
                    "overlap-searchable LayerSpec networks. Any listed "
                    "name (or the grammar '<arch>[:phase][@length]"
                    "[xblocks]') works with 'dse --network', "
                    "'serve-dse --network' and a MappingRequest.")
    p.add_argument("--smoke", action="store_true",
                   help="list the reduced smoke configs (CPU-test scale)")
    p.add_argument("--arch", default=None,
                   help="only scenarios of this zoo arch")
    args = p.parse_args(argv)

    from repro.configs import get_config
    from repro.workloads import list_scenarios, parse_scenario, \
        lower_scenario
    print(f"{'scenario':44s} {'family':7s} {'layers':>6s} "
          f"{'macs/block':>14s} {'blocks':>6s} {'macs/model':>14s}")
    for name in list_scenarios(smoke=args.smoke):
        sc = parse_scenario(name)
        if args.arch and args.arch.replace("-", "_") not in (sc.arch_id,):
            continue
        cfg = sc.config()
        layers, _ = lower_scenario(sc)
        macs = sum(l.macs for l in layers)
        if cfg.family in ("hybrid", "audio"):
            # the lowered tranche mixes block kinds with different
            # repeat counts (SSM vs shared-attention / enc vs dec), so
            # a single whole-model multiplier would mislead
            blocks_s, total_s = "mixed", "-"
        else:
            blocks_s = str(max(1, cfg.n_layers))
            total_s = f"{macs * max(1, cfg.n_layers):,d}"
        print(f"{name:44s} {cfg.family:7s} {len(layers):6d} "
              f"{macs:14,d} {blocks_s:>6s} {total_s:>14s}")


def main() -> None:
    argv = sys.argv[1:]
    if argv and argv[0] == "dse":
        dse_main(argv[1:])
    elif argv and argv[0] == "serve-dse":
        serve_dse_main(argv[1:])
    elif argv and argv[0] == "serve-http":
        serve_http_main(argv[1:])
    elif argv and argv[0] == "dse-worker":
        dse_worker_main(argv[1:])
    elif argv and argv[0] == "dse-coordinator":
        dse_coordinator_main(argv[1:])
    elif argv and argv[0] == "obs-report":
        obs_report_main(argv[1:])
    elif argv and argv[0] == "obs-profile":
        obs_profile_main(argv[1:])
    elif argv and argv[0] == "workloads":
        workloads_main(argv[1:])
    elif not argv or argv[0] == "bench":
        bench_main(argv[1:] if argv else [])
    else:
        print(f"unknown subcommand {argv[0]!r}; use 'bench', 'dse', "
              "'serve-dse', 'serve-http', 'dse-worker', "
              "'dse-coordinator', 'obs-report', 'obs-profile' or "
              "'workloads'", file=sys.stderr)
        sys.exit(2)


if __name__ == '__main__':
    main()
