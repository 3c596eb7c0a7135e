"""The paper's technique as a framework feature: discover collective-
overlap design rules for OUR OWN train step.

The LM train step decomposes into an op-DAG (per-layer fwd/bwd compute,
per-layer gradient reduce-scatters, the optimizer update). "Streams" are
the TPU compute stream + ICI channels. The search portfolio (greedy
seeding → MCTS refinement → surrogate-screened exploitation) + the
machine model search the (emission order x channel assignment) space;
the decision tree then emits human-readable rules like "rs0 before
bwd2" or "rs1 different stream than bwd1" — exactly the paper's
output, for a 2026 workload.

With ``--space`` the same pipeline runs over any *registered* design
space instead of the train-step DAG: the paper's schedule spaces
(``spmv``, ``spmv_fine``, ``halo3d``) or the repo's own Pallas kernel
parameter grids (``flash_attention``, ``spmv_mulsum``, ``pack`` —
autotuned through the wall-clock runner, emitting block-size design
rules; ``demo`` is an analytic grid needing no JAX).

Usage: PYTHONPATH=src python examples/schedule_search.py
           [--arch qwen2.5-32b] [--layers 4] [--iters 600]
           [--space spmv|halo3d|flash_attention|...]
           [--strategy portfolio|mcts]
           [--backend sim|vectorized|pool|wallclock|rpc]
           [--hosts host:port,host:port]
           [--surrogate ridge|boost]
           [--acquisition argmin_topk|ucb|expected_improvement]
           [--rules [PATH]] [--store PATH]
           [--trace PATH] [--telemetry]
"""
import argparse

import repro.rules as R
import repro.search as S
from repro import obs
from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.driver import ACQUISITIONS
from repro.core.stepdag import StepCosts, train_step_dag, \
    with_comm_durations
from repro.launch.costs import HBM_BW, LINK_BW, PEAK_FLOPS
from repro.space import SPACES, ParamSpace, make_space


def costs_from_arch(arch: str, layers: int, tokens_per_chip: int,
                    tp: int = 16, dp: int = 16) -> StepCosts:
    cfg = get_config(arch)
    n_per_layer = cfg.active_param_count() / cfg.n_layers
    # Per-chip, per-(coarsened)-layer costs; `layers` coarse stages.
    coarse = cfg.n_layers / layers
    fwd_flops = 2 * n_per_layer * tokens_per_chip * coarse / tp
    fwd_bytes = fwd_flops / 50.0          # ~50 flops/byte at bf16
    grad_bytes = n_per_layer * coarse * 4 / tp * (dp - 1) / dp
    return StepCosts(fwd_flops=fwd_flops, bwd_flops=2 * fwd_flops,
                     fwd_bytes=fwd_bytes, bwd_bytes=2 * fwd_bytes,
                     grad_bytes=grad_bytes)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-32b")
    ap.add_argument("--layers", type=int, default=4,
                    help="coarse pipeline stages in the DAG")
    ap.add_argument("--iters", type=int, default=600)
    ap.add_argument("--channels", type=int, default=2)
    ap.add_argument("--space", choices=tuple(sorted(SPACES)),
                    default=None,
                    help="search a registered design space "
                         "(repro.space registry) instead of the "
                         "train-step DAG; kernel grids default to the "
                         "wall-clock runner")
    ap.add_argument("--strategy", choices=("portfolio", "mcts"),
                    default="portfolio",
                    help="portfolio = greedy seeding + MCTS refinement "
                         "+ surrogate-screened exploitation "
                         "(graph spaces only; kernel grids always "
                         "use mcts)")
    ap.add_argument("--backend",
                    choices=("sim", "vectorized", "pool", "wallclock",
                             "rpc"),
                    default=None,
                    help="evaluation engine (repro.engine registry); "
                         "all analytic backends are bit-identical — "
                         "a pure throughput choice. Default: sim for "
                         "analytic spaces, wallclock for kernel "
                         "grids (see src/repro/engine/README.md). "
                         "rpc requires --hosts")
    ap.add_argument("--hosts", default=None, metavar="H:P,H:P",
                    help="comma-separated host:port evaluation servers "
                         "for --backend rpc (each running python -m "
                         "repro.engine.server on a matching --space)")
    ap.add_argument("--batch-size", type=int, default=None,
                    help="schedules per propose() call; default 1 for "
                         "the sim backend (the paper's strictly "
                         "sequential loop) and 32 for vectorized/pool, "
                         "which only amortize across batches")
    ap.add_argument("--surrogate", choices=tuple(sorted(S.SURROGATES)),
                    default="ridge",
                    help="screening model for the portfolio's "
                         "exploitation phase (repro.search surrogate "
                         "registry; 'boost' = gradient-boosted trees)")
    ap.add_argument("--acquisition",
                    choices=tuple(sorted(ACQUISITIONS)),
                    default="argmin_topk",
                    help="how the candidate pool is ranked "
                         "(repro.driver acquisition registry; ucb / "
                         "expected_improvement add the boosted "
                         "ensemble's per-tree uncertainty — pair them "
                         "with --surrogate boost)")
    ap.add_argument("--store", default=None, metavar="PATH",
                    help="persistent content-addressed evaluation "
                         "store (repro.engine.EvalStore): base times "
                         "measured this run are appended, and a later "
                         "run on the same graph/machine replays them "
                         "as store hits without re-simulating — "
                         "warm-start across processes and backends")
    ap.add_argument("--rules", nargs="?", const="-", default=None,
                    metavar="PATH",
                    help="render the full design-rule report "
                         "(repro.rules.distill) to PATH, or to stdout "
                         "when given without a value")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome trace-event / Perfetto JSON "
                         "trace of the whole run (driver rounds, "
                         "evaluator batches, store traffic, distill "
                         "stages) to PATH — open it at "
                         "https://ui.perfetto.dev. Trace-enabled runs "
                         "attach an ephemeral evaluation store when "
                         "--store is not given, so the store layer "
                         "shows up in the trace (results are "
                         "byte-identical either way)")
    ap.add_argument("--telemetry", action="store_true",
                    help="print the telemetry summary table (span "
                         "walls, counters, gauges) after the run")
    args = ap.parse_args()
    enable_compile_cache()

    tel = None
    if args.trace or args.telemetry:
        exporters = [obs.PerfettoExporter(args.trace)] if args.trace \
            else []
        tel = obs.Telemetry(exporters=exporters)
        obs.set_current(tel)
    ephemeral_store = None
    if args.trace and args.store is None:
        # A pure observer: the store holds noiseless base times, and
        # cold runs with a store attached are byte-identical to
        # storeless ones (locked by tests/test_engine_store.py) — so a
        # throwaway store is a free way to get store-layer spans into
        # the trace.
        import tempfile
        ephemeral_store = tempfile.mkdtemp(prefix="repro-trace-")
        args.store = f"{ephemeral_store}/trace.evalstore"

    if args.space is not None:
        try:
            target = make_space(args.space, n_streams=args.channels)
        except TypeError:  # parameter grids take no n_streams
            target = make_space(args.space)
        graph = getattr(target, "graph", None)
        kind = "parameter grid" if isinstance(target, ParamSpace) \
            else "schedule space"
        print(f"design space {target.name!r} ({kind})")
    else:
        costs = costs_from_arch(args.arch, args.layers,
                                tokens_per_chip=16 * 4096 // 16)
        graph = with_comm_durations(train_step_dag(args.layers, costs),
                                    LINK_BW)
        target = graph
        print(f"train-step DAG for {args.arch}: "
              f"{graph.n_vertices()} ops, {args.layers} stages")

    kernel_grid = isinstance(target, ParamSpace) \
        and target.runner is not None
    if args.backend is None:
        args.backend = "wallclock" if kernel_grid else "sim"
    if args.batch_size is None:
        args.batch_size = 1 if args.backend == "sim" else 32
    backend_kwargs = None
    if args.backend == "rpc":
        if not args.hosts:
            ap.error("--backend rpc requires --hosts host:port[,...]")
        hosts = [h.strip() for h in args.hosts.split(",") if h.strip()]
        backend_kwargs = {"hosts": hosts}
        print(f"evaluation fleet: {len(hosts)} host(s) "
              f"({', '.join(hosts)})")
    elif args.hosts:
        ap.error("--hosts only applies to --backend rpc")

    if args.strategy == "portfolio" and graph is not None:
        strategy = S.PortfolioSearch(graph, args.channels, seed=0,
                                     surrogate=args.surrogate,
                                     acquisition=args.acquisition)
    else:  # graph-less spaces: the space-generic MCTS
        strategy = S.MCTSSearch(target, seed=0) if graph is None \
            else S.MCTSSearch(graph, args.channels, seed=0)
    res = S.run_search(target, strategy, budget=args.iters,
                       backend=args.backend, batch_size=args.batch_size,
                       backend_kwargs=backend_kwargs,
                       store_path=args.store)
    times = res.times_array()
    best, best_t = res.best()
    print(f"explored {len(res.schedules)} schedules "
          f"({res.n_proposed} evaluations, {res.cache_hits} memo hits); "
          f"best {times.min() * 1e3:.2f} ms, "
          f"worst {times.max() * 1e3:.2f} ms "
          f"({times.max() / times.min():.2f}x)")
    if args.store is not None:
        print(f"evaluation store {args.store}: {res.store_hits} warm "
              f"hits, {res.cache_misses} new measurements appended")
    if args.strategy == "portfolio" and graph is not None:
        q = strategy.screening_quality()
        print(f"surrogate screened {q['n_screened']} candidates "
              f"({q['n_compared']} simulated; rank corr "
              f"{q['spearman']:.2f})")
    if graph is None:
        print(f"best parameters: {target.describe(best)}")
    else:
        print("best emission order:",
              " ".join(str(i) for i in best.items
                       if i.name not in ("start", "end")))

    report = R.distill(res)
    print(f"\n{report.labeling.n_classes} performance classes; "
          f"design rules:")
    print(R.render_rules_table(report.grouped(), top_k=2))
    if args.rules == "-":
        print("\n" + report.render())
    elif args.rules is not None:
        path = report.write(args.rules)
        print(f"\nfull design-rule report written to {path}")

    if tel is not None:
        if args.telemetry:
            print("\n" + tel.summary())
        if res.telemetry:
            r_last = res.telemetry[-1]
            print(f"\ntelemetry: {len(res.telemetry)} driver rounds; "
                  f"final round {r_last['round']} "
                  f"(best {r_last['best'] * 1e6:.2f} us, "
                  f"{r_last['misses']} misses)")
        tel.close()
        if args.trace:
            print(f"trace written to {args.trace} — open it at "
                  "https://ui.perfetto.dev")
        obs.set_current(None)
    if ephemeral_store is not None:
        import shutil
        shutil.rmtree(ephemeral_store, ignore_errors=True)

    # Roofline context for the fastest train-step schedule.
    if args.space is None:
        total_flops = sum(op.flops for op in graph.ops.values())
        print(f"\ncompute-only bound "
              f"{total_flops / PEAK_FLOPS * 1e3:.2f} ms;"
              f" best overlap schedule {times.min() * 1e3:.2f} ms "
              f"({total_flops / PEAK_FLOPS / times.min():.0%} of peak)")


if __name__ == "__main__":
    main()
