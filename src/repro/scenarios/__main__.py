"""CLI: run a named scenario suite and print a results table.

    PYTHONPATH=src python -m repro.scenarios smoke16 --backend flowsim_fast
    PYTHONPATH=src python -m repro.scenarios table2_train_space \\
        --backend m4 --n 16 --num-flows 200 --cache-dir results/sweep_cache
    PYTHONPATH=src python -m repro.scenarios --list

The m4 backend loads the cached benchmark artifact via
`benchmarks.common.trained_m4` (training it on first use); run from the
repo root for that. Compile counts come from the jax backends'
`TRACE_COUNTS`, so the footer shows exactly how many XLA programs the
sweep cost.
"""
from __future__ import annotations

import argparse
import inspect
import sys


def _compile_count() -> int:
    """Total batched/sharded XLA traces across the jax backends."""
    from ..core import flowsim_fast, simulate
    return sum(flowsim_fast.TRACE_COUNTS.values()) \
        + sum(simulate.TRACE_COUNTS.values())


def _build_backend(name: str, log):
    from ..sim import get_backend
    if name != "m4":
        return get_backend(name)
    try:
        from benchmarks.common import trained_m4
    except ImportError as e:
        raise SystemExit(
            "--backend m4 needs the trained benchmark artifact "
            "(run from the repo root so `benchmarks` is importable): "
            f"{e}")
    params, cfg = trained_m4(log=log)
    return get_backend("m4", params=params, cfg=cfg)


def main(argv=None) -> int:
    from . import SUITES, SweepRunner, get_suite, list_suites
    ap = argparse.ArgumentParser(
        prog="python -m repro.scenarios",
        description="Run a named scenario suite through one simulator "
                    "backend and print a results table.")
    ap.add_argument("suite", nargs="?", help="suite name (see --list)")
    ap.add_argument("--list", action="store_true", help="list suites")
    ap.add_argument("--backend", default="flowsim_fast",
                    help="simulator backend (default: flowsim_fast)")
    ap.add_argument("--num-flows", type=int, default=None,
                    help="flows per scenario (suite default if omitted)")
    ap.add_argument("--n", type=int, default=None,
                    help="scenario count for random suites "
                         "(table2_train_space)")
    ap.add_argument("--limit", type=int, default=None,
                    help="run only the first K specs of the suite")
    ap.add_argument("--chunk", type=int, default=8,
                    help="scenarios per batched compile (default 8; "
                         "0 = one chunk for the whole sweep)")
    ap.add_argument("--cache-dir", default=None,
                    help="on-disk result cache directory (off by default)")
    args = ap.parse_args(argv)
    from ..runtime.compile_cache import use_persistent_cache
    use_persistent_cache()

    if args.list or not args.suite:
        print("available suites:")
        for name in list_suites():
            print(f"  {name}")
        return 0 if args.list else 2

    knobs = {}
    if args.num_flows is not None:
        knobs["num_flows"] = args.num_flows
    if args.n is not None:
        knobs["n"] = args.n
    if args.suite in SUITES:
        # fail cleanly when a knob isn't one of this suite's parameters
        accepted = set(inspect.signature(SUITES[args.suite]).parameters)
        rejected = set(knobs) - accepted
        if rejected:
            raise SystemExit(
                f"suite {args.suite!r} does not take "
                f"{', '.join('--' + k.replace('_', '-') for k in sorted(rejected))} "
                f"(its knobs: {', '.join(sorted(accepted)) or 'none'})")
    sweep = get_suite(args.suite, **knobs)
    if args.limit is not None:
        sweep = sweep.limit(args.limit)

    backend = _build_backend(args.backend, log=print)
    runner = SweepRunner(backend, cache_dir=args.cache_dir,
                         chunk_size=args.chunk or None)
    c0 = _compile_count()
    report = runner.run(sweep)
    print(report.table())
    print(f"-- compiles this run: {_compile_count() - c0}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
