"""Benchmark harness: one module per paper table/figure + system benches.

Prints ``name,us_per_call,derived`` CSV (deliverable d). ``--quick`` runs
reduced sizes (used by CI/tests); the full run is what EXPERIMENTS.md cites.
Roofline tables (deliverable g) are produced by repro.launch.dryrun and
summarised from benchmarks/results/*.jsonl by benchmarks/report.py.
"""
import argparse
import inspect
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--tiny", action="store_true",
                    help="CI-smoke sizes; suites that support it (stream) "
                         "run only their latency section")
    ap.add_argument("--only", type=str, default=None,
                    help="comma-separated subset: cholupdate,kernels,"
                         "distributed,optimizer,stream")
    args = ap.parse_args()

    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        cholupdate_bench,
        distributed_bench,
        kernel_bench,
        optimizer_bench,
        stream_bench,
    )

    suites = {
        "cholupdate": cholupdate_bench.run,     # paper Figs 2-3
        "kernels": kernel_bench.run,            # Pallas tiles / VMEM / AI
        "distributed": distributed_bench.run,   # multi-device scaling
        "optimizer": optimizer_bench.run,       # O(kd^2) vs O(d^3) in situ
        "stream": stream_bench.run,             # coalesce-width sweep (§9)
    }
    chosen = args.only.split(",") if args.only else list(suites)
    rows = []
    for name in chosen:
        fn = suites[name]
        kw = {"quick": args.quick or args.tiny}
        if args.tiny and "tiny" in inspect.signature(fn).parameters:
            kw["tiny"] = True
        fn(rows, **kw)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
