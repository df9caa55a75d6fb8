"""Append-only benchmark snapshots: the repo's perf trajectory.

``scripts/bench.sh`` runs the benchmark suites and appends ONE json record
(line-delimited) per snapshot file:

    {"ts": ..., "commit": ..., "backend": ..., "platform": ...,
     "device_kind": ..., "lowering": ..., "quick": ...,
     "rows": [{"name": ..., "us": ..., "derived": ...}, ...]}

The ``platform`` / ``device_kind`` / ``lowering`` triple (ISSUE 7) pins
each record to the hardware and the fused-kernel lowering that produced
it — ``resolve_lowering('auto')``: mosaic on TPU, portable (Triton) on
GPU — so trajectories never silently mix numbers from different
lowerings of the same kernel. Per-row ``interpret=``/``lowering=``
tokens in ``derived`` refine this where a row pins its own mode.

Suites map to snapshot files: the kernel/cholupdate/optimizer suites share
``benchmarks/results/BENCH_cholupdate.json``; the streaming-service suite
lands in ``BENCH_stream.json`` (its axis is coalesce width, not problem
size) and the distributed suite in ``BENCH_distributed.json`` (its axes
are device count and fleet size, DESIGN.md §10) — mixing differently-axed
suites would make every trajectory unqueryable.

Every future PR that touches a hot path runs the same script; each file
then holds the before/after pair (and the whole history), so regressions
are a ``jq`` query instead of archaeology. Interpret-mode wall-clock
off-TPU is dispatch-bound, not kernel performance — compare like against
like via the recorded ``backend`` field.
"""
from __future__ import annotations

import argparse
import inspect
import json
import subprocess
import time
from pathlib import Path

RESULTS = Path(__file__).resolve().parent / "results"
SNAPSHOT = RESULTS / "BENCH_cholupdate.json"
SNAPSHOT_STREAM = RESULTS / "BENCH_stream.json"
SNAPSHOT_DISTRIBUTED = RESULTS / "BENCH_distributed.json"
# ISSUE 8: the structured-factor suite has its own axes (block size b,
# bytes-per-update vs dense at matched n) — its own trajectory file.
SNAPSHOT_BLOCKTRIDIAG = RESULTS / "BENCH_blocktridiag.json"


def _git_commit() -> str:
    repo = Path(__file__).resolve().parent.parent
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10, cwd=repo,
        ).stdout.strip() or "unknown"
        # A snapshot from uncommitted code must not masquerade as HEAD's —
        # the trajectory file is only comparable when rows attribute truly.
        dirty = subprocess.run(
            ["git", "status", "--porcelain"],
            capture_output=True, text=True, timeout=10, cwd=repo,
        ).stdout.strip()
        return f"{sha}-dirty" if dirty else sha
    except Exception:
        return "unknown"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (default: quick)")
    ap.add_argument("--only", type=str, default="cholupdate,kernels,stream",
                    help="comma-separated suite subset (see benchmarks.run)")
    ap.add_argument("--dtype", type=str, default="float32,bfloat16",
                    help="comma-separated storage-dtype axis for suites that "
                         "support it (DESIGN.md §8): per-dtype rows record "
                         "bytes-per-update alongside wall-clock")
    args = ap.parse_args()

    import jax

    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        blocktridiag_bench,
        cholupdate_bench,
        distributed_bench,
        kernel_bench,
        optimizer_bench,
        stream_bench,
    )

    # suite -> (runner, snapshot file): the stream suite's axis (coalesce
    # width) and the distributed suite's axes (device count, fleet size)
    # each get their own trajectory file.
    suites = {
        "cholupdate": (cholupdate_bench.run, SNAPSHOT),
        "kernels": (kernel_bench.run, SNAPSHOT),
        "distributed": (distributed_bench.run, SNAPSHOT_DISTRIBUTED),
        "optimizer": (optimizer_bench.run, SNAPSHOT),
        "stream": (stream_bench.run, SNAPSHOT_STREAM),
        "blocktridiag": (blocktridiag_bench.run, SNAPSHOT_BLOCKTRIDIAG),
    }
    dtypes = tuple(d for d in args.dtype.split(",") if d)
    by_file = {}
    suites_by_file = {}
    for name in args.only.split(","):
        fn, outfile = suites[name]
        rows = by_file.setdefault(outfile, [])
        suites_by_file.setdefault(outfile, []).append(name)
        if "dtypes" in inspect.signature(fn).parameters:
            fn(rows, quick=not args.full, dtypes=dtypes)
        else:
            fn(rows, quick=not args.full)

    from repro.core import backends

    RESULTS.mkdir(parents=True, exist_ok=True)
    commit = _git_commit()
    ts = time.strftime("%Y-%m-%dT%H:%M:%S%z")
    # ISSUE 7: record WHERE the numbers came from. ``platform`` is the jax
    # backend, ``device_kind`` the concrete accelerator (e.g. "TPU v4" /
    # "NVIDIA H100" / "cpu"), ``lowering`` what resolve('auto') picks there
    # — two snapshots are only comparable when all three match, and the
    # lowering field is what separates a mosaic trajectory from a portable
    # one on the same problem sizes.
    device_kind = jax.devices()[0].device_kind
    # PR 9: each record embeds the registry snapshot the run accumulated,
    # so report.py renders serving metrics (flush percentiles, retraces,
    # occupancy) from the SAME source the serving stack measures itself
    # with — bench rows and serving metrics can never disagree.
    from repro.obs import metrics as obs_metrics

    obs_snapshot = obs_metrics.snapshot()
    for outfile, rows in by_file.items():
        record = {
            "ts": ts,
            "commit": commit,
            "backend": jax.default_backend(),
            "platform": jax.default_backend(),
            "device_kind": device_kind,
            "lowering": backends.resolve_lowering("auto"),
            "quick": not args.full,
            "suites": ",".join(suites_by_file[outfile]),
            "dtypes": list(dtypes),
            "obs": obs_snapshot,
            "rows": [
                {"name": n, "us": round(us, 1), "derived": derived}
                for n, us, derived in rows
            ],
        }
        with outfile.open("a") as fh:
            fh.write(json.dumps(record) + "\n")
        print(f"appended {len(rows)} rows to {outfile}")
        for n, us, derived in rows:
            print(f"{n},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
