"""End-to-end LM training with the CholeskyPrecond optimizer (reduced
llama3.2 config on CPU; pass --full on real hardware for the 3B config).

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 200]
"""
import argparse
import sys

from repro.launch.train import main as train_main
from repro.runtime.compile_cache import enable_compile_cache

if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--optimizer", default="cholesky_precond")
    args = ap.parse_args()
    enable_compile_cache()
    losses = train_main([
        "--arch", args.arch,
        "--steps", str(args.steps),
        "--optimizer", args.optimizer,
        "--batch", "8",
        "--seq", "128",
        "--ckpt-dir", "/tmp/repro_train_lm",
    ])
    assert losses[-1] < losses[0], "loss must decrease"
    print("OK: loss decreased", losses[0], "->", losses[-1])
