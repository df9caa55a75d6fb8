"""Tiny sizes at which each cell runs on the CPU, interpreted, in a second
or two of window: the same driver, harness and result line the command
uses, with the chip checks off."""
from bench import harness

SEED = 2**33 + 12345  # run seeds may be larger than 32 signed bits

SHRINK = {
    "gp.n5000.k16": {"config": {"n": 64, "window_rows": 32},
                     "traffic": {"pool_batches": 64}},
    "gp.n5000.k1": {"config": {"n": 64, "window_rows": 8},
                    "traffic": {"pool_batches": 4096}},
}


def run(cell: str, *, seconds: float = 1.0, seed: int = SEED, **kw):
    """``(result line, outcome, ctx)`` of one tiny CPU run of ``cell``."""
    return harness.run_cell(cell, seed=seed, seconds=seconds, on_chip=False,
                            interpret=True, shrink=SHRINK[cell], **kw)
