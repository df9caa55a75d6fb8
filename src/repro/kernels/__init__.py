# OPTIONAL layer. Add <name>.py (or .cu) ONLY for compute hot-spots the
# paper itself optimizes with a custom kernel. Leave this package empty if
# the paper has none.
#
# This paper's hot spot IS a custom-kernel cascade (§4.4); here it runs as
# one launch per modification:
#   cholupdate.py    — the in-kernel math every kernel below shares: the
#                      diagonal phase (diag_reflect) and the transform-GEMM
#                      panel apply (apply_transform)
#   fused.py         — single-launch dense kernel, one chain walk with two
#                      lowerings (DESIGN.md §5)
#   fleet.py         — single-tile fleets, one member per lane (DESIGN.md §5.2)
#   sharded.py       — one-launch-per-shard panel kernel for the distributed
#                      fused composition (DESIGN.md §7)
#   blocktridiag.py  — block-chain kernel for block-bidiagonal factors
#                      (DESIGN.md §12)
