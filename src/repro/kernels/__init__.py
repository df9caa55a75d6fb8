# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
#
# This paper's hot spot IS a custom-kernel cascade (§4.4):
#   cholupdate.py — per-panel Pallas kernels (the paper's dispatch pattern)
#   fused.py      — single-launch pipelined kernel (DESIGN.md §5)
#   fleet.py      — single-tile fleets, one member per lane (DESIGN.md §5.2)
#   sharded.py    — one-launch-per-shard panel kernel for the distributed
#                   fused composition (DESIGN.md §7)
#   ops.py        — jit'd wrappers wiring the per-panel kernels to the driver
