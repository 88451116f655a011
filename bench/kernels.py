"""Names by which the trace reduction finds the program's kernels and
programs (regular expressions over device operation and module names).

On the TPU each device operation is named by its HLO instruction, whose
text holds the op: a Pallas kernel is a ``custom-call``. The GNN step
holds no Pallas kernel but the aggregation kernels of kernels/aggregate.py
(forward, and the backward of each layer), whose own names
(``jvp__.4``, ``transpose_jvp___.5``) come from the custom VJP and carry
no stable meaning, so the aggregation is every custom call.
"""

AGG_KERNELS = r"custom-call\("
# the serving runtime's per-bucket jitted forward
SERVE_FORWARD = r"^jit_fwd"
