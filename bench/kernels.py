"""How the trace shows the kernels the per-layer rooflines read.

The program's Pallas kernels carry no name of their own in the trace: each
is a ``custom-call`` with target ``tpu_custom_call`` inside the compiled
program that calls it.  The paged flash-decode kernel is the only one in
the serving engine's ``decode`` program.
"""


def is_paged_decode(op):
    return op.program == "decode" and op.custom_call == "tpu_custom_call"
