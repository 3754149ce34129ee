"""Operations and bytes of one `tree_sep_update` call (one tree's sweep).

Per point it compares H code pairs with the opened center's and rewrites
the point's weight: integer compares and one exp2 and min, no matrix
work, so the call is bound by bytes.  It needs every operand (the (H, n)
code planes, the center's codes, the (1, n) weights) read once and its
(1, n) result written once; a vmapped call carries a leading lane axis
on the weights and the result.
"""

from xplane import nbytes

TRACE_NAME = "tree_sep_update_pallas"


def cost(call: dict) -> tuple:
    """(operations, bytes) of one call, from `xplane.parse_call`."""
    return 0.0, float(nbytes(call["operands"]) + nbytes(call["results"]))
