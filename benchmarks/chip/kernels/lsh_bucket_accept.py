"""Operations and bytes of one `lsh_bucket_accept` call.

For B candidates against K center slots it takes the squared distance by
the expansion |q|^2 - 2 q.c + |c|^2 (2 B K D operations in the product)
and tests L bucket-key pairs per (candidate, center).  Operands, in
order: candidate keys lo/hi (L, B), candidates (B, D), center keys lo/hi
(L, K), centers (K, D), penalty (1, K), weights (1, B); a vmapped call
carries a leading lane axis.  Bytes: every operand read once, both
(1, B) results written once.  The product runs at HIGHEST precision (the
kernel's `MATMUL_PRECISION`), six bfloat16 passes on a TPU, so at the
bfloat16 ridge of ~240 operations per byte a call is bound by its
operations once 6 x 2 B K D exceeds 240 x its bytes.  At the KDD-Cup
widths (D = 74, K = 512, L = 15) that holds from a candidate block of
B = 256 up, and bytes bind below it; at the PQ widths (D = 16, K = 256)
bytes bind at every block.
"""

from xplane import nbytes

TRACE_NAME = "lsh_bucket_accept_pallas"
PRECISION = "HIGHEST"


def _size(dims) -> int:
    out = 1
    for x in dims:
        out *= x
    return out


def cost(call: dict) -> tuple:
    """(operations, bytes) of one call, from `xplane.parse_call`."""
    ops_ = call["operands"]
    _, q = ops_[2]
    _, c = ops_[5]
    flops = 2.0 * _size(q) * c[-2]
    return flops, float(nbytes(ops_) + nbytes(call["results"]))
