"""Share of its roofline that `lsh_bucket_accept` reaches (the larger of
its byte and operation bounds per call, its product counted at six
bfloat16 passes; see `kernels/lsh_bucket_accept.py` for which binds)."""

from roofline import share


def read(run):
    return share(run, "lsh_bucket_accept")
