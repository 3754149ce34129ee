"""Share of its roofline that `tree_sep_update` reaches (bytes-bound)."""

from roofline import share


def read(run):
    return share(run, "tree_sep_update")
