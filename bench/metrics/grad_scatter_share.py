"""grad_scatter_share: the device time of the row backward's gradient
scatter, over the busy time of the cell's chips, in percent.

The ops whose innermost program scope is ``grad_scatter``
(``bench/scopes.py``): each row's input gradient padded to the full input
and added into it, under ``bp_row<r>``."""

from bench import scopes


def read(ctx):
    return scopes.share(ctx, ("grad_scatter",))
