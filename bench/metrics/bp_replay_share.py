"""bp_replay_share: the device time of the row backward's replay, over
the busy time of the cell's chips, in percent.

The ops whose innermost program scope is ``replay`` (``bench/scopes.py``):
the forward of a row that the backward runs again under ``bp_row<r>`` before
it takes the row's VJP.  A replay that XLA merged into the forward leaves
no op there and reads 0."""

from bench import scopes


def read(ctx):
    return scopes.share(ctx, ("replay",))
