"""sd_cache_share: the device time of the 2PS boundary caches, over the
busy time of the cell's chips, in percent.

The ops whose innermost program scope is ``sd_import`` (a row's imported
cache rows joined to its own) or ``sd_export`` (the rows cut for the next
row), in the forward, the replay and their gradients (``bench/scopes.py``).
Slices and joins that XLA fused into a convolution take the convolution's
scope, so this counts only what the caches cost on their own."""

from bench import scopes


def read(ctx):
    return scopes.share(ctx, ("sd_import", "sd_export"))
