"""dwconv_share: the device time of ConvNeXt's 7x7 depthwise
convolutions, over the busy time of the cell's chips, in percent.

The ops whose innermost scope is ``dwconv`` (``bench/layer_scopes.py``):
each block's depthwise convolution in the forward rows, in the backward's
replay and, transposed, its input and kernel gradients.  A program whose
blocks open no ``dwconv`` scope gives no reading."""

from bench import layer_scopes


def read(ctx):
    return layer_scopes.share(ctx, "dwconv")
