"""ConvNeXt-T [Liu et al., "A ConvNet for the 2020s", CVPR'22,
arXiv:2201.03545] at 512x512, the crop of the paper's ADE20K training:
depthwise 7x7 blocks with LayerNorm and GELU through the row engines."""
from repro.configs.vgg16 import CNNConfig
from repro.exec.plan import PlanRequest

CONFIG = CNNConfig(name="convnext_t", arch="convnext_t", image=512)


def reduced():
    # 256, not the other presets' 64: each 7x7 block pushes a 2PS row's
    # closure 3 rows down, so at 64 every segment would run one row
    return CNNConfig(name="convnext_t-reduced", arch="convnext_t", image=256,
                     width_mult=0.125, batch=2,
                     plan=PlanRequest(engine="twophase_h", n_rows=2))
