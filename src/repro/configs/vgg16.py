"""VGG-16 [Simonyan & Zisserman, ICLR'15] — the paper's own benchmark.

Row-centric CNN training config: the config carries a :class:`PlanRequest`
(engine + granularity, or just a byte budget) which the launcher resolves
to an :class:`~repro.exec.plan.ExecutionPlan` via ``Planner`` — the
paper's RTX3090 = 24 GB / RTX3080 = 10 GB scenarios are reproduced in
benchmarks/.
"""
import dataclasses

from repro.exec.plan import PlanRequest


@dataclasses.dataclass(frozen=True)
class CNNConfig:
    name: str
    arch: str              # vgg16 | resnet50 | convnext_t
    image: int = 224
    channels: int = 3
    n_classes: int = 10
    batch: int = 32
    width_mult: float = 1.0
    # plan request: pinned engine+N by default; set engine="" and a
    # budget to let Planner.for_budget auto-select (Table I trade-offs).
    # mesh="data=8" additionally shards the plan — the budget becomes
    # per-device and the batch divides over the data axis (equivalently,
    # pass --mesh to repro.launch.train); keep it "" for hosts whose
    # device count is unknown at config time.
    plan: PlanRequest = PlanRequest(engine="twophase_h", n_rows=8,
                                    budget_gb=24.0)


CONFIG = CNNConfig(name="vgg16", arch="vgg16")


def reduced():
    return CNNConfig(name="vgg16-reduced", arch="vgg16", image=64,
                     width_mult=0.125, batch=2,
                     plan=PlanRequest(engine="twophase", n_rows=2))
