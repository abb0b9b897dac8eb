"""Share of the MoE blocks' roofline in a decode step: their least time
(the held experts', the shared expert's and the router's weights read
once, their operations at the bf16 peak) over their device time in the
step (scope ``moe``)."""
from bench.metrics._scoped import roofline


def read(obs):
    return roofline(obs, "moe")
