"""Share of the Mamba-2 blocks' roofline in a decode step: their least
time (their weights read once, each active row's SSM state and conv
window read and written, their operations at the bf16 peak) over their
device time in the step (scope ``mamba2``)."""
from bench.metrics._scoped import roofline


def read(obs):
    return roofline(obs, "mamba2")
