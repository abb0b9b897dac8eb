"""Model substrate: assigned architectures as one composable Transformer."""
from .layers import P, Policy, cross_entropy, rms_norm
from .transformer import STACKS, Transformer

__all__ = ["Transformer", "STACKS", "P", "Policy", "cross_entropy",
           "rms_norm"]
