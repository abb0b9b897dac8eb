"""Optimizers: AdamW, Adafactor, host-offloaded state (paper technique)."""
from .adafactor import adafactor
from .adamw import Optimizer, adamw
from .offload import (attention_step_program, host_memory_kind,
                      offload_shardings, offloaded_optimizer,
                      plan_step_program)


def default_optimizer(cfg) -> Optimizer:
    """Adafactor for the 480B MoE (Adam fp32 state > one pod's HBM);
    AdamW elsewhere."""
    from repro.configs import param_count
    if param_count(cfg) > 100e9:
        return adafactor()
    return adamw()


__all__ = ["adamw", "adafactor", "Optimizer", "default_optimizer",
           "offload_shardings", "offloaded_optimizer",
           "plan_step_program", "attention_step_program",
           "host_memory_kind"]
