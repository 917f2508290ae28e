"""Least time by the chip's peaks for the traced steps' two-matmul latent experts (ssm_cost.latent_ffn_cost of the rows that landed on this chip) over the device time of scope `moe_experts`."""

from benchmark import ssm_trace


def read(records):
    return ssm_trace.latent_experts_roofline(records)
