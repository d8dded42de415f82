"""Kernels: the grouped products of the held experts of a Mamba-2 +
many-expert decoder's expert block, share of their roofline. Work
(benchmarks/roofline/ssm_moe_experts.py): the FLOPs of the pairs held here,
and the three matrices of every held expert that got a row, read once a layer
a call — from the program's ``serving_moe_pairs{held="1"}`` and
``serving_moe_experts_active`` over the traced steps; time = device time of
the matching trace events."""
from benchmarks.latent_moe_readers import total, traced_pairs
from benchmarks.readers import kernel_roofline
from benchmarks.reference.ssm_moe_lm import sizes
from benchmarks.roofline import ssm_moe_experts as work


def patterns(cfg):
    """By name, else by structure: every op that reads a stack of the held
    experts' matrices, [held, hidden, width] or [held, width, hidden]."""
    z = sizes(cfg)
    held = z["held"][1] - z["held"][0]
    return [r"expert_gmm",
            rf"\w+\[{held},{z['H']},{z['F']}\]|\w+\[{held},{z['F']},{z['H']}\]"]


def read(run):
    st = traced_pairs(run)
    held = total(st, "pairs_held")
    if not st or not held:
        return None
    cfg = run["config"]
    return kernel_roofline(
        run, patterns(cfg), work.flops(cfg, held),
        work.nbytes(cfg, held, total(st, "experts_active")))
