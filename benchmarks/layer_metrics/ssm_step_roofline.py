"""Kernels: the selective scan's one-step state update of the decode tick,
share of its roofline (memory bound: the float32 state is read and written
once a token; benchmarks/roofline/ssm.py). Time = device time of the
matching trace events in the traced steps."""
from benchmarks.hybrid_readers import traced_pairs
from benchmarks.readers import kernel_roofline
from benchmarks.reference.sambay_lm import sizes
from benchmarks.roofline import ssm as work


def patterns(cfg):
    """The kernel by its name, else by structure: the custom-call that
    returns the new float32 state [B > 1, d_state, d_inner] (the chunk scan
    of a prefill returns ONE slot's state, [d_state, d_inner])."""
    z = sizes(cfg)
    return [r"ssm_step",
            rf"f32\[(?!1,)\d+,{z['S']},{z['di']}\]\S*\) custom-call\("]


def read(run):
    st = traced_pairs(run)
    if not st:
        return None
    rows = sum(h["decode_rows"] for _, h in st)
    calls = sum(1 for _, h in st if h["decode_rows"])
    cfg = run["config"]
    return kernel_roofline(run, patterns(cfg), work.step_flops(cfg, rows),
                           work.step_nbytes(cfg, rows, calls))
