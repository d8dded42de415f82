"""Kernels: Mamba-2's one-step state update of the decode tick, share of its
roofline (memory bound: the float32 state, 4 MB a row a layer at the
published widths, is read and written once a token; benchmarks/roofline/
ssm2.py). Work = the decode rows of the traced steps (the program's
``serving_decode_rows`` as drivers/serve_ssm_moe.py keeps it per step) over
the Mamba-2 layers; time = device time of the matching trace events. Rows
that are idle or prefilling pass through the kernel too and count for
nothing here."""
from benchmarks.hybrid_readers import traced_pairs
from benchmarks.readers import kernel_roofline
from benchmarks.reference.ssm_moe_lm import sizes
from benchmarks.roofline import ssm2 as work


def patterns(cfg):
    """The update by its name (the Pallas kernel's, or the scope the jnp
    composition traces under), else by structure: the fusion or custom-call
    whose LAST output is the new float32 state [B > 1, heads, head_dim,
    d_state] (beside y; the chunk program's write of one slot's state
    returns the state alone)."""
    z = sizes(cfg)
    return [r"ssm2_step",
            rf"f32\[(?!1,)\d+,{z['mh']},{z['P']},{z['N']}\]\S*\) "
            rf"(?:fusion|custom-call)\("]


def read(run):
    st = traced_pairs(run)
    rows = sum(h["decode_rows"] for _, h in st)
    if not st or not rows:
        return None
    calls = sum(1 for _, h in st if h["decode_rows"])
    cfg = run["config"]
    return kernel_roofline(run, patterns(cfg), work.step_flops(cfg, rows),
                           work.step_nbytes(cfg, rows, calls))
