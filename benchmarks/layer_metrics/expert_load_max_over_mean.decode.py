"""Router: how uneven the held experts' loads are — the rows on the busiest
held expert of a layer over the mean rows of a held expert, both summed over
layers and program calls of the window (``serving_moe_load_max`` over
``serving_moe_pairs{held="1"}`` / held experts). 1 is an even split; the
grouped product's row tiles and the tick's tail follow the busiest expert."""
from benchmarks.latent_moe_readers import total, window_pairs
from benchmarks.reference.latent_moe_lm import sizes


def read(run):
    st = window_pairs(run)
    held = total(st, "pairs_held")
    if not st or not held:
        return None
    lo, hi = sizes(run["config"])["held"]
    return total(st, "load_max") / (held / (hi - lo))
