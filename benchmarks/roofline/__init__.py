"""Operations and bytes that an ALGORITHM needs, from shapes and observed
lengths alone — whichever implementation the program selected."""


def least_seconds(flops: float, nbytes: float, peaks: dict):
    """(seconds, bound): the least time the chip could take, and which of
    the two bounds it ("compute" or "memory")."""
    tf = flops / peaks["bf16_flops"]
    tb = nbytes / peaks["hbm_bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")
