"""Share (%) of the HBM roofline that the X^T w kernel of the certified
gap (``fw_sparse_xtw``) reaches in the traced path work (bench/xtw.py)."""
from bench import xtw


def read(ctx):
    return xtw.xtw_share(ctx) if ctx.entry == "path" else None
