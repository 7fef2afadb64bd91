"""Share (%) of the HBM roofline that the sparse column-statistics kernel
(``fw_sparse_colstats``) reaches in the traced fit work (bench/roofline.py)."""
from bench import roofline


def read(ctx):
    return roofline.colstats_share(ctx) if ctx.entry == "fit" else None
