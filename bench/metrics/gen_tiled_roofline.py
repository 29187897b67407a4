"""Share of its roofline of the gen_tiled kernel (work/gen_tiled.py),
read from the device trace of the --trace 1 run."""
from harness import roofline


def read(ctx):
    return roofline.share(ctx, "gen_tiled")
