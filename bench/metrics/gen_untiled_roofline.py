"""Share of its roofline of the gen_untiled kernel (work/gen_untiled.py),
read from the device trace of the --trace 1 run."""
from harness import roofline


def read(ctx):
    return roofline.share(ctx, "gen_untiled")
