"""Share of its roofline of the f15_eval kernel (work/f15_eval.py),
read from the device trace of the --trace 1 run."""
from harness import roofline


def read(ctx):
    return roofline.share(ctx, "f15_eval")
