"""Chip-0 idle time under the program's ``driver.init`` span (an
experiment's fresh state built), inside each experiment span of the
harness, per experiment, in ms (``harness.program.idle_by_span``)."""
from harness import program


def read(ctx):
    return program.reading(ctx, program.idle_by_span, "driver.init")
