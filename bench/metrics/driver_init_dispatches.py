"""Programs JAX launches (outermost ``PjitFunction`` host events) that
start under the program's ``driver.init`` span, per experiment of the
harness (``harness.program.dispatches_by_span``)."""
from harness import program


def read(ctx):
    return program.reading(ctx, program.dispatches_by_span, "driver.init")
