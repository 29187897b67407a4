"""Chip-0 idle time under no span of the program (the harness's
``device_get`` of the result, and the host's time between the program's
spans), inside each experiment span of the harness, per experiment, in ms
(``harness.program.idle_by_span``)."""
from harness import program


def read(ctx):
    return program.reading(ctx, program.idle_by_span, program.UNATTRIBUTED)
