"""Host ms per decode step from the dispatch of the step's device programs
until their results are on the host: the program's
``engine.decode.dispatch`` spans over its ``engine.decode_step`` spans, in
the traced window (``bench/program_trace.py``)."""
from bench import program_trace as PT

SPAN = "engine.decode.dispatch"


def read(ctx):
    if ctx.program_trace is None or SPAN not in ctx.program_trace.spans:
        return None
    return PT.per_step_ms(ctx.program_trace, SPAN)
