"""Host ms of the M2 neuron cache's bookkeeping per decode step: the
program's ``engine.decode.m2_manager`` spans (active-set union, ATU update,
tier pricing) over its ``engine.decode_step`` spans, in the traced window
(``bench/program_trace.py``)."""
from bench import program_trace as PT

SPAN = "engine.decode.m2_manager"


def read(ctx):
    if ctx.program_trace is None or SPAN not in ctx.program_trace.spans:
        return None
    return PT.per_step_ms(ctx.program_trace, SPAN)
