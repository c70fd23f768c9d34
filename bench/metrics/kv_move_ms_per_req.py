"""Host ms the program spends moving KV, per request admitted: its
``kv.demote`` (HBM to DRAM, spilled on to SSD), ``kv.promote`` (back to HBM)
and ``engine.restore_prefix`` (prefix KV into a fresh session cache) spans
in the traced window, each counted once where one encloses another
(``bench/program_trace.py``). Nothing is fenced: device work the moves
queue is not waited for."""
from bench import program_trace as PT
from bench import window as Wn

MOVES = ("kv.demote", "kv.promote", "engine.restore_prefix")


def read(ctx):
    red = ctx.program_trace
    n = len(Wn.admitted(ctx))
    if red is None or not n or not any(m in red.spans for m in MOVES):
        return None
    return PT.outermost_seconds(red.events, MOVES) * 1e3 / n
