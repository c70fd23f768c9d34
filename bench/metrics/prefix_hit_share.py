"""Prompt tokens served from the prefix cache over the prompt tokens of the
requests admitted in the run (scheduler's radix cache)."""
from bench import window as Wn


def read(ctx):
    if ctx.pre0 is None:
        return None
    prompt = sum(r.prompt_len for r in Wn.admitted(ctx))
    hit = ctx.pre1["prefix_hit_tokens"] - ctx.pre0["prefix_hit_tokens"]
    return hit / prompt if prompt else None
