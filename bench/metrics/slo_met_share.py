"""Share of the requests due in the window that met both limits of the
mix's service class: time to first token and mean gap between tokens."""
from bench import window as Wn


def read(ctx):
    met = Wn.slo_met(ctx)
    return sum(met) / len(met) if met else None
