"""MB demoted, promoted, written to SSD and read from SSD by the KV tiers
during the run, per request admitted (exact counts of the tiered cache)."""
from bench import window as Wn

KEYS = ("kv_swap_out_bytes", "kv_swap_in_bytes", "kv_ssd_write_bytes",
        "kv_ssd_read_bytes")


def read(ctx):
    n = len(Wn.admitted(ctx))
    if not n:
        return None
    moved = sum(ctx.kv1[k] - ctx.kv0[k] for k in KEYS)
    return moved / 1e6 / n
