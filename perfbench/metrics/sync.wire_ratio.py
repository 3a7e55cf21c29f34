"""Wire bytes over raw bytes of the window's updates, as the program
counts them on each ``SyncUpdate``."""


def read(ctx):
    rounds = ctx.counters.get("rounds") or []
    raw = sum(r["raw_bytes"] for r in rounds)
    return sum(r["wire_bytes"] for r in rounds) / raw if raw else None
