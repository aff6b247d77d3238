"""Set-up: process start to the first timed step or request, compiles,
weights and warm-up included (host clock)."""
KIND, UNIT = "end_to_end", "s"


def read(rec):
    return rec.get("setup_s")
