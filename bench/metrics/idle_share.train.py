"""Share of the traced training window in which no operation ran on the
device (averaged over the chips)."""
KIND, UNIT = "per_layer", "%"


def read(rec):
    if rec["cell"]["driver"] != "train" or "trace" not in rec:
        return None
    t = rec["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
