"""Share of the traced serving window in which no operation ran on the
device."""
KIND, UNIT = "per_layer", "%"


def read(rec):
    if rec["cell"]["driver"] != "serve" or "trace" not in rec:
        return None
    t = rec["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
