"""95th percentile, over every request due in the window, of the time
from its scheduled arrival to the host holding its first token; a request
with no first token by the window's end counts its elapsed time (host
clock)."""
import numpy as np

KIND, UNIT = "end_to_end", "ms"


def read(rec):
    if "ttft_s" not in rec:
        return None
    return 1e3 * float(np.percentile(rec["ttft_s"], 95))
