"""95th percentile over every gap between successive deliveries of
tokens to a request in the window, read on the host clock after each
ServeEngine.step()."""
import numpy as np

KIND, UNIT = "end_to_end", "ms"


def read(rec):
    if "itl_s" not in rec:
        return None
    return 1e3 * float(np.percentile(rec["itl_s"], 95))
