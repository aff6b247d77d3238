"""Useful training tokens per second: vanilla DP's batch (N x
per_type_batch x seq) for every step completed in the window, over the
window's seconds; the window ends in block_until_ready on the parameters
and optimizer state (host clock)."""
KIND, UNIT = "end_to_end", "tokens/s"


def read(rec):
    if "useful_tokens" not in rec:
        return None
    return rec["useful_tokens"] / rec["window_s"]
