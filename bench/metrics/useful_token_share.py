"""Useful tokens over the tokens the stacked step computed, from the
batch's shape: 1 / S_A for a masked SPARe step (the inverse of the
paper's computation overhead)."""
KIND, UNIT = "per_layer", "%"


def read(rec):
    if "useful_tokens" not in rec:
        return None
    return 100.0 * rec["useful_tokens"] / rec["computed_tokens"]
