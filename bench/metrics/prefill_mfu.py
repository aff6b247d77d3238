"""Model FLOP utilization of prefill: the FLOPs the window's prompts need
(``models/<type>.py``: every layer at every position, causal attention
at half, the head at the last position) over the device time of the
prefill programs (the engine's ``jit__lambda`` programs other than
decode), over the chip's bf16 peak."""
from bench import spec

KIND, UNIT = "per_layer", "%"


def read(rec):
    if rec["cell"]["driver"] != "serve" or "trace" not in rec:
        return None
    decode_of = spec.module("metrics", "decode_hbm_share").programs
    _, prefills = decode_of(rec)
    seconds = sum(p["seconds"] for p in prefills)
    if not seconds or not rec["prefills"] or not rec.get("peak"):
        return None
    c = rec["cell"]["config_spec"]
    model = spec.module("models", c["model_type"])
    flops = sum(model.prefill_flops(c, n) for n in rec["prefills"])
    return 100.0 * flops / seconds / rec["peak"]["bf16_flops"]
