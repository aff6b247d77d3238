"""Model FLOP utilization of the training step: useful tokens per second
of the traced window times the forward and backward FLOPs a token needs
(models/<type>.py, remat not counted), over the chips' bf16 peak."""
from bench import spec

KIND, UNIT = "per_layer", "%"


def read(rec):
    if "useful_tokens" not in rec or not rec.get("peak"):
        return None
    c = rec["cell"]["config_spec"]
    seq = rec["cell"]["traffic_spec"]["seq"]
    flops = spec.module("models", c["model_type"]).train_flops_per_token(
        c, seq)
    rate = rec["useful_tokens"] / rec["window_s"]
    return 100.0 * rate * flops / (rec["chips"] * rec["peak"]["bf16_flops"])
