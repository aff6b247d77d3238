"""Operation and byte counts of the benchmark's configurations against
hand counts."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import spec  # noqa: E402


def _model(config):
    c = spec.load("configs", config)
    return c, spec.module("models", c["model_type"])


def test_qwen_l4_train_flops_per_token():
    c, m = _model("qwen2.5-3b-L4")
    # per layer: q and o 2048x2048, k and v 2048x256, MLP 3 x 2048x11008
    layer = 2 * 2048 * 2048 + 2 * 2048 * 256 + 3 * 2048 * 11008
    head = 2048 * 151936                      # tied, counted as the head
    attn = 4 * 2 * 2 * 16 * 128 * 512         # QK^T and PV, causal half
    assert m.train_flops_per_token(c, 1024) == 6 * (4 * layer + head) \
        + 3 * attn == 3_767_009_280


def test_mamba_l12_train_flops_per_token():
    c, m = _model("mamba2-1.3b-L12")
    # per layer: z, x 2048x4096; B, C 2048x128; dt 2048x64; out 4096x2048
    layer = 2048 * (2 * 4096 + 2 * 128 + 64) + 4096 * 2048
    head = 2048 * 50277
    # SSD per token and layer, chunk 256: C.B^T at half (one group), the
    # masked product with x at half, the chunk state and its read-out
    ssd = 256 * 128 + 256 * 64 * 64 + 2 * (2 * 64 * 128 * 64)
    assert m.train_flops_per_token(c, 2048) == 6 * (12 * layer + head) \
        + 3 * 12 * ssd == 2_591_354_880


def test_qwen_serve_bytes_and_prefill_flops():
    c, m = _model("qwen2.5-3b")
    matrices = 2 * 2048 * 2048 + 2 * 2048 * 256 + 3 * 2048 * 11008
    layer = 2 * matrices + 4 * (2 * 2048) + 4 * (2048 + 2 * 256)
    weights = 2 * 151936 * 2048 + 4 * 2048 + 36 * layer
    assert m.param_bytes(c) == weights == 6_172_360_704
    per_position = 36 * 2 * 2 * 128 * 2       # k and v, bf16, all layers
    assert m.decode_bytes(c, 1000, 10) == weights + per_position * 1010
    body = 36 * matrices
    attn = 36 * 2 * 2 * 16 * 128 * 1024
    assert m.prefill_flops(c, 2048) == 2048 * (2 * body + attn) \
        + 2 * 2048 * 151936 == 11_983_581_085_696
