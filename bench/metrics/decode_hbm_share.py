"""Share of the chip's HBM bandwidth that decoding reaches: the bytes
every decode step of the traced window needs (each stored weight once,
the keys and values of the live positions, the new keys and values;
``models/<type>.py``) over the decode program's device time, over the
peak bytes per second.

The decode program is the serving engine's ``jit__lambda`` program that
ran most often in the window (once per engine step; each prefill
program runs once per admitted request of its bucket)."""
from bench import spec

KIND, UNIT = "per_layer", "%"


def programs(rec):
    """(decode, prefills): the engine's programs in the trace."""
    mods = {k: v for k, v in rec["trace"]["modules"].items()
            if k.startswith("jit__lambda(")}
    if not mods:
        return None, []
    decode = max(mods, key=lambda k: mods[k]["runs"])
    return mods[decode], [v for k, v in mods.items() if k != decode]


def read(rec):
    if rec["cell"]["driver"] != "serve" or "trace" not in rec:
        return None
    decode, _ = programs(rec)
    if decode is None or not rec["decode_steps"] or not rec.get("peak"):
        return None
    c = rec["cell"]["config_spec"]
    model = spec.module("models", c["model_type"])
    need = sum(model.decode_bytes(c, keys, active)
               for active, keys in rec["decode_steps"])
    return 100.0 * need / decode["seconds"] / rec["peak"]["hbm_bytes_per_s"]
