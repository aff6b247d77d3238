# Pallas kernels: int8_ef.py (the compressed gradient sync's fused
# quantize-accumulate), its jitted wrapper in ops.py and its jnp oracle
# in ref.py. A kernel lands here only with a caller on a production path.
