# Pallas TPU kernels for GENIE's compute hot-spots (match-count engines and
# the c-PQ gate histogram).  Each kernel module holds the pl.pallas_call +
# BlockSpec implementation; ops.py is the jit'd public wrapper; ref.py the
# pure-jnp oracle.  On the CPU backend they run in interpret mode;
# on the TPU they compile; any other backend raises (common.use_interpret).
