"""Plain references for the benchmark's ``correct``: straightforward
``jax.numpy`` in float32 at full matmul precision, no kernels, caches or
batching tricks, and nothing imported from the program."""
