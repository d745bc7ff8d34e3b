"""Plain references: straightforward jax.numpy, float32 at "highest" matmul
precision, no kernels, nothing imported from the program and nothing the program
has made.  Each also makes its configuration's data from the seed, and can be
computed at a lower precision to serve as the comparison's control."""
