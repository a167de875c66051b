"""Plain references: jax.numpy, float32, matmuls at `highest`, no kernels.
Nothing here imports the program (geomx_tpu) or takes anything it made."""
