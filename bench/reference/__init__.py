"""Plain references, one module per model family, found by name.

Each imports nothing of the program and computes in float32 with
`jax.default_matmul_precision("highest")`.
"""
