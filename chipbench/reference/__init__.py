"""Plain float32 references of the benchmarked rounds.

Nothing here imports the system under test: the models, the client-level
RLOO pass, the Eq. 10-12 aggregation and the server step are written out
in `jax.numpy`, at `jax.default_matmul_precision("highest")`.  The same
functions run in bfloat16 as the control (`dtype=jnp.bfloat16`), the
precision a later change would be tempted to drop to.
"""
