"""Plain references: each architecture's forward pass (and loss) in
straightforward ``jax.numpy`` and float32, with no kernels, cache or
batching tricks, written from the published description and independent
of the program's model code.  Departures are noted in each file."""
