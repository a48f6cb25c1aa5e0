"""Plain references of the model zoo's architectures: straightforward
``jax.numpy`` at float32, no cache, kernels or batching, for tests to
compare the serving path with."""
