"""Plain float32 reference of the served text-to-image workflow.

Straightforward ``jax.numpy`` at highest matmul precision, with no kernel,
no batching across requests and no cache.  It imports nothing of the
program under test: weights are rebuilt here from the same seeds the
program's loaders use (CRC32 of the model id), by this package's own code.
"""
