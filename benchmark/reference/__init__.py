"""Plain float32 ``jax.numpy`` forward passes (and losses) of the benchmark's
architectures, written from the published descriptions and independent of
``kubeflow_tpu.models``: no kernels, no cache, no batching tricks. They read
the program's parameter tree (names are data, not mathematics) and set
``jax.default_matmul_precision("highest")``, since a float32 product on a
TPU otherwise runs in bfloat16 passes.
"""
