"""The plain reference: each model's forward and loss in float32
``jax.numpy`` (no kernels, no batching tricks), the clipped gradient sum,
the Poisson draw and the RDP accountant, written from the published
descriptions.  Nothing here imports the program under test."""
