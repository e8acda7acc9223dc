"""Plain references the benchmark compares the store against.

They import nothing of ``repro``: each follows the documented semantics
(checksum and parity layout, the record store, the OLMo layer equations and
AdamW) in straightforward array code that runs under numpy or jax.numpy.
"""
