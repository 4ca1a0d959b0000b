"""The plain reference: the model's forward pass in ``jax.numpy`` and
float32 under ``jax.default_matmul_precision("highest")`` — no cache, no
kernels, no batching — and the comparison that decides ``correct``.

It imports nothing of the program and takes nothing the program made: the
weights are made again from ``--seed`` by its own copy of the init recipe
(``weights.py``), the prompt ids by its own copy of the chat templates and
the byte tokenizer (``tokens.py``).
"""
