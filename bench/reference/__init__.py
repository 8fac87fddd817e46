"""Plain float32 references for the benchmark's correctness checks.

They import nothing of the program: the model is written out in
``jax.numpy`` at ``"highest"`` matmul precision, layer by layer, and the
weights are made anew from the run's seed (``bench/weights.py``).
"""
