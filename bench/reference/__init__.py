"""Plain references the benchmark's ``correct`` compares against.

They import nothing of ``repro``: each is a straightforward host (NumPy)
or ``jax.numpy`` statement of one law the system promises, fed the
inputs of the operation under check (the table and key of a draw, the
ring of a stack, the seed of the learner's weights) and never an output
of it.  Each law takes a ``dtype``: float32 (float64 where the law is
host arithmetic) for the reference, bfloat16 for the control that must
come out as not correct.
"""
