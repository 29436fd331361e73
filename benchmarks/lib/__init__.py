"""The benchmark's own code: copies of what `chip_smoke.py` proved on the
chip, the load generator, the references, the trace reduction. Nothing
here is imported by the program, and the references import nothing of
the program."""
