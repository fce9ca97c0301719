"""Benchmark of the VC-ASGD round on the TPU (see run.py)."""
