"""Benchmark of the Demeter sweep and fleet paths on the chip (see run.py)."""
