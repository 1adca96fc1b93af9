"""Tests of the benchmark, on the CPU at small sizes."""
