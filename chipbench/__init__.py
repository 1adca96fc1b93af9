"""On-chip benchmark of the FedZero round loop (see run.py)."""
