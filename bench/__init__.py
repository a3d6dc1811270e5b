"""On-chip benchmark of SmartFreeze federated rounds (see bench/run.py)."""
