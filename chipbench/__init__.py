"""On-chip benchmark of the FleetOpt served path (see run.py)."""
