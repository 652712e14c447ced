"""On-chip benchmark of the AMTHA mapping system: ``python3 -m bench.run``."""
