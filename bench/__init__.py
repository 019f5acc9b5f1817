"""The chip benchmark of the Pallas CNN path: ``python3 -m bench.run``."""
