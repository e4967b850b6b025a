"""The benchmark's own library: traffic, yardsticks, trace reduction and
the comparison that decides ``correct``.  It imports nothing of the program
except in ``harness.py``, which drives the system under test."""
