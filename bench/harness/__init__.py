"""The on-chip benchmark's own code: the yardstick that later changes to
the program are measured against. It imports nothing of the program
except the entry points it drives (``run.py`` and ``drivers.py``)."""
