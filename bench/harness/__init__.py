"""The on-chip benchmark's own code: the yardstick that later changes to
the program are measured against. It imports nothing of the program
except the entry points that ``run.py`` and the drivers under
``bench/drivers/`` drive; every part of a deployment is a file that
``registry.py`` finds by name."""
