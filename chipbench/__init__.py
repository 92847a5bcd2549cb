"""Chip benchmark of the serving path: one cell (configuration x traffic mix)
per process, driven open loop from the client side.  ``run.py`` is the entry
point; everything that belongs to one configuration, mix or per-layer metric
lives in a file of its own that the harness finds by name."""
