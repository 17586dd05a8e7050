"""One driver per job the system does.  ``run(ctx)`` builds the system
from the cell's files and ``--seed``, warms it, measures the window,
reads the device's memory peak, frees the system and decides
``correct`` against the plain reference."""
