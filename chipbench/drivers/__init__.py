"""One file per kind of cell (``driver`` in a cell's file)."""
