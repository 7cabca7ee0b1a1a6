"""Chip benchmark of the mapping search, the DSE sweep and the server."""
