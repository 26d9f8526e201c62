"""On-chip benchmark of the Myrmics repo's serving engine (see README.md)."""
