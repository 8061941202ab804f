"""One module per entry kind; each drives a cell's measured window."""
