"""Plain references, named by the configurations that use them."""
