"""The harness: cell lookup, traffic generation, trace reduction, checks."""
