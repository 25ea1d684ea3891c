"""The benchmark's own library: loading, traffic, window, references,
trace reduction."""
