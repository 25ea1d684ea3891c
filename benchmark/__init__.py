"""The benchmark of cluster_tools_tpu: harness, cells and readers."""
