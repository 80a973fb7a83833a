"""On-chip benchmark of Sylvie full-graph GNN training (see PERF.md)."""
