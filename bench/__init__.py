"""The chip benchmark of the BN-LSTM serving path (see PERF.md)."""
