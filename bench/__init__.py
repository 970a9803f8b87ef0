"""On-chip benchmark of the packed CIFAR-10 BNN (see PERF.md)."""
