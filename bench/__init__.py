"""On-chip benchmark of PCCL's training path: harness, cells, reference."""
