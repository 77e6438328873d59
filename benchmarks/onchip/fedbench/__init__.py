"""On-chip benchmark harness for the federated round engine."""
