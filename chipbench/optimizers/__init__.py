"""Plain references of the optimizers the traffic mixes name."""
