"""The uHD chip benchmark: harness, drivers, metrics and references."""
