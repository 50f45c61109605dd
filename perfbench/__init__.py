"""The repo's benchmark: harness, yardstick and data files (see README.md here)."""
