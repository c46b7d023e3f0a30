"""The benchmark: its harness, yardsticks, references, cells and readers."""
