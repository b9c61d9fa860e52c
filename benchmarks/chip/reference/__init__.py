"""Plain references of the configured architectures (import nothing of
the program)."""
