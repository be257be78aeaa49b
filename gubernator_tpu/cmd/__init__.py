"""Command-line entry points (reference: cmd/ binaries)."""
