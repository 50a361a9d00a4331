"""Plain references that decide ``correct``; independent of ``src/``."""
