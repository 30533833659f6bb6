"""Plain float32 references of what the cells compute (no program imports)."""
