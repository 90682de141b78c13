"""Plain float32 reference of the served model (imports nothing of the
program under test)."""
