"""Plain float32 references of the model families, to check the program
against (ROADMAP R8)."""
