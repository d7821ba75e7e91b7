"""Plain float32 references, one per configuration kind, written from the
published equations.  They import nothing of the program."""
