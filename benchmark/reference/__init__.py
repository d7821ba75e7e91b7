"""Plain float32 references, one per architecture, written from the
published equations and found by the ``reference`` key of a
configuration's file.  They import nothing of the program."""
