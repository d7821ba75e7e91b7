"""The repo's benchmark: one cell per run, driven by the data files here.

Nothing outside this directory belongs to the yardstick; from the program
it takes only the system under test (``nnstreamer_tpu``'s pipeline strings
and the zoo hook), its ring spans and its program and kernel names.
"""
