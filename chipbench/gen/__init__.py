"""The benchmark's own copies of the paper's data generators (section IV).

They are copies, not imports, so that a later change to the program's
``repro.data`` cannot move the data a cell runs on.  Both return plain
numpy arrays; the harness hands them to the program in its own types.
"""
