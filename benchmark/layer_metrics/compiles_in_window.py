"""XLA backend compilations counted by the program's ``CompilationCounter``
between the first and the last measured step or request.  Must be 0: a run
with any is ``correct: false``."""


def read(observed):
    return observed.get("compiles_in_window")
