"""Compile caches (compile_cache.py): lowerings and compiles counted by
the program's compile_cache.count_compiles() inside the measured window;
anything but 0 also makes the run not correct.  Read as
``compiles_in_window.train`` and ``compiles_in_window.serve``."""


def read(facts):
    return facts.get("compiles_in_window")
