"""Device (TPU v5e): what the fullest chip held at its peak, read after
the window with the program's state still alive, in GB (1e9 bytes) — the
result line's ``memory_peak_bytes`` (harness.memory_peak_bytes: the larger
of the runtime's ``peak_bytes_in_use`` and ``bytes_in_use +
bytes_reserved``, since a compiled program's temporaries are counted under
``bytes_reserved`` only).  Read as ``peak_hbm_gb.train`` and
``peak_hbm_gb.serve``."""


def read(facts):
    peak = facts.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
