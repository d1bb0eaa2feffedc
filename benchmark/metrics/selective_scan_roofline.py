"""Op kernels (ops/pallas/selective_scan.py): the least time the step's
selective scans could take, forward and backward — the larger of the
recurrence's elementwise operations over the matrix unit's peak and the bytes
ANY implementation must read and write (``c``, ``delta``, ``B``, ``C``, ``dy``
in; ``y`` and their gradients out; ``A``, ``D`` once) over the memory's, from
the configuration's flops module (the generator puts the floor in ``facts``)
— over the device time of ``selective_scan`` and ``selective_scan_grad`` per
traced step.  The recurrence is elementwise work on the vector unit, which
neither peak describes: a low share is what it costs to be sequential."""

from benchmark.metrics import _types


def read(facts):
    floor = facts.get("selective_scan_floor_s")
    s = _types.seconds_per_step(facts, ("selective_scan",))
    if not floor or not s:
        return None
    return 100.0 * floor / s
