"""Op kernels (ops/attention.py, ops/pallas/streamed_attention.py): device
milliseconds per traced step of the first chip's operations whose innermost
Fluid scope is one of the WINDOW layers' ``fused_attention`` ops or of their
gradient ops — the scopes' names (``fluid[<type>]<first output>``,
``paddle_tpu/registry.py``) are the program's own, which the generator puts
in ``facts`` (``window_attention_scopes``).  Beside
``device_ms_per_step.attention`` it says what the window layers cost against
the full ones, which no reader by Fluid type can.  The reader reduces the
traced run's file itself (``_scopes`` keeps sums by type and group alone)."""

from benchmark.metrics import _scopes
from benchmark.trace import scopes


def read(facts):
    wanted = {scopes.fluid_scope(name) for name in
              facts.get("window_attention_scopes") or ()}
    got = _scopes.reading(facts)
    if not wanted or not got or not got["device"]:
        return None
    path = scopes.find_newest()
    trace = scopes.load(path)
    chips = sorted(i for i, d in trace["devices"].items() if d["ops"])
    ops = trace["devices"][chips[0]]["ops"]
    found = [own for op, own in zip(ops, scopes.self_times(ops))
             if scopes.fluid_scope(op[3]) in wanted]
    return sum(found) / 1e9 / got["steps"] * 1e3 if found else None
