"""Shared by the readers of the program's request spans
(monitor/tracing.RequestTrace, on during the traced stretch only)."""


def durations_ms(spans, name, distinct=None):
    """``dur_ms`` of the spans called ``name``; with ``distinct`` one per
    value of that attribute (every rider of a tick records the tick)."""
    seen, out = set(), []
    for s in spans or ():
        if s.get("name") != name:
            continue
        if distinct is not None:
            key = (s.get("attrs") or {}).get(distinct, s.get("mono_us"))
            if key in seen:
                continue
            seen.add(key)
        out.append(float(s["dur_ms"]))
    return out
