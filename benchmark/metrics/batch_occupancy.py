"""Serving scheduler: mean share of the engine's slots that rode a decode
tick in the traced stretch (the ``active`` attribute of the decode spans)."""


def read(facts):
    seen = {}
    for s in facts.get("spans") or ():
        if s.get("name") == "decode":
            a = s.get("attrs") or {}
            seen[a.get("tick")] = a.get("active", 0)
    if not seen or not facts.get("slots"):
        return None
    return 100.0 * sum(seen.values()) / len(seen) / facts["slots"]
